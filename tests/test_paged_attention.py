"""Fused paged-attention decode kernel (ops/paged_attention.py).

The correctness spine of round 20's serving half: the Pallas kernel
(run INTERPRETED here — tier-1 forces the CPU platform; the real-chip
variants live in tests_tpu/test_paged_attention_tpu.py) must match the
XLA gather+attend reference to 1e-6 at every shape class the engine
produces — GQA llama heads, ragged ``seq_lens``, page-boundary lengths,
trash-page-0 padded lanes — and the reference itself must match the
pre-kernel ``cached_attention`` spelling exactly, so the engine-level
greedy-parity pins (tests/test_serve.py) transfer to the kernel path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.ops import paged_attention as pa
from distributedtraining_tpu.ops.attention import cached_attention


def _case(B, Hq, Hkv, D, P, MP, lens, *, pool=None, seed=0,
          tables=None):
    rng = np.random.default_rng(seed)
    pool = pool or (1 + B * MP)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    # one layer of the pool in its STORED shape: lane-dense Hkv*D rows
    kp = jnp.asarray(rng.standard_normal((pool, P, Hkv * D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((pool, P, Hkv * D)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, 1, Hkv, D)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, 1, Hkv, D)), jnp.float32)
    if tables is None:
        tables = rng.integers(1, pool, (B, MP))
    pt = jnp.asarray(tables, jnp.int32)
    sl = jnp.asarray(lens, jnp.int32)
    return q, kp, vp, pt, sl, kn, vn


def _parity(args, atol=1e-6):
    out = pa.paged_decode_attention(*args, interpret=True)
    assert out is not None, "kernel declined a supported shape"
    ref = pa.paged_decode_reference(*args)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < atol, f"kernel/reference divergence {err}"
    return out


# ---------------------------------------------------------------------------
# Kernel vs reference (interpret mode)
# ---------------------------------------------------------------------------

def test_kernel_matches_reference_gqa_ragged():
    """GQA llama heads (Hq=8 over Hkv=2) with ragged per-slot lengths —
    the llama serving shape class."""
    _parity(_case(3, 8, 2, 64, 8, 4, [13, 27, 5]))


def test_kernel_matches_reference_mha():
    """GPT-2 heads: Hkv == Hq (group size 1)."""
    _parity(_case(2, 4, 4, 32, 8, 4, [30, 2]))


def test_kernel_matches_reference_page_boundary_lengths():
    """Lengths at exact page multiples (0, P, MP*P-1): the mask edge
    sits on a DMA chunk edge; off-by-one here reads a dead page."""
    _parity(_case(4, 4, 2, 64, 8, 4, [0, 8, 16, 31]))


def test_kernel_matches_reference_multi_chunk():
    """MP > the pages of a chunk: the online softmax crosses chunk
    boundaries (the row's loop actually streams, the next chunk in
    flight)."""
    ppc = pa._chunk_pages(512, 8, 128, jnp.float32)
    assert 512 > 2 * ppc
    _parity(_case(2, 4, 2, 64, 8, 512, [2 * ppc * 8 + 5, ppc * 8]))


def test_trash_page_zero_lanes():
    """Padded batch lanes: table all-zeros (the trash page), seq_len 0.
    The lane's output must be attention over ONLY its fresh token —
    trash-page garbage must not leak (the engine's dead-lane
    contract)."""
    q, kp, vp, pt, sl, kn, vn = _case(2, 4, 2, 64, 8, 4, [0, 0])
    # poison the trash page to make leakage loud
    kp = kp.at[0].set(1e3)
    vp = vp.at[0].set(1e3)
    pt = jnp.zeros_like(pt)
    out = _parity((q, kp, vp, pt, sl, kn, vn))
    # seq_len 0: softmax over the single fresh column = exactly v_new
    vn_heads = jnp.repeat(vn, 2, axis=2)     # broadcast kv -> q heads
    np.testing.assert_allclose(np.asarray(out), np.asarray(vn_heads),
                               atol=1e-6)


def test_kernel_under_jit():
    """The engine calls through jit: trace-time decline/accept must be
    stable and the jitted output identical to eager."""
    args = _case(2, 4, 2, 64, 8, 4, [13, 27])
    eager = pa.paged_decode_attention(*args, interpret=True)
    jitted = jax.jit(
        lambda *a: pa.paged_decode_attention(*a, interpret=True))(*args)
    np.testing.assert_allclose(np.asarray(eager), np.asarray(jitted),
                               atol=1e-6)


def test_selection_is_by_backend_and_shape():
    """The model-facing entry selects from what it can observe: off-TPU
    it IS the XLA reference (tier-1 production path); the kernel itself
    never declines — a shape its layout cannot hold is a ValueError,
    and multi-token queries are such a shape (decode is one token per
    step)."""
    args = _case(2, 4, 2, 64, 8, 4, [13, 27])
    np.testing.assert_array_equal(
        np.asarray(pa.paged_attention(*args)),           # CPU backend
        np.asarray(pa.paged_decode_reference(*args)))
    q, kp, vp, pt, sl, kn, vn = args
    assert pa.kernel_supports(q, kp)
    q3 = jnp.concatenate([q, q, q], axis=1)
    assert not pa.kernel_supports(q3, kp)
    with pytest.raises(ValueError, match="unsupported shapes"):
        pa.paged_decode_attention(q3, kp, vp, pt, sl, kn, vn,
                                  interpret=True)
    # Hkv*D must fill whole 128-lane tiles (the tiny preset's 4x16 does
    # not): selected away from the kernel, never probed
    q_s, kp_s = q[..., :16], kp[..., :2 * 16]
    assert not pa.kernel_supports(q_s, kp_s)


# ---------------------------------------------------------------------------
# The reference vs the pre-kernel spelling (satellite: folded mask)
# ---------------------------------------------------------------------------

def _cached_attention_materialized_mask(q, k, v, ctx_lens):
    """The pre-round-20 cached_attention spelling: concatenated
    broadcast boolean mask + dot_product_attention — kept here as the
    oracle that the folded-iota rewrite changed no semantics."""
    from distributedtraining_tpu.ops.attention import \
        dot_product_attention
    B, Tq, _, _ = q.shape
    S = k.shape[1] - Tq
    ctx_valid = jnp.arange(S)[None, :] < ctx_lens[:, None]
    new_mask = jnp.tril(jnp.ones((Tq, Tq), bool))
    mask = jnp.concatenate(
        [jnp.broadcast_to(ctx_valid[:, None, :], (B, Tq, S)),
         jnp.broadcast_to(new_mask[None], (B, Tq, Tq))], axis=-1)
    return dot_product_attention(q, k, v, mask[:, None, :, :])


@pytest.mark.parametrize("Tq", [1, 3])
def test_cached_attention_folded_mask_matches_old_spelling(Tq):
    """The iota-compare mask fold is bit-for-bit the old concatenated
    mask: context valid below ctx_lens (0 and S included), trailing Tq
    causal among themselves and self-visible."""
    rng = np.random.default_rng(0)
    B, S, H, D = 3, 24, 2, 16
    q = jnp.asarray(rng.standard_normal((B, Tq, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S + Tq, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S + Tq, H, D)), jnp.float32)
    ctx_lens = jnp.asarray([0, 7, S], jnp.int32)
    new = cached_attention(q, k, v, ctx_lens)
    old = _cached_attention_materialized_mask(q, k, v, ctx_lens)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


def test_cached_attention_hlo_has_no_mask_concatenate():
    """The satellite's actual claim: the decode mask no longer exists
    as a concatenated broadcast buffer — no concatenate op over the
    mask shape in the lowered HLO (the k/v inputs still concatenate in
    the CALLER, not here)."""
    B, Tq, S, H, D = 4, 1, 64, 2, 16
    q = jnp.zeros((B, Tq, H, D), jnp.float32)
    k = jnp.zeros((B, S + Tq, H, D), jnp.float32)
    v = jnp.zeros((B, S + Tq, H, D), jnp.float32)
    lens = jnp.zeros((B,), jnp.int32)
    hlo = jax.jit(cached_attention).lower(q, k, v, lens).as_text()
    assert f"pred[{B},{Tq},{S + Tq}]" not in hlo


# ---------------------------------------------------------------------------
# Model wiring: the paged path is the gathered path, relocated
# ---------------------------------------------------------------------------

def test_model_kv_pages_matches_kv_ctx_gpt2():
    """One gpt2 decode step via the NEW kv_pages hook vs the legacy
    pre-gathered kv_ctx hook: same logits, same sown (k, v) — paging
    through the model is a memory-layout change, not a math change."""
    from distributedtraining_tpu.models import gpt2
    model, cfg = gpt2.make_model(gpt2.GPT2Config(
        vocab_size=64, n_positions=32, n_embd=32, n_layer=2, n_head=2,
        dtype="float32", vocab_multiple=64))
    params = model.init_params(jax.random.PRNGKey(0), seq_len=8)
    L, P, MP, B = cfg.n_layer, 8, 2, 2
    pool = 1 + B * MP
    rng = np.random.default_rng(1)
    kp = jnp.asarray(rng.standard_normal(
        (L, pool, P, cfg.n_head, cfg.head_dim)) * 0.1, jnp.float32)
    vp = jnp.asarray(rng.standard_normal(
        (L, pool, P, cfg.n_head, cfg.head_dim)) * 0.1, jnp.float32)
    tables = jnp.asarray(1 + np.arange(B * MP).reshape(B, MP), jnp.int32)
    seq_lens = jnp.asarray([5, 11], jnp.int32)
    tokens = jnp.asarray([[3], [7]], jnp.int32)

    paged, muts_p = model.apply(
        {"params": params}, tokens, position_ids=seq_lens[:, None],
        kv_pages=tuple((kp[i].reshape(pool, P, -1),
                        vp[i].reshape(pool, P, -1)) for i in range(L)),
        page_tables=tables, kv_lens=seq_lens,
        sow_kv=True, mutable=["intermediates"])
    k_ctx = kp[:, tables].reshape(L, B, MP * P, cfg.n_head, cfg.head_dim)
    v_ctx = vp[:, tables].reshape(L, B, MP * P, cfg.n_head, cfg.head_dim)
    gathered, muts_g = model.apply(
        {"params": params}, tokens, position_ids=seq_lens[:, None],
        kv_ctx=tuple((k_ctx[i], v_ctx[i]) for i in range(L)),
        kv_lens=seq_lens, sow_kv=True, mutable=["intermediates"])
    np.testing.assert_allclose(np.asarray(paged), np.asarray(gathered),
                               atol=1e-6)
    for name in muts_p["intermediates"]:
        kp_s, vp_s = muts_p["intermediates"][name]["kv_cache"][0]
        kg_s, vg_s = muts_g["intermediates"][name]["kv_cache"][0]
        np.testing.assert_allclose(np.asarray(kp_s), np.asarray(kg_s),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(vp_s), np.asarray(vg_s),
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# A suffix over a LONG paged context: blocks, no [Tq, context] tensor
# ---------------------------------------------------------------------------

def _suffix_case(B, Tq, Hq, Hkv, D, P, MP, lens, seed=0):
    q, kp, vp, pt, sl, _, _ = _case(B, Hq, Hkv, D, P, MP, lens, seed=seed)
    rng = np.random.default_rng(seed + 1)
    q = jnp.asarray(rng.standard_normal((B, Tq, Hq, D)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, Tq, Hkv, D)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, Tq, Hkv, D)), jnp.float32)
    return q, kp, vp, pt, sl, kn, vn


@pytest.mark.parametrize("MP, block, lens", [
    (8, 16, [37, 3]),       # whole blocks, one row's context ends early
    (7, 16, [56, 20]),      # the table is no whole number of blocks
    (4, 64, [32, 1]),       # one block holds the whole table
    (6, 8, [0, 48]),        # no context at all on a row
])
def test_blocked_suffix_attention_is_the_gathered_reference(MP, block, lens):
    args = _suffix_case(2, 5, 8, 2, 16, 8, MP, lens, seed=MP)
    ref = pa.paged_decode_reference(*args)
    out = pa.paged_suffix_attention(*args, block=block)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-6


def test_blocked_suffix_is_chosen_by_the_tables_reach_alone(monkeypatch):
    """A rule over the shape: below BLOCKED_MIN_CONTEXT positions the
    entry is the gathered reference, bit for bit (what every short-context
    engine ran before the blocked spelling existed); at or past it, the
    blocked one; one token a row is the decode path's either way."""
    args = _suffix_case(1, 3, 4, 2, 16, 8, 8, [50])
    assert 8 * 8 < pa.BLOCKED_MIN_CONTEXT
    np.testing.assert_array_equal(
        np.asarray(pa.paged_attention(*args)),
        np.asarray(pa.paged_decode_reference(*args)))
    called = []
    monkeypatch.setattr(pa, "BLOCKED_MIN_CONTEXT", 64)
    monkeypatch.setattr(
        pa, "paged_suffix_attention",
        lambda *a, **k: called.append(a[0].shape) or a[0])
    pa.paged_attention(*args)
    assert called == [(1, 3, 4, 16)]
    one = _case(1, 4, 2, 16, 8, 8, [50])
    pa.paged_attention(*one)
    assert len(called) == 1


def test_blocked_suffix_lowers_no_context_wide_score_tensor():
    """What it is for: at 64 heads x 256 rows over 32,768 positions the
    gathered spelling holds a [1, 64, 256, 33024] float32 score tensor
    (2 GiB); the blocked one's largest buffer is a block's."""
    q = jax.ShapeDtypeStruct((1, 256, 64, 128), jnp.bfloat16)
    pages = jax.ShapeDtypeStruct((4096, 16, 1024), jnp.bfloat16)
    new = jax.ShapeDtypeStruct((1, 256, 8, 128), jnp.bfloat16)
    text = jax.jit(pa.paged_attention).lower(
        q, pages, pages, jax.ShapeDtypeStruct((1, 2048), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32), new, new).as_text()
    assert "33024" not in text and "32768" not in text


# ---------------------------------------------------------------------------
# A window layer: a lower bound on every path, translation invariant
# ---------------------------------------------------------------------------

def _dense_window(args, window):
    """Dense masked attention over the gathered context and the fresh
    rows: fresh row t at position len + t sees positions in (len + t -
    window, len + t]. Written with a materialised mask, from the
    definition."""
    q, kp, vp, pt, sl, kn, vn = args
    B, Tq, Hq, D = q.shape
    P, Hkv = kp.shape[1], kp.shape[2] // D
    S = pt.shape[1] * P
    k = jnp.concatenate([kp[pt].reshape(B, S, Hkv, D), kn], axis=1)
    v = jnp.concatenate([vp[pt].reshape(B, S, Hkv, D), vn], axis=1)
    k, v = (jnp.repeat(x, Hq // Hkv, axis=2) for x in (k, v))
    pos = jnp.concatenate([jnp.broadcast_to(jnp.arange(S), (B, S)),
                           sl[:, None] + jnp.arange(Tq)[None]], axis=1)
    real = jnp.concatenate([jnp.arange(S)[None] < sl[:, None],
                            jnp.ones((B, Tq), bool)], axis=1)
    qpos = sl[:, None] + jnp.arange(Tq)[None]
    mask = (real[:, None, :] & (pos[:, None, :] <= qpos[:, :, None])
            & (pos[:, None, :] > qpos[:, :, None] - window))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# window 40 over pages of 8: a context under it, at it, one past it, and
# many windows long (with the chunk of 8 pages = 64 rows: chunks wholly
# behind the bound, a chunk the bound cuts, the newest chunk)
@pytest.mark.parametrize("lens", [[3, 39], [40, 41], [200, 129], [255, 64]])
def test_window_kernel_is_its_twin_is_dense_masked_attention(lens):
    args = _case(2, 8, 2, 64, 8, 32, lens, seed=sum(lens))
    want = _dense_window(args, 40)
    twin = pa.paged_decode_reference(*args, window=40)
    out = pa.paged_decode_attention(*args, interpret=True, window=40)
    assert float(jnp.max(jnp.abs(twin - want))) < 2e-6
    assert float(jnp.max(jnp.abs(out - twin))) < 1e-6
    if max(lens) > 41:
        plain = pa.paged_decode_reference(*args)
        assert float(jnp.max(jnp.abs(plain - twin))) > 1e-3


@pytest.mark.parametrize("lens", [[5, 0], [24, 23], [25, 70], [96, 41]])
def test_window_suffix_in_blocks_is_dense_masked_attention(lens):
    """A chunk of 7 fresh rows over a paged context, window 24: the
    chunk's first row behind, at and past the window's edge."""
    args = _suffix_case(2, 7, 8, 2, 16, 8, 12, lens, seed=sum(lens))
    want = _dense_window(args, 24)
    for got in (pa.paged_suffix_attention(*args, block=16, window=24),
                pa.paged_decode_reference(*args, window=24),
                pa.paged_attention(*args, window=24)):
        assert float(jnp.max(jnp.abs(got - want))) < 2e-6


def test_window_paths_read_lengths_from_the_tables_first_row():
    """Translation invariance, which the shifted table rests on: the same
    newest pages behind a table that starts LATER, with the lengths counted
    from its first row, give the same numbers."""
    q, kp, vp, pt, sl, kn, vn = _case(1, 4, 2, 64, 8, 32, [203], seed=9)
    whole = pa.paged_decode_attention(q, kp, vp, pt, sl, kn, vn,
                                      interpret=True, window=40)
    # positions 160.. are pages 20..: a table of 8 entries from page 20
    moved = pa.paged_decode_attention(q, kp, vp, pt[:, 20:28], sl - 160, kn,
                                      vn, interpret=True, window=40)
    assert float(jnp.max(jnp.abs(moved - whole))) < 1e-6


def test_window_none_lowers_to_what_stood():
    args = _case(2, 8, 2, 64, 8, 16, [50, 9])
    suffix = _suffix_case(1, 5, 8, 2, 16, 8, 16, [50])
    for fn, a in ((pa.paged_decode_reference, args),
                  (pa.paged_suffix_attention, suffix),
                  (pa.paged_attention, args), (pa.paged_attention, suffix)):
        assert str(jax.make_jaxpr(fn)(*a)) == str(jax.make_jaxpr(
            lambda *x, f=fn: f(*x, window=None))(*a))
    plain = pa._build_call(2, 4, 128, 64, 8, 16, jnp.float32, jnp.float32,
                           True)
    windowed = pa._build_call(2, 4, 128, 64, 8, 16, jnp.float32, jnp.float32,
                              True, 40)
    text = [str(jax.make_jaxpr(c)(args[3], args[4], jnp.zeros((2, 8, 128)),
                                  args[1], args[2], jnp.zeros((2, 128)),
                                  jnp.zeros((2, 128))))
            for c in (plain, windowed)]
    assert "name=paged_decode_attention" in text[0]
    assert "name=paged_window_decode_attention" in text[1]


# ---------------------------------------------------------------------------
# The four served head shapes, both pool dtypes, the lengths that cut a
# page, a chunk and a table
# ---------------------------------------------------------------------------

# Hq, Hkv, D of the cells that run the kernel: gpt2-large (G 1), nemotron
# (G 16), solar (G 8), trinity (G 8; its sliding layers take a window)
SERVED = {"large": (20, 20, 64), "nemotron": (32, 2, 128),
          "solar": (64, 8, 128), "trinity": (32, 4, 128)}


def _served_case(name, dtype, MP, lens, seed=0):
    Hq, Hkv, D = SERVED[name]
    args = _case(len(lens), Hq, Hkv, D, 16, MP, lens, seed=seed,
                 pool=1 + sum(-(-n // 16) for n in lens) + 3)
    q, kp, vp, pt, sl, kn, vn = args
    return tuple(x.astype(dtype) for x in (q, kp, vp)) + (pt, sl) + tuple(
        x.astype(dtype) for x in (kn, vn))


def _agree(out, ref, dtype):
    """float32: the module's parity contract. bfloat16: the twin rounds
    the NORMALISED probabilities to bfloat16, the kernel the unnormalised
    ones (as every flash kernel), and both round the output: a few
    bfloat16 steps of a value of order 1."""
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < (1e-6 if dtype == jnp.float32 else 4e-2), err


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name, window", [
    ("large", None), ("nemotron", None), ("solar", None), ("trinity", None),
    ("trinity", 300)])
def test_kernel_is_its_twin_at_the_served_head_shapes(name, window, dtype):
    """Lengths: a dead row, 1, a page's edge, a chunk's edge - 1 / at / +
    1, and a short context under a table far wider; the table (136 pages)
    is no whole number of chunks of 16 or 32 pages (every case but
    gpt2-large's float32 pool, 8 pages a chunk)."""
    HD = SERVED[name][1] * SERVED[name][2]
    chunk = 16 * pa._chunk_pages(136, 16, HD, dtype)
    assert 136 * 16 > 2 * chunk + 1
    lens = [0, 1, 16, chunk - 1, chunk, chunk + 1, 2 * chunk + 1, 40]
    args = _served_case(name, dtype, 136, lens, seed=len(name))
    kw = {} if window is None else {"window": window}
    out = pa.paged_decode_attention(*args, interpret=True, **kw)
    _agree(out, pa.paged_decode_reference(*args, **kw), dtype)


@pytest.mark.parametrize("window", [None, 40])
def test_pages_the_context_does_not_name_are_never_read(window):
    """Every page of the pool that no live position lies in holds NaN (the
    trash page, the tables' dead entries, pages behind a window's reach):
    the output is finite and bit-equal to a clean pool's. The twin reads
    them (0 x NaN), so it cannot be the reference here."""
    lens = [0, 5, 16, 100, 700]
    args = _served_case("trinity", jnp.float32, 136, lens, seed=7)
    q, kp, vp, pt, sl, kn, vn = args
    kw = {} if window is None else {"window": window}
    clean = pa.paged_decode_attention(*args, interpret=True, **kw)
    named = np.zeros(kp.shape[0], bool)
    for row, n in enumerate(lens):
        first = 0 if window is None else max(n - window + 1, 0) // 16
        named[np.asarray(pt[row, first:-(-n // 16)])] = True
    poison = jnp.where(jnp.asarray(named)[:, None, None], 0.0, jnp.nan)
    # dead table entries point at unnamed pages too
    pt = jnp.where(jnp.arange(pt.shape[1])[None] * 16 < sl[:, None], pt, 0)
    dirty = pa.paged_decode_attention(q, kp + poison, vp + poison, pt, sl,
                                      kn, vn, interpret=True, **kw)
    assert bool(jnp.all(jnp.isfinite(dirty)))
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))


def test_chunk_is_chosen_from_the_rows_width_and_the_pools_dtype():
    """No name, no argument: 512 bfloat16 lanes or fewer walk 1,024
    positions a chunk, 1,024 lanes 512 (1 MiB a buffer), 1,280 lanes 256,
    a float32 pool half of a bfloat16 one's; never more pages than the
    table holds."""
    assert pa._chunk_pages(2560, 16, 1024, jnp.bfloat16) == 32
    assert pa._chunk_pages(64, 16, 1280, jnp.bfloat16) == 16
    assert pa._chunk_pages(2112, 16, 512, jnp.bfloat16) == 64
    assert pa._chunk_pages(128, 16, 256, jnp.bfloat16) == 64
    assert pa._chunk_pages(64, 16, 1280, jnp.float32) == 8
    assert pa._chunk_pages(4, 8, 128, jnp.float32) == 4
