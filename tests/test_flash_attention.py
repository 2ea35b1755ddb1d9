"""Flash-attention selection rule on the CPU lane.

The suite's conftest forces the CPU platform (virtual 8-device mesh), where
the rule never selects the Pallas kernel; its numerics against the dense
oracle run on the chip (tests_tpu/test_flash_attention_tpu.py). What is
pinned here is the rule itself, the XLA path the caller takes when it
says no, and the kernel's schedule as a pure function of T: which blocks,
and which (q block, kv block) pairs a causal mask leaves to run.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.ops import flash_attention as fl
from distributedtraining_tpu.ops.attention import causal_attention


def _qkv(B=2, T=512, H=4, D=64, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
                 for _ in range(3))


def test_rule_is_backend_and_shape():
    q, k, v = _qkv(T=512)
    assert fl.supports(q, None)
    assert fl.flash_attention(q, k, v) is None           # CPU backend
    assert not fl.supports(_qkv(T=128)[0], None)         # short
    assert not fl.supports(_qkv(T=320)[0], None)         # unaligned
    assert not fl.supports(_qkv(D=32)[0], None)          # lane tiling
    assert not fl.supports(q, jnp.ones(q.shape[:2], jnp.int32))  # padding


def test_flash_impl_takes_xla_path_off_tpu():
    q, k, v = _qkv(T=256)
    out = causal_attention(q, k, v, impl="flash")
    ref = causal_attention(q, k, v, impl="dense")
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))


def _pairs_share(info):
    """Of a kernel's (q block, kv block) pairs, the share its block table
    leaves to run (0 = the mask covers the pair whole)."""
    table = np.asarray(info.block_mask)
    return np.count_nonzero(table) / table.size


@pytest.mark.parametrize("T", [256, 384, 512, 640, 768, 1024, 1536, 2048,
                               4096])
def test_schedule_blocks_divide_T(T):
    """For every T the rule accepts, every block of the forward and of the
    backward divides T, is a whole number of 128-lane tiles, and the
    kernel's tables can be built (the library refuses blocks that do not
    divide)."""
    assert fl.supports(_qkv(B=1, T=T, H=1)[0], None)
    b = fl._block_sizes(T)
    assert b.use_fused_bwd_kernel and b.has_backward_blocks
    for name in ("block_q", "block_kv", "block_kv_compute", "block_q_dkv",
                 "block_kv_dkv", "block_kv_dkv_compute"):
        size = getattr(b, name)
        assert T % size == 0 and size % 128 == 0, (name, size)
    fl._causal_kernel(T, 2)


@pytest.mark.parametrize("T, most", [(256, 1.0), (512, 1.0), (1024, 0.75),
                                     (2048, 0.625), (4096, 0.5625)])
def test_schedule_runs_only_the_causal_pairs(T, most):
    """The share of block pairs a causal mask leaves to run is a constant
    of (T, blocks): at T = 1024 at most 0.75 in the forward and in the
    backward, and no pair above the diagonal in either table. One q block
    of T rows (what stood here before) would read 1.0."""
    kernel = fl._causal_kernel(T, 2)
    assert kernel.dq_mask_info is None          # the fused backward
    fwd, dkv = kernel.fwd_mask_info, kernel.dkv_mask_info
    assert _pairs_share(fwd) <= most and _pairs_share(dkv) <= most
    # [heads, q blocks, kv blocks]: nothing above the diagonal runs, every
    # pair strictly below it runs unmasked (2), the diagonal masks (1)
    for table in (np.asarray(fwd.block_mask)[0],
                  np.asarray(dkv.block_mask)[0]):
        assert not np.triu(table, 1).any()
        assert (np.diag(table) == 1).all()
        assert (table[np.tril_indices_from(table, -1)] == 2).all()


# -- block tables from the rows' segment ids ---------------------------------

def _packing(T, seed, rows=2, shortest=64):
    """[rows, T] ids of documents of Pareto lengths (shape 1.2) packed with
    no padding, `gen.packed_batches`' law: boundaries off every block grid."""
    rng = np.random.default_rng(seed)
    lens = (shortest * (1.0 + rng.pareto(1.2, (rows, T // shortest)))
            ).astype(np.int64)
    return np.stack([np.repeat(np.arange(r.size), r)[:T]
                     for r in lens]).astype(np.int32)


def _shuffled(seg, seed):
    """The same documents under ids that rise AND fall along the row."""
    perm = np.random.default_rng(seed).permutation(seg.max() + 1)
    return perm[seg].astype(np.int32)


def _brute_pairs(seg_q, seg_kv, block):
    """[T / block, T / block]: does the block pair hold a (same segment,
    causal) pair? From the [T, T] mask itself, one row of a batch."""
    T = seg_q.size
    allowed = np.tril(seg_q[:, None] == seg_kv[None, :])
    n = T // block
    return allowed.reshape(n, block, n, block).any((1, 3))


def _listed(needed, forward):
    """The pair list of `needed` ([B, n, n] booleans, [row, q block, kv
    block]) as numpy: (row, q block, kv block, edges) of its `count` pairs,
    and the filler after them. The forward lists q-major, the backward the
    transpose (kv-major)."""
    arg = needed if forward else needed.swapaxes(1, 2)
    row, major, minor, edges, count = (
        np.asarray(x) for x in fl._pair_list(jnp.asarray(arg)))
    count = int(count)
    q, kv = (major, minor) if forward else (minor, major)
    filler = np.stack([row, q, kv])[:, count:]
    return (row[:count], q[:count], kv[:count], edges[:count]), filler


def _ran(needed, forward):
    """[B, n, n] booleans: the pairs the list visits, each once."""
    (row, q, kv, _), _ = _listed(needed, forward)
    ran = np.zeros(needed.shape, np.int64)
    np.add.at(ran, (row, q, kv), 1)
    assert ran.max() <= 1
    return ran.astype(bool)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("T, block", [(2048, 128), (2048, 512),
                                      (8192, 128), (8192, 512)])
def test_table_from_segment_ids_is_the_brute_force_one(T, block, seed):
    """On the packer's ids the pairs that run, forward and kv-major, are
    exactly the pairs that hold an allowed (same segment, causal) pair."""
    seg = _packing(T, seed)
    needed = np.asarray(fl.needed_pairs(seg, seg, block))
    want = np.stack([_brute_pairs(row, row, block) for row in seg])
    np.testing.assert_array_equal(needed, want)
    np.testing.assert_array_equal(_ran(needed, True), want)
    np.testing.assert_array_equal(_ran(needed, False), want)
    if T == 8192:
        # most of the causal half is left out (the replay's 27.7% at 512)
        n = T // block
        assert needed.sum() < 0.6 * seg.shape[0] * n * (n + 1) / 2


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("T, block", [(2048, 128), (2048, 512),
                                      (8192, 512)])
@pytest.mark.parametrize("kv_differs", [False, True])
def test_table_skips_no_pair_it_needs_on_any_ids(T, block, seed, kv_differs):
    """Ids that DO decrease (shuffled segments), and kv ids that are not
    the q ids: the list may run too much, never too little."""
    seg_q = _shuffled(_packing(T, seed), seed)
    seg_kv = _shuffled(_packing(T, seed + 7), seed) if kv_differs else seg_q
    needed = np.asarray(fl.needed_pairs(seg_q, seg_kv, block))
    want = np.stack([_brute_pairs(q, kv, block)
                     for q, kv in zip(seg_q, seg_kv)])
    assert not (want & ~needed).any()
    assert not (want & ~_ran(needed, True)).any()
    assert not (want & ~_ran(needed, False)).any()
    for row in needed:
        assert not np.triu(row, 1).any() and row.diagonal().all()


@pytest.mark.parametrize("T", [2048, 4096, 8192])
def test_a_row_of_one_document_gives_the_static_causal_table(T):
    """One document a row: the list is the pairs the library's constants
    run, forward and backward."""
    kernel = fl._causal_kernel(T, 2)
    block = fl._block_sizes(T).block_q
    seg = np.zeros((1, T), np.int32)
    needed = np.asarray(fl.needed_pairs(seg, seg, block))
    for info, forward in ((kernel.fwd_mask_info, True),
                          (kernel.dkv_mask_info, False)):
        static = np.asarray(info.block_mask)[0] > 0
        np.testing.assert_array_equal(_ran(needed, forward)[0], static)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("shuffle", [False, True])
def test_the_list_is_in_order_and_its_edges_mark_the_groups(seed, shuffle):
    """Forward: row, then q block, then kv block, ascending, so a q block's
    pairs follow each other and its diagonal comes last (the softmax sees a
    key of its own before it writes); bit 0 / 1 of `edges` mark a q
    block's first / last pair. Backward: row, kv block, q block, a kv
    block's diagonal first; bits 2 / 3 a row's first / last pair (where dQ
    is zeroed and written). Nothing sits above the diagonal, and the filler
    past `count` names the last pair again."""
    seg = _packing(4096, seed)
    if shuffle:
        seg = _shuffled(seg, seed)
    needed = np.asarray(fl.needed_pairs(seg, seg, 128))
    n = needed.shape[-1]
    for forward in (True, False):
        (row, q, kv, edges), filler = _listed(needed, forward)
        assert (kv <= q).all()
        major, minor = (q, kv) if forward else (kv, q)
        key = (row * n + major) * n + minor
        assert (np.diff(key) > 0).all()
        group = row * n + major
        starts = np.flatnonzero(np.diff(group, prepend=-1))
        ends = np.flatnonzero(np.diff(group, append=-1))
        assert len(starts) == len(ends) == needed.shape[0] * n
        np.testing.assert_array_equal(np.flatnonzero(edges & 1), starts)
        np.testing.assert_array_equal(np.flatnonzero(edges & 2), ends)
        diagonal = ends if forward else starts
        assert (q[diagonal] == kv[diagonal]).all()
        np.testing.assert_array_equal(
            np.flatnonzero(edges & 4),
            np.flatnonzero(np.diff(row, prepend=-1)))
        np.testing.assert_array_equal(
            np.flatnonzero(edges & 8),
            np.flatnonzero(np.diff(row, append=-1)))
        assert filler.shape[1] == needed.shape[0] * n * (n + 1) // 2 - len(q)
        assert (filler == np.array([row[-1], q[-1], kv[-1]])[:, None]).all()


def _pallas_calls(fn, *args):
    import jax

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize("T, packed, H, D, engages", [
    (1024, True, 2, 64, True),      # train-large-t1024: two blocks a row
    (8192, False, 2, 64, False),    # no ids: nothing to read a table from
    (512 * (fl.TABLE_MIN_BLOCKS - 1), True, 2, 64, False),
    (512 * fl.TABLE_MIN_BLOCKS, True, 4, 64, True),
    (8192, True, 2, 64, True),
    (1024, False, 2, 64, False),    # GPT-2's shape without ids
    (1024, True, 1, 64, False),     # one head of 64 fills half a lane block
    (1024, True, 3, 64, False),     # an odd head count: 192 lanes
    (1024, True, 1, 128, True),     # a head of 128 is a lane block
    (1024, True, 3, 128, True),
    (768, True, 2, 64, True)])      # three blocks of 256
def test_the_table_engages_with_ids_at_or_over_the_threshold(
        T, packed, H, D, engages, monkeypatch):
    """Without ids, under `TABLE_MIN_BLOCKS` blocks a row and where the
    heads do not fill whole 128-lane blocks the kernel is the library's,
    heads first, mapped over the rows, its tables `_causal_kernel`'s
    constants; elsewhere ONE call of this module's kernel over all rows'
    pairs, its grid's length the count of them (an operand, not a
    constant), its operands rows-major."""
    import jax
    monkeypatch.setattr(fl, "_on_tpu", lambda: True)
    B = 2
    seg = jnp.asarray(_packing(T, 0, rows=B)) if packed else None
    assert fl._table_engages(T, H, D, seg) == engages
    q = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16)
    calls = _pallas_calls(
        lambda q, k, v: fl.flash_attention(q, k, v, segment_ids=seg),
        q, q, q)
    assert [c.params["name"].startswith("flash_mha_fwd") for c in calls] \
        == [True]
    assert bool(calls[0].params["grid_mapping"].num_dynamic_grid_bounds) \
        == engages
    assert ((B, H, T, D) in [v.aval.shape for v in calls[0].invars]) \
        != engages
    pairs = fl.block_pairs(q, None, seg)
    assert (pairs is not None) == engages


def test_the_rule_stops_where_a_row_outgrows_the_chips_small_memories():
    """The backward holds a head's whole dQ row in VMEM and the kernels the
    pair list in scalar memory: past `TABLE_MAX_T` tokens a row the
    library's kernels stand."""
    seg = np.zeros((1, 8), np.int32)        # the rule reads T, not the ids
    assert fl._table_engages(fl.TABLE_MAX_T, 2, 64, seg)
    assert not fl._table_engages(2 * fl.TABLE_MAX_T, 2, 64, seg)
    assert fl._vmem_limit(fl.TABLE_MAX_T, 128, True) <= 32 * 2 ** 20


# a row of blocks of 128 just over the threshold: the smallest shape the
# rule hands a pair list, at a size the interpreter runs in seconds
_T_SMALL = 128 * (fl.TABLE_MIN_BLOCKS + 1)


@pytest.fixture
def interpreted():
    fl.use_interpret(True)
    try:
        yield
    finally:
        fl.use_interpret(False)


def _with_gradients(fn, q, k, v, do):
    import jax
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(do)


@pytest.mark.parametrize("ids, dtype", [
    ("packed", "float32"), ("shuffled", "float32"),
    ("kv_differs", "float32"), ("packed", "bfloat16")])
def test_interpreted_kernel_with_the_table_is_dense_and_the_static_one(
        ids, dtype, interpreted, monkeypatch):
    """Output and the three gradients: `dot_product_attention` with the
    same ids to the dtype's rounding, and the library's kernel over the
    causal constants BIT FOR BIT (a pair the list leaves out is one whose
    scores the segment mask sets to the mask value whole: where it ran it
    added exact zeros). In bfloat16 that holds for the output, dK and dV;
    dQ adds up in float32 here, where the library rounds every kv block's
    share to bfloat16 and sums those, so the two differ by a rounding."""
    from distributedtraining_tpu.ops.attention import (
        combine_masks, dot_product_attention, make_causal_mask)
    B, T, H, D = 2, _T_SMALL, 2, 64
    rng = np.random.default_rng(3)
    q, k, v, do = (jnp.asarray(rng.standard_normal((B, T, H, D)),
                               dtype) for _ in range(4))
    seg_q = _packing(T, 5, shortest=32)
    if ids != "packed":
        seg_q = _shuffled(seg_q, 5)
    seg_kv = seg_q
    if ids == "kv_differs":
        # a row must see one key at least, or dense and kernel each give
        # their own garbage: a document's first key keeps the q id
        first = np.diff(seg_q, axis=1, prepend=-1) != 0
        seg_kv = np.where(first | (rng.random(seg_q.shape) < 0.5), seg_q,
                          _shuffled(_packing(T, 6), 5))
    seg_q, seg_kv = jnp.asarray(seg_q), jnp.asarray(seg_kv)
    assert fl._table_engages(T, H, D, seg_q)
    if ids == "packed":
        run, causal = fl.block_pairs(q, None, seg_q)
        assert int(run) < int(causal)       # some pair is left out

    def flash(q, k, v):
        return fl.flash_attention(q, k, v, segment_ids=seg_q,
                                  kv_segment_ids=seg_kv)

    def dense(q, k, v):
        mask = combine_masks(make_causal_mask(T), None, seg_q, seg_kv)
        return dot_product_attention(q, k, v, mask)

    f32 = lambda x: np.asarray(x, np.float32)
    exact = dtype == "float32"
    got = _with_gradients(flash, q, k, v, do)
    # the forward alone (an eval) is the kernel that keeps no log-sum-exp
    np.testing.assert_array_equal(f32(flash(q, k, v)), f32(got[0]))
    for g, want in zip(got, _with_gradients(dense, q, k, v, do)):
        np.testing.assert_allclose(f32(g), f32(want), rtol=0,
                                   atol=2e-5 if exact else 2 ** -5)
    monkeypatch.setattr(fl, "TABLE_MIN_BLOCKS", T)       # never engages
    assert not fl._table_engages(T, H, D, seg_q)
    static = _with_gradients(lambda *a: flash(*a), q, k, v, do)
    for name, g, want in zip(("out", "dq", "dk", "dv"), got, static):
        if name in ("out", "dv") or (name == "dk" and not exact):
            np.testing.assert_array_equal(f32(g), f32(want), err_msg=name)
        elif exact:
            # `di` is summed as a product with a 0 / 1 matrix here: in
            # float32 another order of the same sum (exact in bfloat16)
            np.testing.assert_allclose(f32(g), f32(want), rtol=0, atol=1e-5)
        else:
            # a rounding of the largest share, not of the sum
            np.testing.assert_allclose(f32(g), f32(want), rtol=2 ** -7,
                                       atol=2 ** -6)


def test_block_pairs_counts_what_the_tables_run(interpreted):
    T = _T_SMALL
    seg = jnp.asarray(_packing(T, 9, shortest=16))
    q = jnp.zeros((2, T, 2, 64), jnp.float32)
    run, causal = fl.block_pairs(q, None, seg)
    n = T // 128
    assert int(causal) == 2 * n * (n + 1) // 2
    assert int(run) == sum(_brute_pairs(row, row, 128).sum()
                           for row in np.asarray(seg))
    assert int(run) < int(causal)
    one = jnp.zeros((2, T), jnp.int32)
    run, causal = fl.block_pairs(q, None, one)
    assert int(run) == int(causal)
    assert fl.block_pairs(q, None, None) is None
    assert fl.block_pairs(q, jnp.ones((2, T), jnp.int32), seg) is None
    # a head of 64 alone fills half a lane block: the library's kernels
    assert fl.block_pairs(q[:, :, :1], None, seg) is None


# -- the rows-major kernels: [B, T, H D] operands, two heads of 64 a block ----

def _documents(lengths):
    """[rows, T] ids from each row's document lengths."""
    return jnp.asarray(np.stack([np.repeat(np.arange(len(row)), row)
                                 for row in lengths]).astype(np.int32))


def _dense_oracle(seg):
    from distributedtraining_tpu.ops.attention import (
        combine_masks, dot_product_attention, make_causal_mask)

    def dense(q, k, v):
        mask = combine_masks(make_causal_mask(q.shape[1]), None, seg)
        return dot_product_attention(q, k, v, mask)
    return dense


def _against_dense_and_the_library(q, k, v, do, seg, monkeypatch):
    """out, dq, dk, dv of the rows-major kernels: dense to bfloat16's
    rounding; the library's kernel bit for bit in `out` and dV, to a
    rounding in dQ and in a few elements of dK."""
    T, H, D = q.shape[1:]
    f32 = lambda x: np.asarray(x, np.float32)

    def flash(q, k, v):
        return fl.flash_attention(q, k, v, segment_ids=seg)

    assert fl._table_engages(T, H, D, seg)
    got = _with_gradients(flash, q, k, v, do)
    for g, want in zip(got, _with_gradients(_dense_oracle(seg), q, k, v, do)):
        np.testing.assert_allclose(f32(g), f32(want), rtol=0, atol=2 ** -5)
    monkeypatch.setattr(fl, "TABLE_MIN_BLOCKS", T)       # the library's
    assert not fl._table_engages(T, H, D, seg)
    for name, g, want in zip(("out", "dq", "dk", "dv"), got,
                             _with_gradients(flash, q, k, v, do)):
        if name in ("out", "dv"):
            np.testing.assert_array_equal(f32(g), f32(want), err_msg=name)
        else:
            # dq: a float32 sum here, a sum of bfloat16 shares there; dq
            # and dk: `di` summed in another order (a product with the
            # heads' 0 / 1 matrix), which turns a rounding in a few
            np.testing.assert_allclose(f32(g), f32(want), rtol=2 ** -7,
                                       atol=2 ** -6, err_msg=name)
            if name == "dk":
                assert (f32(g) != f32(want)).mean() < 1e-4


@pytest.mark.parametrize("H, Hkv, D", [
    (20, 20, 64),       # gpt2-large: ten lane blocks of two heads
    (32, 8, 64),        # LFM2: 32 query heads over 8 repeated K / V heads
    (2, 2, 128),        # a head of 128 is a lane block
    (4, 4, 64)])
def test_rows_major_kernels_are_dense_and_the_library(
        H, Hkv, D, interpreted, monkeypatch):
    B, T = 1, _T_SMALL
    rng = np.random.default_rng(H)
    q, do = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
             for _ in range(2))
    k, v = (jnp.repeat(jnp.asarray(rng.standard_normal((B, T, Hkv, D)),
                                   jnp.bfloat16), H // Hkv, axis=2)
            for _ in range(2))
    seg = jnp.asarray(_packing(T, 4, rows=B, shortest=32))
    _against_dense_and_the_library(q, k, v, do, seg, monkeypatch)


def test_two_blocks_a_row_gpt2s_shape(interpreted, monkeypatch):
    """T = 1,024 is two blocks of 512: a document boundary inside a block, a
    row of one document (3 of 3 causal pairs), and a row whose second block
    shares no document with its first (2 of 3: the pair below the diagonal
    is left out, and nothing it would have added is missed)."""
    T, H, D = 1024, 2, 64
    seg = _documents([[300, 724], [1024], [512, 512]])
    rng = np.random.default_rng(8)
    q, k, v, do = (jnp.asarray(rng.standard_normal((3, T, H, D)),
                               jnp.bfloat16) for _ in range(4))
    run, causal = fl.block_pairs(q, None, seg)
    assert (int(run), int(causal)) == (3 + 3 + 2, 9)
    _against_dense_and_the_library(q, k, v, do, seg, monkeypatch)


def test_the_two_heads_of_a_lane_block_do_not_leak(interpreted):
    """Heads 2c and 2c + 1 share a 128-lane block and a grid step: a change
    to one head's v (its `do`) leaves the other head's output (gradients)
    as they were, bit for bit."""
    B, T, H, D = 1, _T_SMALL, 4, 64
    rng = np.random.default_rng(12)
    q, k, v, do = (jnp.asarray(rng.standard_normal((B, T, H, D)),
                               jnp.bfloat16) for _ in range(4))
    seg = jnp.asarray(_packing(T, 2, rows=B, shortest=32))

    def flash(q, k, v):
        return fl.flash_attention(q, k, v, segment_ids=seg)

    f32 = lambda x: np.asarray(x, np.float32)
    base = [f32(x) for x in _with_gradients(flash, q, k, v, do)]
    for head in range(H):
        other = [h for h in range(H) if h != head]
        v2 = v.at[:, :, head].multiply(-1.5)
        do2 = do.at[:, :, head].add(1.0)
        out = f32(flash(q, k, v2))
        assert (out[:, :, head] != base[0][:, :, head]).any()
        np.testing.assert_array_equal(out[:, :, other],
                                      base[0][:, :, other])
        for g, want in zip(_with_gradients(flash, q, k, v, do2)[1:],
                           base[1:]):
            g = f32(g)
            assert (g[:, :, head] != want[:, :, head]).any()
            np.testing.assert_array_equal(g[:, :, other], want[:, :, other])


@pytest.mark.parametrize("H, D", [(4, 64), (2, 128)])
def test_the_fused_entry_is_the_split_entry_bit_for_bit(H, D, interpreted):
    """`flash_attention_qkv` on c_attn's `[B, T, 3E]` against
    `flash_attention` on its three parts: the same kernels on the same
    blocks (an index map adds E / 128 and 2E / 128 lane blocks), so output
    and gradients are equal to the bit."""
    B, T, E = 2, _T_SMALL, H * D
    rng = np.random.default_rng(21)
    qkv = jnp.asarray(rng.standard_normal((B, T, 3 * E)), jnp.bfloat16)
    do = jnp.asarray(rng.standard_normal((B, T, E)), jnp.bfloat16)
    seg = jnp.asarray(_packing(T, 6, rows=B, shortest=32))

    def fused(qkv):
        return fl.flash_attention_qkv(qkv, H, segment_ids=seg)

    def split(qkv):
        q, k, v = (x.reshape(B, T, H, D) for x in jnp.split(qkv, 3, -1))
        return fl.flash_attention(q, k, v, segment_ids=seg).reshape(B, T, E)

    import jax
    for fn in (fused, split):
        out, vjp = jax.vjp(fn, qkv)
        got = (out, vjp(do)[0])
        if fn is fused:
            want = got
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
    # the rule's other side: no ids (the library's kernels on the three
    # parts), a padding mask (no kernel)
    np.testing.assert_array_equal(
        np.asarray(fl.flash_attention_qkv(qkv, H), np.float32),
        np.asarray(fl.flash_attention(
            *(x.reshape(B, T, H, D) for x in jnp.split(qkv, 3, -1))
        ).reshape(B, T, E), np.float32))
    assert fl.flash_attention_qkv(
        qkv, H, attention_mask=jnp.ones((B, T), jnp.int32)) is None


def _equations(jaxpr):
    """Every equation outside the kernels' own bodies."""
    import jax
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations(sub)


@pytest.mark.parametrize("entry", ["fused", "split"])
def test_nothing_lies_between_the_projections_and_the_kernels(
        entry, monkeypatch):
    """Traced at gpt2-large's shape (B=4, T=1,024, 20 heads of 64): in the
    forward NOTHING but the kernel touches an array as large as q (fused:
    `c_attn`'s `[B, T, 3E]` goes in as it is; split: a free reshape of each
    `[B, T, H, D]` and of the result); no kernel takes or gives a 4-D
    array; under `jax.vjp` what is added is `di` (a product of `out * do`
    with the heads' 0 / 1 matrix, and the one `transpose`, of its
    `[B, T, H]`, a 64th of q), the names remat keeps by, and, fused only,
    dq | dk | dv side by side for `c_attn`'s backward."""
    import jax
    monkeypatch.setattr(fl, "_on_tpu", lambda: True)
    B, T, H, D = 4, 1024, 20, 64
    E = H * D
    seg = jax.ShapeDtypeStruct((B, T), jnp.int32)
    if entry == "fused":
        x = (jax.ShapeDtypeStruct((B, T, 3 * E), jnp.bfloat16),)

        def fn(qkv, seg):
            return fl.flash_attention_qkv(qkv, H, segment_ids=seg)
    else:
        x = (jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16),) * 3

        def fn(q, k, v, seg):
            return fl.flash_attention(q, k, v, segment_ids=seg)

    def with_gradients(*args):
        out, vjp = jax.vjp(lambda *a: fn(*a, args[-1]), *args[:-1])
        return out, vjp(out)

    def touching_q_sized(traced):
        return {e.primitive.name for e in traced
                if max(v.aval.size for v in e.invars + e.outvars) >= B * T * E}

    free = {"reshape"} if entry == "split" else set()
    forward = list(_equations(jax.make_jaxpr(fn)(*x, seg).jaxpr))
    assert touching_q_sized(forward) == {
        "custom_vjp_call", "jit", "pallas_call"} | free
    both = list(_equations(jax.make_jaxpr(with_gradients)(*x, seg).jaxpr))
    assert touching_q_sized(both) == {
        "jit", "pallas_call", "name", "convert_element_type", "mul",
        "dot_general"} | free | ({"concatenate"} if entry == "fused"
                                 else set())
    calls = [e for e in both if e.primitive.name == "pallas_call"]
    assert len(calls) == 2
    for e in calls:
        assert max(len(v.aval.shape) for v in e.invars + e.outvars) == 3
    assert max(v.aval.size for e in both if e.primitive.name == "transpose"
               for v in e.invars) == B * T * H


def test_gpt2s_step_counts_the_block_pairs_its_layers_run(interpreted):
    """GPT-2's train step hands out `train.attn.block_pairs_run` /
    `_causal` beside its loss where this module's kernels run (every layer
    the same pairs: layers x one call's count), and `{"loss", "tokens"}`
    alone where the library's or no kernel does."""
    import dataclasses

    import jax

    from distributedtraining_tpu.engine.train import TrainEngine
    from distributedtraining_tpu.models import gpt2
    T = _T_SMALL
    cfg = dataclasses.replace(gpt2.PRESETS["tiny"], n_embd=128, n_head=2,
                              n_positions=T, remat=True)
    model, _ = gpt2.make_model(cfg)
    seg = _documents([[100, 200, T - 300], [130, T - 130]])
    batch = {"input_ids": np.zeros((2, T), np.int32),
             "segment_ids": np.asarray(seg),
             "loss_mask": np.ones((2, T), np.float32)}
    engine = TrainEngine(model, seq_len=T)
    _, m = engine.train_step(engine.init_state(jax.random.PRNGKey(0)), batch)
    run, causal = fl.BLOCK_PAIR_COUNTERS
    assert set(m) == {"loss", "tokens", run, causal}
    one_run, one_causal = fl.block_pairs(
        jax.ShapeDtypeStruct((2, T, 2, 64), jnp.float32), None, seg)
    assert int(m[run]) == cfg.n_layer * int(one_run)
    assert int(m[causal]) == cfg.n_layer * int(one_causal)
    assert int(one_run) < int(one_causal)
    # one head of 64 a device: the library's kernels, nothing counted
    odd = dataclasses.replace(cfg, n_embd=64, n_head=1)
    engine = TrainEngine(gpt2.make_model(odd)[0], seq_len=T)
    _, m = engine.train_step(engine.init_state(jax.random.PRNGKey(0)), batch)
    assert set(m) == {"loss", "tokens"}
