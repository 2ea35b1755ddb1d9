"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464), on the serving
path.

Per VALUE head, with ``S`` the ``[dk, dv]`` float32 state, ``k`` and ``q``
the head's key and query (``dk`` wide, ``k`` of unit length), ``v`` its
value (``dv`` wide), ``g <= 0`` a log-decay and ``0 < beta < 1`` a write
strength, one token moves the state so::

    S <- exp(g) S            r = (v - S^T k) beta
    S <- S + k r^T           o = S^T q

It differs from Mamba-2's update (ops/ssm.py) in kind, not in numbers: the
state is multiplied by ``exp(g) (I - beta k k^T)``, a decay with a rank-one
correction, so a step READS ``S^T k`` before it writes. Key heads serve
``Hv / Hk`` value heads each (head ``h`` uses key head ``h // (Hv / Hk)``).

The decay is the caller's to shape. ``g`` of ``[.., Hv]`` is one log-decay
a head (Gated DeltaNet: the lines above as written). ``g`` of ``[.., Hv,
dk]`` is one a key CHANNEL (Kimi Delta Attention, arXiv:2510.26692): ``S <-
diag(exp(g)) S``, each of the state's ``dk`` rows fading at its own rate.
One code serves both: a head's scalar is the vector whose entries are
equal, carried as a trailing axis of one. ``beta`` may pass 1 (up to 2 the
factor ``I - beta k k^T`` has an eigenvalue in (-1, 1]: a step that can
flip what it read); nothing here assumes it does not.

Three spellings of the same function:

* :func:`delta_rule_scan` — the recurrence as written, one position at a
  time: what the other two are tested against.
* :func:`delta_rule_prefill` — a whole prompt at once, CHUNKED in the WY /
  UT form: inside a chunk of ``chunk`` positions the ``chunk`` rank-one
  corrections are one unit-triangular solve (``(I + strict_lower(beta K
  K^T decay)) U = beta V``) and the outputs a masked product; between
  chunks the ``[dk, dv]`` state is carried. Positions at or past
  ``live_len`` (a prefill bucket's padding) take ``g = 0`` and ``beta =
  0``: they neither decay the state nor write to it, so the state after
  the last chunk IS the state after position ``live_len - 1``. XLA einsums
  in float32 at ``highest`` precision, as ``ssm.ssd_prefill`` is: the
  products here are a few hundredths of the layer's projections. The decay
  between two positions of a chunk is ``exp`` of a DIFFERENCE of running
  sums, never a quotient of two ``exp``: with a decay a channel the
  factorised ``(k_i exp(G_i)) . (k_j exp(-G_j))`` overflows float32 once a
  channel's ``|G|`` passes 88 inside a chunk (:func:`_pair_scores`).
* :func:`gdn_decode_update` — one token for each live slot of a decode
  bucket, against a pool of per-SLOT states ``[slots, Hv, dk, dv]`` that is
  read and written IN PLACE through the slot indices: a Pallas kernel over
  ``(heads block, slot)`` on a TPU at shapes its tiling takes
  (:func:`kernel_supports`), its XLA twin (:func:`gdn_decode_reference`)
  otherwise. The caller says which rows of the bucket are ``live``: for a
  row that is not (a bucket's padding) nothing is computed (at 44% of
  slots busy a bucket is a third padding), both spellings read zero there,
  and the slot it names keeps the state it had.

The state is float32 whatever the activations are: it is a running sum
over the whole sequence whose every step subtracts what it read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
# value heads a kernel block moves: 16 states of [128, 128] float32 are
# 1 MiB in and 1 MiB out, double-buffered 4 MiB of VMEM
HEADS_PER_BLOCK = 16
_HIGHEST = jax.lax.Precision.HIGHEST


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _per_channel(g, lead: int):
    """``g`` with ``lead`` leading axes before its head axis, float32, as
    ``[.., Hv, dk or 1]``: a head's scalar is carried as a vector of one
    entry that broadcasts over the state's rows."""
    g = g.astype(jnp.float32)
    return g[..., None] if g.ndim == lead + 1 else g


def _live(g, beta, live_len):
    """``g`` [B, T, Hv, dk or 1] and ``beta`` [B, T, Hv] in float32 with
    the positions at or past ``live_len`` [B] made steps that do nothing."""
    pos = (jnp.arange(g.shape[1])[None, :] < live_len[:, None])[..., None]
    return (jnp.where(pos[..., None], _per_channel(g, 2), 0.0),
            jnp.where(pos, beta.astype(jnp.float32), 0.0))


def delta_rule_scan(q, k, v, g, beta, live_len, s0=None):
    """The recurrence, one position at a time. Arguments and results are
    :func:`delta_rule_prefill`'s."""
    B, T, Hk, dk = k.shape
    Hv, dv = v.shape[2:]
    R = Hv // Hk
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    g, beta = _live(g, beta, live_len)
    if s0 is None:
        s0 = jnp.zeros((B, Hv, dk, dv), jnp.float32)

    def step(S, inp):
        # [B,Hk,dk] x2, [B,Hv,dv], [B,Hv,dk or 1], [B,Hv]
        q_t, k_t, v_t, g_t, b_t = inp
        q_h, k_h = jnp.repeat(q_t, R, axis=1), jnp.repeat(k_t, R, axis=1)
        S = S * jnp.exp(g_t)[..., None]
        read = jnp.einsum("bhkv,bhk->bhv", S, k_h, precision=_HIGHEST)
        r = (v_t - read) * b_t[..., None]
        S = S + k_h[..., :, None] * r[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_h, precision=_HIGHEST)

    S, o = jax.lax.scan(step, s0.astype(jnp.float32), tuple(
        jnp.moveaxis(a, 1, 0) for a in (f32(q), f32(k), f32(v), g, beta)))
    return jnp.moveaxis(o, 0, 1), S


SUB_CHUNK = 16


def _pair_scores(a, b, cum, *, strict: bool):
    """``sum_d a_i[d] b_j[d] exp(cum_i[d] - cum_j[d])`` for the positions
    ``j <= i`` (``j < i`` if ``strict``) of each chunk, zero elsewhere.
    ``a``, ``b`` [B, n, Q, Hk, dk]; ``cum`` [B, n, Q, Hk, R, dk or 1] the
    inclusive running log-decay -> [B, n, Q, Q, Hk, R].

    A decay a head leaves the sum: one product a key head and one
    ``exp`` of a difference a pair. A decay a channel does not, and the
    factorised product overflows (the module's docstring), so a chunk is
    cut into sub-chunks of ``SUB_CHUNK``: a pair inside one takes its
    difference channel by channel; a pair across two is factorised about
    the running sum just BEFORE the later sub-chunk, which lies between
    the two positions, so both exponents are <= 0 and nothing can
    overflow (what underflows is a decay that has gone to nothing)."""
    Q = cum.shape[2]
    ein = functools.partial(jnp.einsum, precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
    keep = jnp.tril(jnp.ones((Q, Q), bool), -1 if strict else 0)
    keep = keep[None, None, :, :, None, None]
    if cum.shape[-1] == 1:
        cum = cum[..., 0]
        decay = jnp.exp(jnp.where(
            keep, cum[:, :, :, None] - cum[:, :, None, :], -jnp.inf))
        return ein("bnihd,bnjhd->bnijh", a, b)[..., None] * decay
    c = SUB_CHUNK if Q % SUB_CHUNK == 0 else Q
    rows = []
    for lo in range(0, Q, c):
        hi = lo + c
        # inside the sub-chunk: [b,n,i,j,h,r,d], summed over d as it is
        # made (an elementwise product and a reduce: one fusion)
        diff = cum[:, :, lo:hi, None] - cum[:, :, None, lo:hi]
        inside = jnp.tril(jnp.ones((c, c), bool))[None, None, :, :, None,
                                                  None, None]
        own = jnp.sum(
            a[:, :, lo:hi, None, :, None] * b[:, :, None, lo:hi, :, None]
            * jnp.exp(jnp.where(inside, diff, -jnp.inf)), axis=-1)
        parts = [own, jnp.zeros(own.shape[:3] + (Q - hi,) + own.shape[4:],
                                jnp.float32)]
        if lo:
            base = cum[:, :, lo - 1:lo]                 # [b,n,1,h,r,d]
            a_in = a[:, :, lo:hi, :, None] * jnp.exp(cum[:, :, lo:hi] - base)
            b_out = b[:, :, :lo, :, None] * jnp.exp(base - cum[:, :, :lo])
            parts.insert(0, ein("bnihrd,bnjhrd->bnijhr", a_in, b_out))
        rows.append(jnp.concatenate(parts, axis=3))
    return jnp.where(keep, jnp.concatenate(rows, axis=2), 0.0)


def delta_rule_prefill(q: jax.Array, k: jax.Array, v: jax.Array,
                       g: jax.Array, beta: jax.Array, live_len: jax.Array,
                       s0: jax.Array | None = None, *, chunk: int = CHUNK
                       ) -> tuple[jax.Array, jax.Array]:
    """``q``, ``k`` [B, T, Hk, dk] (``q`` scaled, ``k`` of unit length);
    ``v`` [B, T, Hv, dv]; ``g`` [B, T, Hv] the log-decay a head, or
    [B, T, Hv, dk] a key channel; ``beta`` [B, T, Hv]; ``live_len`` [B];
    ``s0`` [B, Hv, dk, dv] or None (zero) -> (``o`` [B, T, Hv, dv]
    float32, the state after position ``live_len - 1`` [B, Hv, dk, dv]
    float32)."""
    B, T, Hk, dk = k.shape
    Hv, dv = v.shape[2:]
    R, Q = Hv // Hk, chunk
    pad = -T % Q
    f32 = jnp.float32
    g, beta = _live(g, beta, live_len)
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                    * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    n = (T + pad) // Q
    # [B, n, Q, Hk, R, .]: value head h = (key head, r)
    qc = q.reshape(B, n, Q, Hk, dk)
    kc = k.reshape(B, n, Q, Hk, dk)
    vc = v.reshape(B, n, Q, Hk, R, dv)
    bc = beta.reshape(B, n, Q, Hk, R)
    # inclusive; [b,n,i,h,r,dk or 1]
    cum = jnp.cumsum(g.reshape(B, n, Q, Hk, R, g.shape[-1]), axis=2)
    ein = functools.partial(jnp.einsum, precision=_HIGHEST,
                            preferred_element_type=f32)

    # (I + A) U = beta V, (I + A) W = beta exp(cum) K: each row's rank-one
    # correction reads the rows before it, so A is strictly lower
    A = _pair_scores(kc, kc, cum, strict=True) * bc[:, :, :, None]
    A = jnp.moveaxis(A, (2, 3), (4, 5))                      # [b,n,h,r,i,j]
    rhs = jnp.concatenate([
        jnp.moveaxis(vc * bc[..., None], 2, 4),              # [b,n,h,r,i,dv]
        jnp.moveaxis(kc[:, :, :, :, None] * bc[..., None] * jnp.exp(cum),
                     2, 4)], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(Q, dtype=f32), rhs, lower=True, unit_diagonal=True)
    u, w = solved[..., :dv], solved[..., dv:]                # [b,n,h,r,i,.]
    attn = jnp.moveaxis(_pair_scores(qc, kc, cum, strict=False),
                        (2, 3), (4, 5))
    q_in = jnp.moveaxis(qc[:, :, :, :, None] * jnp.exp(cum), 2, 4)
    # what a chunk's row j adds to the state at the chunk's end
    k_out = jnp.moveaxis(
        kc[:, :, :, :, None] * jnp.exp(cum[:, :, -1:] - cum), 2, 4)
    through = jnp.exp(cum[:, :, -1])                   # [b,n,h,r,dk or 1]

    S = (jnp.zeros((B, Hk, R, dk, dv), f32) if s0 is None
         else s0.astype(f32).reshape(B, Hk, R, dk, dv))

    def carry(S, inp):
        u_c, w_c, attn_c, q_c, k_c, through_c = inp
        fresh = u_c - ein("bhrik,bhrkv->bhriv", w_c, S)
        o = (ein("bhrik,bhrkv->bhriv", q_c, S)
             + ein("bhrij,bhrjv->bhriv", attn_c, fresh))
        S = (S * through_c[..., None]
             + ein("bhrjk,bhrjv->bhrkv", k_c, fresh))
        return S, o

    S, o = jax.lax.scan(carry, S, tuple(
        jnp.moveaxis(a, 1, 0) for a in (u, w, attn, q_in, k_out, through)))
    o = jnp.moveaxis(o, 0, 1)                                # [b,n,h,r,i,dv]
    o = jnp.moveaxis(o, 4, 2).reshape(B, T + pad, Hv, dv)[:, :T]
    return o, S.reshape(B, Hv, dk, dv)


# ---------------------------------------------------------------------------
# decode: one token a slot, the pool updated in place
# ---------------------------------------------------------------------------

def _block_heads(Hv: int, R: int) -> int:
    """Value heads a block: the most, up to HEADS_PER_BLOCK, that is whole
    key heads and divides ``Hv``."""
    return max(n for n in range(R, min(Hv, HEADS_PER_BLOCK) + 1, R)
               if Hv % n == 0)


def kernel_supports(state: jax.Array, key_heads: int) -> bool:
    """The shapes the kernel's blocks take: a float32 pool whose ``[dk,
    dv]`` state is whole (8, 128) tiles, and whole key heads."""
    _, Hv, dk, dv = state.shape
    return (state.dtype == jnp.float32 and dk % 8 == 0 and dv % 128 == 0
            and Hv % key_heads == 0)


def gdn_decode_reference(state, slots, q, k, v, g, beta, live=None):
    """The XLA twin of :func:`gdn_decode_update`: slot by slot, each
    state sliced out of the pool, moved on and written back where it lay
    (a gather of the whole bucket would be a copy the size of the pool).
    A row that is not ``live`` writes back what it read and reads zero."""
    R = v.shape[1] // k.shape[1]
    f32 = jnp.float32
    q = jnp.repeat(q.astype(f32), R, axis=1)                 # [B, Hv, dk]
    k = jnp.repeat(k.astype(f32), R, axis=1)
    v, beta = v.astype(f32), beta.astype(f32)
    a = jnp.exp(_per_channel(g, 1))                          # [B, Hv, dk|1]
    live = (jnp.ones(slots.shape, bool) if live is None
            else live.astype(bool))

    def one(i, carry):
        state, o = carry
        was = jax.lax.dynamic_index_in_dim(state, slots[i], keepdims=False)
        S = was * a[i][:, :, None]
        read = jnp.sum(S * k[i][:, :, None], axis=1)         # [Hv, dv]
        r = (v[i] - read) * beta[i][:, None]
        S = S + k[i][:, :, None] * r[:, None, :]
        o = o.at[i].set(jnp.where(
            live[i], jnp.sum(S * q[i][:, :, None], axis=1), 0.0))
        return jax.lax.dynamic_update_index_in_dim(
            state, jnp.where(live[i], S, was), slots[i], 0), o

    state, o = jax.lax.fori_loop(0, v.shape[0], one,
                                 (state, jnp.zeros(v.shape, f32)))
    return o, state


def _decode_kernel(slots_ref, live_ref, s_ref, q_ref, k_ref, v_ref, a_ref,
                   b_ref, o_ref, s_out_ref, *, heads: int, per_key: int):
    """One (heads block, slot) block: ``heads`` states ``[dk, dv]``; the
    block's keys and queries transposed to ``[dk, key heads]`` so that a
    head's column broadcasts along the state's lanes; values ``[heads,
    dv]`` rows; beta ``[1, heads]``; the decay ``[1, heads]`` a head or
    ``[dk, heads]`` a key channel (a column a head either way: one entry
    that broadcasts over the state, or one a row of it). For a row that is not live
    (a bucket's padding) nothing is computed (a half-empty bucket would
    else pay every padding row's arithmetic): its output is zero and the
    slot it names is written back as it was read."""
    del slots_ref                          # the block specs' index maps' own
    live = live_ref[pl.program_id(1)] != 0

    @pl.when(live)
    def _move():
        for h in range(heads):
            kh = h // per_key
            k_col, q_col = k_ref[:, kh:kh + 1], q_ref[:, kh:kh + 1]
            S = s_ref[h] * a_ref[:, h:h + 1]                      # [dk, dv]
            read = jnp.sum(S * k_col, axis=0, keepdims=True)      # [1, dv]
            r = (v_ref[h:h + 1, :] - read) * b_ref[:, h:h + 1]
            S = S + k_col * r
            s_out_ref[h] = S
            o_ref[h:h + 1, :] = jnp.sum(S * q_col, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _pad():
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.lru_cache(maxsize=None)
def _decode_call(S, Bk, Hv, Hk, dk, dv, dkg, interpret: bool):
    """``dkg`` is the decay's rows a head: 1, or ``dk`` a channel."""
    R = Hv // Hk
    n = _block_heads(Hv, R)               # value heads a block
    nk = n // R                           # key heads a block

    def at_slot(j, b, slots, live):
        return (slots[b], j, 0, 0)

    def at_row(j, b, slots, live):
        return (b, j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,    # the bucket's slot indices, its live rows
        # slots minor: where a bucket's padding rows all name one slot and
        # lie together at the end (the engine's do), its block is fetched
        # and written back once a heads block, not once a row
        grid=(Hv // n, Bk),
        in_specs=[
            pl.BlockSpec((None, n, dk, dv), at_slot),
            pl.BlockSpec((None, None, dk, nk), at_row),
            pl.BlockSpec((None, None, dk, nk), at_row),
            pl.BlockSpec((None, None, n, dv), at_row),
            pl.BlockSpec((None, None, dkg, n), at_row),
            pl.BlockSpec((None, None, 1, n), at_row),
        ],
        out_specs=[
            pl.BlockSpec((None, None, n, dv), at_row),
            pl.BlockSpec((None, n, dk, dv), at_slot),
        ],
    )
    return pl.pallas_call(  # devprof: exempt (attributed under serve.decode in-step)
        functools.partial(_decode_kernel, heads=n, per_key=R),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Bk, Hv // n, n, dv), jnp.float32),
                   jax.ShapeDtypeStruct((S, Hv, dk, dv), jnp.float32)],
        # operands 0 and 1 are the scalar prefetch; the pool is operand 2
        # and result 1: the named slots' blocks are rewritten where they lie
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="gdn_decode_update",
    )


def gdn_decode_update(state: jax.Array, slots: jax.Array, q: jax.Array,
                      k: jax.Array, v: jax.Array, g: jax.Array,
                      beta: jax.Array, live: jax.Array | None = None, *,
                      impl: str | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """``state`` [S, Hv, dk, dv] float32 (the pool); ``slots`` [B] int32;
    ``q``, ``k`` [B, Hk, dk]; ``v`` [B, Hv, dv]; ``g`` [B, Hv] a head or
    [B, Hv, dk] a key channel; ``beta`` [B, Hv]; ``live`` [B] bool, or
    None for every row -> (``o`` [B, Hv, dv] float32,
    the pool with the live rows' slots moved on by one token). Live rows
    name distinct slots. A row that is not live is a bucket's padding:
    its ``o`` is zero and the slot it names (any that no live row names;
    padding rows may share one) keeps its state. ``impl`` is for the tests and the chip's A/B
    (``"xla"``, ``"kernel"``, ``"kernel_interpret"``); None is the rule:
    the kernel on a TPU at shapes it takes."""
    S, Hv, dk, dv = state.shape
    Bk, Hk = k.shape[:2]
    if impl is None:
        impl = ("kernel" if _on_tpu() and kernel_supports(state, Hk)
                else "xla")
    if impl == "xla":
        return gdn_decode_reference(state, slots, q, k, v, g, beta, live)
    if impl not in ("kernel", "kernel_interpret"):
        raise ValueError(f"unknown state update {impl!r}")
    if not kernel_supports(state, Hk):
        raise ValueError(f"gdn_decode_update: unsupported shapes state="
                         f"{state.shape} {state.dtype} key heads={Hk}")
    R = Hv // Hk
    n = _block_heads(Hv, R)
    f32 = jnp.float32

    def columns(x):                        # [B, Hk, dk] -> [B, blocks, dk, nk]
        return x.astype(f32).reshape(Bk, Hk // (n // R), n // R, dk
                                     ).transpose(0, 1, 3, 2)

    a = jnp.exp(_per_channel(g, 1))                          # [B, Hv, dk|1]
    o, state = _decode_call(S, Bk, Hv, Hk, dk, dv, a.shape[-1],
                            impl == "kernel_interpret")(
        slots.astype(jnp.int32),
        (jnp.ones((Bk,), jnp.int32) if live is None
         else live.astype(jnp.int32)), state, columns(q), columns(k),
        v.astype(f32).reshape(Bk, Hv // n, n, dv),
        a.reshape(Bk, Hv // n, n, -1).transpose(0, 1, 3, 2),
        beta.astype(f32).reshape(Bk, Hv // n, 1, n))
    return o.reshape(Bk, Hv, dv), state
