"""The Mamba-2 recurrence (state-space duality), on the serving path.

Per head, with ``h`` the ``[P, N]`` state (``P`` head width, ``N`` state
width), ``A`` one negative scalar a head and ``dt > 0`` a step a head and
token::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        y_t = h_t C_t + D x_t

``B_t``/``C_t`` are shared by the heads of one GROUP (``H / G`` heads a
group). Two spellings of the same function:

* :func:`ssd_prefill` — a whole prompt at once, CHUNKED: inside a chunk of
  ``chunk`` positions the outputs are one masked product (the decay
  between two positions of a chunk is ``exp`` of a difference of running
  sums), between chunks a ``[P, N]`` state is carried. Positions at or
  past ``live_len`` (a prefill bucket's padding) take ``dt = 0``: they
  neither decay the state nor feed it, so the state after the last chunk
  IS the state after position ``live_len - 1``.
* :func:`ssm_decode_update` — one token for each live slot of a decode
  bucket, against a pool of per-SLOT states ``[slots, H, P, N]`` that is
  read and written IN PLACE through the slot indices: a Pallas kernel over
  ``(group, slot)`` on a TPU at shapes its tiling takes
  (:func:`kernel_supports`), its XLA twin (:func:`ssm_decode_reference`)
  otherwise. A bucket's padding rows name the pool's last slot, which no
  request owns.

The state is float32 whatever the activations are: it is a running sum
over the whole sequence, and one rounding a step to bfloat16 would be
1,536 roundings by the end of a long request.

:func:`causal_conv1d` / :func:`conv_decode_update` are the depthwise
convolution (kernel ``K``) in front of the recurrence, which carries its
own small state a slot: the last ``K - 1`` input rows (the TAIL).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128
_HIGHEST = jax.lax.Precision.HIGHEST


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# the convolution in front
# ---------------------------------------------------------------------------

def causal_conv1d(u: jax.Array, w: jax.Array, b: jax.Array | None,
                  live_len: jax.Array | None,
                  segment_ids: jax.Array | None = None,
                  tail0: jax.Array | None = None
                  ) -> tuple[jax.Array, jax.Array | None]:
    """Depthwise causal convolution from a zero history, or from the
    ``K - 1`` rows ``tail0`` [B, K-1, C] that an earlier part of the same
    sequence left (a prefill that CONTINUES: the tail it returns is then
    the whole sequence's). ``u`` [B, T, C],
    ``w`` [K, C] (``w[K-1]`` multiplies the current row), ``b`` [C] or
    None (no bias), ``live_len`` [B] -> (``conv(u) + b`` [B, T, C] float32,
    the tail: the ``K - 1`` rows of ``u`` before position ``live_len``
    [B, K-1, C], zero where the sequence is shorter; None without
    ``live_len``, for a caller that keeps nothing: the train step).

    ``segment_ids`` [B, T] (packed rows, data/packing.py): every document
    starts from its OWN zero history, so a tap whose source position lies
    in another segment adds zero, as one before the row's start does. One
    function for a prefill (one sequence a row: no ids, and the program is
    what it was, instruction for instruction) and for a packed train
    row."""
    K = w.shape[0]
    T = u.shape[1]
    padded = (jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0))) if tail0 is None
              else jnp.concatenate([tail0.astype(u.dtype), u], axis=1))
    w32 = w.astype(jnp.float32)
    out = 0.0 if b is None else b.astype(jnp.float32)
    for k in range(K):
        tap = padded[:, k:k + T].astype(jnp.float32) * w32[k]
        if segment_ids is not None and k < K - 1:
            # the source of tap k at position t is position t - (K - 1 - k)
            source = jnp.pad(segment_ids, ((0, 0), (K - 1 - k, 0)),
                             constant_values=-1)[:, :T]
            tap = jnp.where((source == segment_ids)[..., None], tap, 0.0)
        out = out + tap
    if live_len is None:
        return out, None
    tail = jax.vmap(lambda rows, n: jax.lax.dynamic_slice_in_dim(
        rows, n, K - 1, axis=0))(padded, live_len)
    return out, tail


def conv_decode_update(tails: jax.Array, slots: jax.Array, u: jax.Array,
                       w: jax.Array, b: jax.Array | None
                       ) -> tuple[jax.Array, jax.Array]:
    """One token a slot. ``tails`` [S, K-1, C] (the pool), ``slots`` [B],
    ``u`` [B, C], ``b`` [C] or None (no bias) -> (``conv + b`` [B, C]
    float32, the pool with the live slots' tails moved on by one row)."""
    window = jnp.concatenate([tails[slots], u[:, None].astype(tails.dtype)],
                             axis=1)                         # [B, K, C]
    out = jnp.sum(window.astype(jnp.float32) * w.astype(jnp.float32),
                  axis=1)
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out, tails.at[slots].set(window[:, 1:])


# ---------------------------------------------------------------------------
# prefill: the chunked scan
# ---------------------------------------------------------------------------

def ssd_scan_reference(x, dt, A, B, C, D, live_len, h0=None):
    """The recurrence as written, one position at a time: what
    :func:`ssd_prefill` is tested against. Same arguments and results."""
    Bsz, T, H, P = x.shape
    G, N = B.shape[2:]
    R = H // G
    pos = jnp.arange(T)[None, :] < live_len[:, None]
    dt = jnp.where(pos[..., None], dt.astype(jnp.float32), 0.0)
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp                # [B,H,P] [B,H] [B,G,N] x2
        b_h = jnp.repeat(b_t, R, axis=1)
        c_h = jnp.repeat(c_t, R, axis=1)
        h = (h * jnp.exp(dt_t * A)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        return h, jnp.sum(h * c_h[:, :, None, :], axis=-1)

    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    h, y = jax.lax.scan(step, h0, (
        jnp.moveaxis(f32(x), 1, 0), jnp.moveaxis(dt, 1, 0),
        jnp.moveaxis(f32(B), 1, 0), jnp.moveaxis(f32(C), 1, 0)))
    return jnp.moveaxis(y, 0, 1) + D[:, None] * f32(x), h


def ssd_prefill(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, D: jax.Array, live_len: jax.Array,
                h0: jax.Array | None = None, *, chunk: int = CHUNK
                ) -> tuple[jax.Array, jax.Array]:
    """``x`` [B, T, H, P]; ``dt`` [B, T, H] (after its softplus); ``A``,
    ``D`` [H]; ``B``, ``C`` [B, T, G, N]; ``live_len`` [B]; ``h0``
    [B, H, P, N] or None (zero) -> (``y`` [B, T, H, P] float32, the state
    after position ``live_len - 1`` [B, H, P, N] float32). Everything is
    computed in float32 at ``highest`` precision: the products here are a
    thousandth of the layer's projections."""
    Bsz, T, H, P = x.shape
    G, N = B.shape[2:]
    R = H // G
    Q = chunk
    pad = -T % Q
    f32 = jnp.float32
    live = jnp.arange(T)[None, :] < live_len[:, None]
    dt = jnp.where(live[..., None], dt.astype(f32), 0.0)
    x32 = x.astype(f32)
    if pad:
        x32, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (a.ndim - 2)) for a in (x32, dt, B, C))
    nC = (T + pad) // Q
    xdt = (x32 * dt[..., None]).reshape(Bsz, nC, Q, G, R, P)
    a = (dt * A).reshape(Bsz, nC, Q, G, R)
    Bc = B.astype(f32).reshape(Bsz, nC, Q, G, N)
    Cc = C.astype(f32).reshape(Bsz, nC, Q, G, N)
    cum = jnp.cumsum(a, axis=2)                              # inclusive
    ein = functools.partial(jnp.einsum, precision=_HIGHEST,
                            preferred_element_type=f32)

    # inside a chunk: y_i += sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(
        causal[None, None, :, :, None, None],
        cum[:, :, :, None] - cum[:, :, None, :], -jnp.inf))  # [b,c,i,j,g,r]
    cb = ein("bcign,bcjgn->bcijg", Cc, Bc)
    y = ein("bcijgr,bcjgrp->bcigrp", cb[..., None] * decay, xdt)

    # a chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, :, -1:] - cum)                   # [b,c,j,g,r]
    own = ein("bcjgrp,bcjgn->bcgrpn", xdt * to_end[..., None], Bc)

    # between chunks: the state entering each chunk
    h = (jnp.zeros((Bsz, G, R, P, N), f32) if h0 is None
         else h0.astype(f32).reshape(Bsz, G, R, P, N))
    through = jnp.exp(cum[:, :, -1])                         # [b,c,g,r]

    def carry(h, inp):
        own_c, through_c = inp
        return h * through_c[..., None, None] + own_c, h

    h, entering = jax.lax.scan(carry, h, (jnp.moveaxis(own, 1, 0),
                                          jnp.moveaxis(through, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                  # [b,c,g,r,p,n]
    y = y + ein("bcign,bcgrpn->bcigrp", Cc, entering) \
        * jnp.exp(cum)[..., None]
    y = y.reshape(Bsz, T + pad, H, P)[:, :T]
    return y + D[:, None] * x32[:, :T], h.reshape(Bsz, H, P, N)


# ---------------------------------------------------------------------------
# decode: one token a slot, the pool updated in place
# ---------------------------------------------------------------------------

def kernel_supports(state: jax.Array, groups: int) -> bool:
    """The shapes the kernel's blocks take: a float32 pool whose ``[P, N]``
    state is whole (8, 128) tiles, and whole groups of heads."""
    _, H, P, N = state.shape
    return (state.dtype == jnp.float32 and P % 8 == 0 and N % 128 == 0
            and H % groups == 0)


def _decode_inputs(x, dt, A, B, C):
    """What both spellings start from, float32: the decay a head
    ``exp(dt A)`` [B, H], ``dt x`` [B, H, P], and B, C [B, G, N]."""
    dt = dt.astype(jnp.float32)
    return (jnp.exp(dt * A), dt[..., None] * x.astype(jnp.float32),
            B.astype(jnp.float32), C.astype(jnp.float32))


def ssm_decode_reference(state, slots, x, dt, A, B, C, D):
    """The XLA twin of :func:`ssm_decode_update`: slot by slot, each
    state sliced out of the pool, moved on and written back where it lay
    (a gather of the whole bucket would be a copy the size of the pool)."""
    R = x.shape[1] // B.shape[1]
    da, dtx, b, c = _decode_inputs(x, dt, A, B, C)
    b, c = jnp.repeat(b, R, axis=1), jnp.repeat(c, R, axis=1)    # [B, H, N]

    def one(i, carry):
        state, y = carry
        h = (jax.lax.dynamic_index_in_dim(state, slots[i], keepdims=False)
             * da[i][:, None, None] + dtx[i][..., None] * b[i][:, None, :])
        y = y.at[i].set(jnp.sum(h * c[i][:, None, :], axis=-1))
        return jax.lax.dynamic_update_index_in_dim(state, h, slots[i], 0), y

    state, y = jax.lax.fori_loop(0, x.shape[0], one,
                                 (state, jnp.zeros(dtx.shape, jnp.float32)))
    return y + D[:, None] * x.astype(jnp.float32), state


def _decode_kernel(slots_ref, h_ref, dtx_ref, da_ref, b_ref, c_ref,
                   y_ref, h_out_ref, *, heads: int):
    """One (group, slot) block: the group's ``heads`` states ``[P, N]``,
    ``dt x`` transposed to ``[P, heads]`` so that a head's column
    broadcasts along the state's lanes, the group's B and C rows."""
    del slots_ref                           # read by the index maps
    b_row, c_row = b_ref[...], c_ref[...]                    # [1, N]
    for r in range(heads):
        h = (h_ref[r] * da_ref[:, r:r + 1]
             + dtx_ref[:, r:r + 1] * b_row)                  # [P, N]
        h_out_ref[r] = h
        y_ref[:, r:r + 1] = jnp.sum(h * c_row, axis=-1, keepdims=True)


@functools.lru_cache(maxsize=None)
def _decode_call(S, Bk, H, P, N, G, interpret: bool):
    R = H // G

    def at_slot(g, b, slots):
        return (slots[b], g, 0, 0)

    def at_row(g, b, slots):
        return (b, g, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,              # the bucket's slot indices
        # slots minor: a bucket's padding rows all name the pool's spare
        # slot and lie together at the end, so its block is fetched and
        # written back once a group, not once a row
        grid=(G, Bk),
        in_specs=[
            pl.BlockSpec((None, R, P, N), at_slot),
            pl.BlockSpec((None, None, P, R), at_row),
            pl.BlockSpec((None, None, 1, R), at_row),
            pl.BlockSpec((None, None, 1, N), at_row),
            pl.BlockSpec((None, None, 1, N), at_row),
        ],
        out_specs=[
            pl.BlockSpec((None, None, P, R), at_row),
            pl.BlockSpec((None, R, P, N), at_slot),
        ],
    )
    return pl.pallas_call(  # devprof: exempt (attributed under serve.decode in-step)
        functools.partial(_decode_kernel, heads=R),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Bk, G, P, R), jnp.float32),
                   jax.ShapeDtypeStruct((S, H, P, N), jnp.float32)],
        # operand 0 is the scalar prefetch; the pool is operand 1 and
        # result 1: the live slots' blocks are rewritten where they lie
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_decode_update",
    )


def ssm_decode_update(state: jax.Array, slots: jax.Array, x: jax.Array,
                      dt: jax.Array, A: jax.Array, B: jax.Array,
                      C: jax.Array, D: jax.Array, *,
                      impl: str | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """``state`` [S, H, P, N] float32 (the pool); ``slots`` [B] int32;
    ``x`` [B, H, P]; ``dt`` [B, H] (after its softplus); ``A``, ``D``
    [H]; ``B``, ``C`` [B, G, N] -> (``y`` [B, H, P] float32, the pool with
    the named slots moved on by one token). ``impl`` is for the tests and
    the chip's A/B (``"xla"``, ``"kernel"``, ``"kernel_interpret"``); None
    is the rule: the kernel on a TPU at shapes it takes."""
    S, H, P, N = state.shape
    Bk, G = B.shape[:2]
    if impl is None:
        impl = ("kernel" if _on_tpu() and kernel_supports(state, G)
                else "xla")
    if impl == "xla":
        return ssm_decode_reference(state, slots, x, dt, A, B, C, D)
    if impl not in ("kernel", "kernel_interpret"):
        raise ValueError(f"unknown state update {impl!r}")
    if not kernel_supports(state, G):
        raise ValueError(f"ssm_decode_update: unsupported shapes state="
                         f"{state.shape} {state.dtype} groups={G}")
    R = H // G
    da, dtx, b, c = _decode_inputs(x, dt, A, B, C)
    y_t, state = _decode_call(S, Bk, H, P, N, G,
                              impl == "kernel_interpret")(
        slots.astype(jnp.int32), state,
        dtx.reshape(Bk, G, R, P).transpose(0, 1, 3, 2),
        da.reshape(Bk, G, 1, R), b[:, :, None], c[:, :, None])
    y = y_t.transpose(0, 1, 3, 2).reshape(Bk, H, P)
    return y + D[:, None] * x.astype(jnp.float32), state
