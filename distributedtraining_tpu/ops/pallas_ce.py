"""Pallas fused linear-cross-entropy for TPU: loss AND grads without ever
materializing the [N, V] logits.

The workload this accelerates is the reference's hot loop — HF-style
shifted CE over a 50k vocabulary every miner step
(hivetrain/training_manager.py:380-392). The standard XLA path writes the
f32 [B, T, V] logits to HBM (GPT-2-124M at B8/T1024: ~1.6 GB) and
traverses them several times across loss + backward. The lax.scan variant
in ops/losses.py already avoids the buffer but pays an extra head-matmul
recompute plus scan/checkpoint overhead.

This module is the Pallas spelling, flash-attention's trick applied to
the vocab axis:

- forward: one (rows x vocab-tiles) grid keeping a running online-softmax
  (max, sumexp, label-logit) in VMEM; per-token loss plus the (m, s)
  stats come out, the logits never leave registers/VMEM.
- backward: two kernels, exactly like the library flash-attention split
  (dq vs dk/dv): a row-major kernel recomputes each logits tile, forms
  dz = (softmax - onehot) * g in-register and accumulates dh = dz @ W in
  VMEM; a vocab-major kernel does the same recompute and accumulates
  dW = dz^T @ h per vocab tile in f32.

FLOP accounting vs the standard path: +1 head-matmul equivalent in the
backward (the recompute, amortized across both kernels) in exchange for
~all the logits HBM traffic. At 124M the head matmul is ~27% of step
FLOPs, so the trade should be near break-even on a single chip and improve
with model size (head share shrinks) and vocab (traffic grows). Its speed
against the other spellings is not measured.

Stats/labels ride (rows, 128)-lane buffers (value broadcast across
lanes), the same layout the library flash kernel uses for its l/m stats
— narrow 1-lane blocks are the classic Mosaic lowering trap.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

_NEG = -1e30   # -inf stand-in without nan hazards (python float: a jnp
               # scalar here would be a captured constant inside the kernels)
_LANES = 128                # stat-vector lane padding (Mosaic-safe blocks)


# test hook: run the kernels through the Pallas interpreter so CPU lanes
# pin the KERNEL math, engine-level callers included (set via
# use_interpret; an explicit ``interpret=`` argument wins)
_FORCE_INTERPRET = False


def use_interpret(on: bool) -> None:
    """Route callers that pass no ``interpret=`` through the interpreter
    (CPU test lanes). Production never sets this; without it the kernels
    compile for the backend or raise."""
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = bool(on)


def pallas_ce_available(hidden: jax.Array, head_kernel: jax.Array) -> bool:
    """The ``impl="auto"`` selection rule: a TPU backend and a
    lane-aligned embedding dim. Anything else routes to the lax.scan
    spelling in ops/losses.py."""
    return (jax.default_backend() == "tpu"
            and hidden.shape[-1] % _LANES == 0)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(h_ref, w_ref, y_ref, loss_ref, m_ref, s_ref, ll_ref, *, v_real):
    """Grid (n_tiles, v_tiles), vocab innermost: the (m, s, label-logit)
    running stats live in the revisited output blocks / scratch and are
    finalized into per-token loss on the last vocab tile."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        s_ref[:] = jnp.zeros_like(s_ref)
        ll_ref[:] = jnp.zeros_like(ll_ref)

    z = jax.lax.dot_general(h_ref[:], w_ref[:], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    bv = z.shape[1]
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    z = jnp.where(col < v_real, z, _NEG)

    m_old = m_ref[:, :1]
    m_new = jnp.maximum(m_old, jnp.max(z, axis=1, keepdims=True))
    s_new = (s_ref[:, :1] * jnp.exp(m_old - m_new)
             + jnp.sum(jnp.exp(z - m_new), axis=1, keepdims=True))
    y = y_ref[:, :1]
    ll_new = ll_ref[:, :1] + jnp.sum(
        jnp.where(col == y, z, 0.0), axis=1, keepdims=True)

    lanes = m_ref.shape[1]
    m_ref[:] = jnp.broadcast_to(m_new, (m_new.shape[0], lanes))
    s_ref[:] = jnp.broadcast_to(s_new, (s_new.shape[0], lanes))
    ll_ref[:] = jnp.broadcast_to(ll_new, (ll_new.shape[0], lanes))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        loss_ref[:] = m_ref[:] + jnp.log(s_ref[:]) - ll_ref[:]


def _dz_tile(h_ref, w_ref, y_ref, m_ref, s_ref, g_ref, j_v, *, v_real):
    """Recompute one logits tile and form dz = (softmax - onehot) * g.
    Shared by both backward kernels; returns dz in the compute dtype so
    the following matmul runs at full MXU rate."""
    z = jax.lax.dot_general(h_ref[:], w_ref[:], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    bv = z.shape[1]
    col = j_v * bv + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    p = jnp.where(col < v_real,
                  jnp.exp(z - m_ref[:, :1]) / s_ref[:, :1], 0.0)
    onehot = (col == y_ref[:, :1]).astype(jnp.float32)
    return ((p - onehot) * g_ref[:, :1]).astype(h_ref.dtype)


def _dh_kernel(h_ref, w_ref, y_ref, m_ref, s_ref, g_ref, dh_ref, acc, *,
               v_real):
    """Grid (n_tiles, v_tiles), vocab innermost: dh accumulates in an f32
    VMEM scratch across vocab tiles, written once per row tile."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)

    dz = _dz_tile(h_ref, w_ref, y_ref, m_ref, s_ref, g_ref, j, v_real=v_real)
    acc[:] += jax.lax.dot_general(dz, w_ref[:], (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        dh_ref[:] = acc[:].astype(dh_ref.dtype)


def _dw_kernel(h_ref, w_ref, y_ref, m_ref, s_ref, g_ref, dw_ref, acc, *,
               v_real):
    """Grid (v_tiles, n_tiles), rows innermost: dW accumulates per vocab
    tile in f32 VMEM, written once per vocab tile (padded-row tokens
    arrive with g = 0 so they contribute nothing)."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)

    j = pl.program_id(0)
    dz = _dz_tile(h_ref, w_ref, y_ref, m_ref, s_ref, g_ref, j, v_real=v_real)
    acc[:] += jax.lax.dot_general(dz, h_ref[:], (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        dw_ref[:] = acc[:]


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

def _stat_spec(bn):
    return pl.BlockSpec((bn, _LANES), lambda i, j: (i, 0))


def _fwd_call(h, w, y2, *, bn, bv, v_real, interpret):
    n, e = h.shape
    vp = w.shape[0]
    grid = (n // bn, vp // bv)
    out = jax.ShapeDtypeStruct((n, _LANES), jnp.float32)
    kernel = functools.partial(_fwd_kernel, v_real=v_real)
    loss, m, s = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, e), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, e), lambda i, j: (j, 0)),
            _stat_spec(bn),
        ],
        out_specs=[_stat_spec(bn), _stat_spec(bn), _stat_spec(bn)],
        out_shape=[out, out, out],
        scratch_shapes=[pltpu.VMEM((bn, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(h, w, y2)
    return loss, m, s


def _bwd_calls(h, w, y2, m, s, g2, *, bn, bv, v_real, interpret):
    n, e = h.shape
    vp = w.shape[0]
    stat = _stat_spec(bn)

    dh = pl.pallas_call(
        functools.partial(_dh_kernel, v_real=v_real),
        grid=(n // bn, vp // bv),
        in_specs=[
            pl.BlockSpec((bn, e), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, e), lambda i, j: (j, 0)),
            stat, stat, stat, stat,
        ],
        out_specs=pl.BlockSpec((bn, e), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, e), h.dtype),
        scratch_shapes=[pltpu.VMEM((bn, e), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(h, w, y2, m, s, g2)

    # vocab-major: same tile recompute, dW side (note the swapped grid —
    # index maps address (row_tile, vocab_tile) as (grid1, grid0))
    stat_sw = pl.BlockSpec((bn, _LANES), lambda j, i: (i, 0))
    dw = pl.pallas_call(
        functools.partial(_dw_kernel, v_real=v_real),
        grid=(vp // bv, n // bn),
        in_specs=[
            pl.BlockSpec((bn, e), lambda j, i: (i, 0)),
            pl.BlockSpec((bv, e), lambda j, i: (j, 0)),
            stat_sw, stat_sw, stat_sw, stat_sw,
        ],
        out_specs=pl.BlockSpec((bv, e), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((vp, e), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bv, e), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(h, w, y2, m, s, g2)
    return dh, dw


# ---------------------------------------------------------------------------
# custom_vjp + public wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _per_token_ce(bn, bv, v_real, interpret, h, w, y2):
    loss, _, _ = _fwd_call(h, w, y2, bn=bn, bv=bv, v_real=v_real,
                           interpret=interpret)
    return loss[:, 0]


def _per_token_ce_fwd(bn, bv, v_real, interpret, h, w, y2):
    loss, m, s = _fwd_call(h, w, y2, bn=bn, bv=bv, v_real=v_real,
                           interpret=interpret)
    return loss[:, 0], (h, w, y2, m, s)


def _per_token_ce_bwd(bn, bv, v_real, interpret, res, g):
    h, w, y2, m, s = res
    g2 = jnp.broadcast_to(g.astype(jnp.float32)[:, None],
                          (g.shape[0], _LANES))
    dh, dw = _bwd_calls(h, w, y2, m, s, g2, bn=bn, bv=bv, v_real=v_real,
                        interpret=interpret)
    return dh, dw.astype(w.dtype), np.zeros(y2.shape, jax.dtypes.float0)


_per_token_ce.defvjp(_per_token_ce_fwd, _per_token_ce_bwd)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _env_block(env: str, default: int, mult: int, why: str) -> int:
    raw = os.environ.get(env)
    if not raw:
        return default
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(f"{env}={raw!r} is not an integer") from None
    if val <= 0 or val % mult:
        raise ValueError(f"{env}={val} must be a positive multiple "
                         f"of {mult} ({why})")
    return val


def _resolve_blocks(block_n: int, block_v: int) -> tuple[int, int]:
    """On-chip tuning knobs without an edit-redeploy loop. Validated
    eagerly: a bad value must fail with a named error, not with a
    cryptic Mosaic lowering failure.

    NOTE: read at TRACE time — they bind at the first compile of a given
    jitted program; changing them in-process later does not retrace
    (bn/bv are not part of the program's avals). Set them before the
    first step, or construct a fresh engine per setting.

    BN is a sublane dim (16 covers the strictest bf16 tiling); BV is the
    MINORMOST dim of the logits tiles — sub-128 lanes are the narrow-lane
    Mosaic trap the module docstring warns about."""
    return (_env_block("DT_PALLAS_CE_BN", block_n, 16, "sublane tiling"),
            _env_block("DT_PALLAS_CE_BV", block_v, 128, "lane width"))


def fused_ce_loss(hidden: jax.Array, head_kernel: jax.Array,
                  labels: jax.Array,
                  loss_mask: Optional[jax.Array] = None,
                  *, block_n: int = 1024, block_v: int = 512,
                  interpret: Optional[bool] = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Drop-in for ops.losses.fused_linear_cross_entropy, Pallas path.

    hidden: [..., E] activations ALREADY shifted/aligned to ``labels``
    [...]; head_kernel: [V, E]; loss_mask like labels. Returns
    (mean_loss, token_count) — the causal_lm_loss contract. Differentiable
    w.r.t. hidden and head_kernel (custom_vjp, two backward kernels).
    """
    if interpret is None:
        interpret = _FORCE_INTERPRET
    block_n, block_v = _resolve_blocks(block_n, block_v)
    total, count = _fused_ce_totals(hidden, head_kernel, labels, loss_mask,
                                    block_n=block_n, block_v=block_v,
                                    interpret=interpret)
    return total / jnp.maximum(count, 1.0), jnp.maximum(count, 1.0)


def _fused_ce_totals(hidden: jax.Array, head_kernel: jax.Array,
                     labels: jax.Array,
                     loss_mask: Optional[jax.Array],
                     *, block_n: int, block_v: int,
                     interpret: bool) -> tuple[jax.Array, jax.Array]:
    """(sum of masked per-token losses, RAW mask sum) — the un-normalized
    half of ``fused_ce_loss``, split out so the shard_map spelling can
    psum totals across devices before normalizing (a per-shard
    ``max(count, 1)`` clamp would silently inflate the denominator for
    shards whose rows are all padding)."""
    e = hidden.shape[-1]
    v = head_kernel.shape[0]
    h = hidden.reshape(-1, e)
    y = labels.reshape(-1).astype(jnp.int32)
    n = h.shape[0]

    bn = min(block_n, _round_up(n, 16))
    bv = min(block_v, _round_up(v, _LANES))
    n_pad = _round_up(n, bn)
    v_pad = _round_up(v, bv)
    if n_pad > n:
        h = jnp.pad(h, ((0, n_pad - n), (0, 0)))
        y = jnp.pad(y, (0, n_pad - n))
    w = head_kernel
    if v_pad > v:
        w = jnp.pad(w, ((0, v_pad - v), (0, 0)))
    # the kernel compares label lanes against vocab columns; broadcast to
    # the stat-lane layout once here (4 bytes/token/lane, trivial next to
    # the saved logits)
    y2 = jnp.broadcast_to(y[:, None], (n_pad, _LANES))

    per_tok = _per_token_ce(bn, bv, v, interpret, h, w, y2)[:n]
    per_tok = per_tok.reshape(labels.shape)
    if loss_mask is not None:
        msk = loss_mask.astype(per_tok.dtype)
    else:
        msk = jnp.ones_like(per_tok)
    return jnp.sum(per_tok * msk), jnp.sum(msk)


# ---------------------------------------------------------------------------
# mesh spelling: the same kernels under shard_map
# ---------------------------------------------------------------------------

def fused_ce_loss_sharded(hidden: jax.Array, head_kernel: jax.Array,
                          labels: jax.Array,
                          loss_mask: Optional[jax.Array] = None,
                          *, mesh, block_n: int = 1024, block_v: int = 512,
                          interpret: Optional[bool] = None,
                          inner: str = "pallas"
                          ) -> tuple[jax.Array, jax.Array]:
    """``fused_ce_loss`` on a dp/fsdp/tp mesh (shard_map over the Pallas
    kernels — pallas_call is not auto-partitionable under GSPMD, which is
    why the plain spelling is single-device).

    ``inner`` selects the per-device tile engine: "pallas" (the Mosaic
    kernels, TPU) or "scan" (losses._scan_ce_totals — portable lax with
    the identical collective structure). The shard_map wrapper is the
    same either way: inside it XLA sees LOCAL shapes, so the vocab
    tiling survives partitioning at any scale — left to GSPMD, the
    plain scan spelling re-materializes full-vocab buffers at 8B
    (measured, scripts/scale_aot.py).

    Layout (parallel/sharding.py rules): hidden [B, T, E] rides the batch
    sharding P(('dp','fsdp'), None, None); the head [V, E] is a param
    sharded P('tp', 'fsdp'). Per device: the head shard is all-gathered
    (the SAME traffic GSPMD inserts for the materialized-logits matmul
    against these shardings), the device's rows are then split across tp
    as well — every device computes a DISTINCT row chunk against the full
    vocabulary, so tp scales the kernel instead of duplicating it — and
    the masked totals psum across the whole mesh. Reverse-mode AD of the
    shard_map transposes the all-gathers into psum_scatters, landing dW
    shards exactly where the optimizer expects them.

    sp meshes compose too: the engine shifts labels GLOBALLY before
    sharding (labels-carry-the-shift, engine/train.py), so each sequence
    shard is self-contained and the kernel never reads across a
    sequence-shard boundary; the sp axis simply joins the row split.
    """
    if inner not in ("pallas", "scan"):
        raise ValueError(f"unknown inner tile engine {inner!r}")
    if inner == "pallas" and interpret is None:
        interpret = _FORCE_INTERPRET
    block_n, block_v = _resolve_blocks(block_n, block_v)

    names = mesh.axis_names
    row_axes = tuple(a for a in ("dp", "fsdp") if a in names)
    sp_ax = "sp" if "sp" in names else None
    tp_ax = "tp" if "tp" in names else None
    fsdp_ax = "fsdp" if "fsdp" in names else None
    tp = int(mesh.shape[tp_ax]) if tp_ax else 1
    psum_axes = (row_axes + ((sp_ax,) if sp_ax else ())
                 + ((tp_ax,) if tp_ax else ()))

    if loss_mask is None:
        loss_mask = jnp.ones(labels.shape, jnp.float32)

    def local(h, w, y, m):
        # reassemble the full head from its tp x fsdp shards
        if fsdp_ax is not None:
            w = jax.lax.all_gather(w, fsdp_ax, axis=1, tiled=True)
        if tp_ax is not None:
            w = jax.lax.all_gather(w, tp_ax, axis=0, tiled=True)
        e = h.shape[-1]
        h2 = h.reshape(-1, e)
        y2 = y.reshape(-1)
        m2 = m.reshape(-1)
        if tp > 1:
            # this device's slice of the local rows: tp peers hold the
            # same batch shard, so carving it up makes tp a second data
            # axis for the kernel (zero duplicate FLOPs). Padding rows
            # carry mask 0 and vanish from both totals; AD transposes the
            # pad back to a slice for dh.
            n = h2.shape[0]
            n_pad = _round_up(n, tp)
            if n_pad > n:
                h2 = jnp.pad(h2, ((0, n_pad - n), (0, 0)))
                y2 = jnp.pad(y2, (0, n_pad - n))
                m2 = jnp.pad(m2, (0, n_pad - n))
            per = n_pad // tp
            i = jax.lax.axis_index(tp_ax)
            h2 = jax.lax.dynamic_slice_in_dim(h2, i * per, per, 0)
            y2 = jax.lax.dynamic_slice_in_dim(y2, i * per, per, 0)
            m2 = jax.lax.dynamic_slice_in_dim(m2, i * per, per, 0)
        if inner == "scan":
            from .losses import _scan_ce_totals
            total, count = _scan_ce_totals(h2, w, y2, m2, chunk=block_v)
        else:
            total, count = _fused_ce_totals(h2, w, y2, m2, block_n=block_n,
                                            block_v=block_v,
                                            interpret=interpret)
        total = jax.lax.psum(total, psum_axes)
        count = jax.lax.psum(count, psum_axes)
        return total, count

    total, count = shard_map(
        local, mesh=mesh,
        # sequence axis rides sp (the engine's mesh spelling shifts the
        # LABELS, not the hidden states, so sequence shards carry no
        # cross-shard dependency — see _fused_lm_loss)
        in_specs=(P(row_axes or None, sp_ax, None),
                  P(tp_ax, fsdp_ax),
                  P(row_axes or None, sp_ax),
                  P(row_axes or None, sp_ax)),
        out_specs=(P(), P()),
        check_vma=False,
    )(hidden, head_kernel, labels, loss_mask)
    return total / jnp.maximum(count, 1.0), jnp.maximum(count, 1.0)
