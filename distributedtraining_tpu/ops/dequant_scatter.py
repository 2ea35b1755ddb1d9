"""Fused dequantize->scatter-add kernel for packed wire-v2 entries.

The averager-side hot loop of the v2 wire: folding one packed
contribution ``{"idx": int32[k], "q": int8|f32[k], "scale": f32}`` into
a running f32 aggregate is ``acc[idx] += w * q_f32 * scale`` — k useful
element updates against a buffer of n >> k elements. The XLA spelling
(``delta._accum_packed``: ``flat.at[idx].add(w * q * scale)``) is
functionally a full-buffer copy plus a scatter. This kernel keeps the
accumulator leaf resident in VMEM, aliased in place
(``input_output_aliases``), and applies the k updates there, so the
dense intermediate of the densify-then-add spelling never exists.
Whether it beats XLA's scatter on the chip has not been measured.

Selection, not probing: :func:`enabled` is true on a TPU backend (or
when a test lane asked for the interpreter through
:func:`use_interpret`), and :func:`kernel_supports` is the shape rule;
once selected, build and compile errors propagate. Leaves the rule
rejects (flat size not a whole number of 128-lane rows, too big for
VMEM, too many entries for SMEM) ride the XLA spelling INSIDE the same
program — correctness identical (duplicate indices SUM in both, the
``_accum_packed`` convention; the screened-upstream hostile cases keep
their semantics because this kernel is only reached AFTER
``packed_matches`` admission, like every accumulate path).

Kernel layout: Mosaic cannot address one element of a 1-D VMEM ref at a
dynamic offset, and cannot read a scalar out of VMEM at all. So the
accumulator is viewed as ``[n/128, 128]`` rows (a free reshape), the
indices and the dequantized values (``q_f32 * w*scale`` — an O(k) XLA
elementwise op in the same program) arrive in SMEM, and each update is
a dynamic-sublane row read-modify-write under a one-hot lane mask.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# accumulator leaves above this many f32 elements stay on the XLA path:
# the aliased input and output each take a whole-leaf VMEM buffer
# (2 x 4 MB here; the v5e compiler's scoped limit is 16 MB, which a
# 2 Mi-element leaf fills exactly)
MAX_ACC_ELEMS = 1024 * 1024
# entries per call: idx + val ride SMEM at 8 bytes an entry (256 KB of
# the chip's 1 MB)
MAX_ENTRIES = 32 * 1024

_LANES = 128

# test hook: force the interpreter so CPU lanes exercise the
# KERNEL math instead of the XLA fallback (set via use_interpret)
_FORCE_INTERPRET = False


def use_interpret(on: bool) -> None:
    """Route :func:`enabled` callers through the interpreter (CPU test
    lanes). Production never sets this."""
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = bool(on)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_supports(n: int, k: int) -> bool:
    """Shape rule: whole 128-lane rows that fit VMEM, entries that fit
    SMEM, and something to scatter."""
    return (0 < k <= MAX_ENTRIES and n % _LANES == 0
            and 0 < n <= MAX_ACC_ELEMS)


def _scatter_kernel(idx_ref, val_ref, acc_ref, out_ref):
    """acc[idx[j]] += val[j] for j in [0, k). ``acc`` is aliased to
    ``out`` (one VMEM-resident buffer); the scatter is a serial
    read-modify-write loop — duplicates SUM, deterministically."""
    del acc_ref  # aliased: out_ref IS the accumulator
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def body(j, _):
        pos = idx_ref[j]
        row = pl.ds(pos // _LANES, 1)
        out_ref[row, :] = out_ref[row, :] + jnp.where(
            lane == pos % _LANES, val_ref[j], 0.0)
        return 0

    jax.lax.fori_loop(0, idx_ref.shape[0], body, 0)


def _build_call(n: int, interpret: bool):
    return pl.pallas_call(  # devprof: exempt (attributed under delta.dequant_scatter — the wrapped _accum_packed_kernel program this kernel runs inside)
        _scatter_kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # idx
            pl.BlockSpec(memory_space=pltpu.SMEM),            # val
            pl.BlockSpec(memory_space=pltpu.VMEM),            # acc
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n // _LANES, _LANES), jnp.float32),
        input_output_aliases={2: 0},
        interpret=interpret,
        name="dequant_scatter_add",
    )


def enabled() -> bool:
    """True when accumulate paths should route packed entries through
    the kernel: a TPU backend, or the CPU interpreter when a test lane
    forced it."""
    return _FORCE_INTERPRET or _on_tpu()


def dequant_scatter_add(flat: jax.Array, idx: jax.Array, q: jax.Array,
                        scale_w, *, interpret: Optional[bool] = None
                        ) -> jax.Array:
    """``flat.at[idx].add(q_f32 * scale_w)`` through the scatter kernel.
    Raises ``ValueError`` on a shape :func:`kernel_supports` rejects;
    build and compile errors propagate.

    ``flat`` f32 [n]; ``idx`` int32 [k]; ``q`` int8 or f32 [k];
    ``scale_w`` the pre-folded ``weight * scale`` scalar. Indexed-form
    entries only (dense-form k==n entries are a plain fused add XLA
    already handles well). ``interpret`` defaults to what
    :func:`use_interpret` set (False in production).
    """
    n, k = flat.shape[0], idx.shape[0]
    if not kernel_supports(n, k):
        raise ValueError(
            f"dequant_scatter_add: unsupported shapes n={n} k={k}")
    if interpret is None:
        interpret = _FORCE_INTERPRET
    val = q.astype(jnp.float32) * jnp.asarray(scale_w, jnp.float32)
    out = _build_call(n, interpret)(
        idx.astype(jnp.int32), val,
        flat.astype(jnp.float32).reshape(n // _LANES, _LANES))
    return out.reshape(n)
