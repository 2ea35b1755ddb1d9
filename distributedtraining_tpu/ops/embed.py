"""Embedding lookup with a mesh-aware backward.

The gather forward is optimal everywhere. Its default VJP, however, is a
scatter-add, and on meshes with BOTH dp > 1 and fsdp > 1 GSPMD must
reshard the incoming [B, T, E] cotangent from batch sharding
(('dp','fsdp') on dim 0, enumerated row-major) onto the table's
embed/fsdp axis (enumerated fsdp-major) — a transfer the SPMD
partitioner cannot express on that device order, so it falls back to
"involuntary full rematerialization": the whole cotangent is replicated
to every device and re-partitioned, each step.

The one-hot einsum spelling of the same backward is a plain matmul
(contract over batch x seq): every device computes a partial [V, E]
gradient from its LOCAL cotangent shard and GSPMD reduces it straight
onto the table sharding — no cotangent reshard, and the work rides the
MXU. The one-hot tensor only exists inside the backward pass and fuses
into the matmul. This is the standard TPU embedding trick (MaxText's
iota-embed); the reference has no counterpart (single-device PyTorch).

``embed_lookup`` picks the spelling at trace time from the ambient mesh
(the engines activate their mesh while tracing): scatter stays the
default everywhere the reshard is expressible (single device, dp-only,
fsdp-only), since the matmul backward costs ~B*T*V*E extra FLOPs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def ambient_mesh():
    """The multi-device mesh of the enclosing ``with mesh:`` — the train
    engine traces the model under one (engine/train.py) — or None."""
    # jax 0.9.0 has no public reader for the legacy mesh context
    # (jax.interpreters.pxla.thread_resources was deprecated in 0.8.2)
    from jax._src.mesh import thread_resources
    mesh = thread_resources.env.physical_mesh
    return None if mesh.empty or mesh.size == 1 else mesh


def _ambient_mesh_needs_matmul_bwd() -> bool:
    """True when the mesh active during tracing has both dp>1 and fsdp>1 —
    the configuration whose gather-backward reshard GSPMD cannot express
    (see module docstring)."""
    mesh = ambient_mesh()
    return (mesh is not None and mesh.shape.get("dp", 1) > 1
            and mesh.shape.get("fsdp", 1) > 1)


import functools


@functools.lru_cache(maxsize=None)
def _take_matmul_bwd(vocab: int, dtype_name: str):
    """custom_vjp gather specialized on the (static) table vocab/dtype."""

    @jax.custom_vjp
    def take(table, ids):
        return jnp.take(table, ids, axis=0, mode="clip")

    def fwd(table, ids):
        return take(table, ids), ids

    def bwd(ids, g):
        onehot = jax.nn.one_hot(ids, vocab, dtype=g.dtype)
        dtable = jnp.einsum("...v,...e->ve", onehot, g)
        return (dtable.astype(dtype_name),
                np.zeros(ids.shape, jax.dtypes.float0))  # int ids: no tangent

    take.defvjp(fwd, bwd)
    return take


def embed_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    """``table[ids]`` with the backward spelling chosen for the ambient
    mesh. Forward is a gather either way."""
    if _ambient_mesh_needs_matmul_bwd():
        return _take_matmul_bwd(table.shape[0], str(table.dtype))(table, ids)
    # mode="clip" preserves `table[ids]` getitem semantics: jnp.take's
    # default is "fill", which turns an out-of-range index (e.g. eval at
    # T > n_positions) into NaN rows instead of the clamped lookup the
    # indexing spelling always did. (The matmul backward's one-hot zeroes
    # OOB rows' gradients; OOB positions are a config error either way.)
    return jnp.take(table, ids, axis=0, mode="clip")
