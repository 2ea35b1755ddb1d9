"""Ring attention: sequence-parallel causal attention over ICI.

Long-context path (Liu et al., "Ring Attention with Blockwise Transformers"):
the sequence axis is sharded over the mesh's ``sp`` axis; each device holds a
query block and streams the K/V blocks around the ring with ``ppermute``,
accumulating attention with an online softmax (running max + denominator, all
fp32). Peak activation memory per device is O(T/sp), and the K/V transfers
overlap compute around the ICI ring — no [T, T] score matrix ever exists.

Causality across blocks: query block q at global offset qo attends K/V block
at offset ko with a full mask when ko + block < qo, a triangular mask when
ko == qo, and contributes nothing when ko > qo (still computed, masked to
-inf — a static ring schedule keeps XLA happy; skipping would need dynamic
control flow).

Usage: the engine calls ``set_ring_mesh(mesh)`` once; models route here via
``causal_attention(..., impl="ring")`` when sequence parallelism is on. With
no mesh set (or sp == 1) the dense path runs instead.

The reference has no long-context support at all (max seq 512,
SURVEY.md §5); this is a capability extension required for the TPU build.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e9

_RING_MESH: Mesh | None = None
_RING_AXIS = "sp"


def set_ring_mesh(mesh: Mesh | None, axis: str = "sp") -> None:
    """Install the mesh used by impl="ring" attention (engine calls this)."""
    global _RING_MESH, _RING_AXIS
    _RING_MESH = mesh
    _RING_AXIS = axis


def get_ring_mesh() -> tuple[Mesh | None, str]:
    return _RING_MESH, _RING_AXIS


def _ring_body(q, k, v, *, axis: str, axis_size: int, t_local: int):
    """Per-device blockwise attention; q/k/v are local [B, Tl, H, D]."""
    idx = jax.lax.axis_index(axis)
    scale = q.shape[-1] ** -0.5
    qf = q.astype(jnp.float32) * scale
    B, Tl, H, D = q.shape

    q_pos = idx * t_local + jnp.arange(Tl)  # global query positions

    def step(s, carry):
        acc, m_prev, l_prev, k_cur, v_cur = carry
        src = (idx - s) % axis_size  # which block we currently hold
        k_pos = src * t_local + jnp.arange(Tl)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf,
                            k_cur.astype(jnp.float32))
        mask = q_pos[:, None] >= k_pos[None, :]
        scores = jnp.where(mask[None, None, :, :], scores, NEG_INF)
        m_cur = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_cur.astype(jnp.float32))
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + pv
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_nxt = jax.lax.ppermute(k_cur, axis, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis, perm)
        return acc, m_new, l_new, k_nxt, v_nxt

    acc0 = jnp.zeros((B, Tl, H, D), jnp.float32)
    m0 = jnp.full((B, H, Tl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tl), jnp.float32)
    acc, m, l, _, _ = jax.lax.fori_loop(
        0, axis_size, step, (acc0, m0, l0, k, v))
    # rows with no visible keys (can't happen causally, but keep the math
    # total) and normalization
    l = jnp.maximum(l, 1e-30)
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   *, mesh: Mesh | None = None,
                   axis: str | None = None) -> jax.Array:
    """Causal ring attention; q/k/v are global [B, T, H, D] with T sharded
    over the sp axis (or replicated — shard_map partitions either way)."""
    mesh = mesh if mesh is not None else _RING_MESH
    axis = axis if axis is not None else _RING_AXIS
    if mesh is None or mesh.shape.get(axis, 1) == 1:
        from .attention import dot_product_attention, make_causal_mask
        mask = make_causal_mask(q.shape[1])[None, None, :, :]
        return dot_product_attention(q, k, v, mask)

    axis_size = mesh.shape[axis]
    B, T, H, D = q.shape
    if T % axis_size:
        raise ValueError(f"seq len {T} not divisible by {axis}={axis_size}")
    t_local = T // axis_size

    spec = P(None, axis, None, None)

    def body(q_, k_, v_):
        return _ring_body(q_, k_, v_, axis=axis, axis_size=axis_size,
                          t_local=t_local)

    fn = _shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                    out_specs=spec, check_vma=False)
    return fn(q, k, v)
