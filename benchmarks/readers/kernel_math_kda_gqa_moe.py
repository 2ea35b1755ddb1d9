"""Operations and bytes the per-channel delta-rule decode update and the
grouped-query paged decode attention of a hybrid need, from shapes alone.
Each is checked against a hand count in `tests/test_kda_gqa_moe.py`. (A
held SwiGLU expert is the accepted expert cell's expert, three matrices:
`moe_held_swiglu_roofline` is read by `trace_kernel_mla_moe` with
`kernel_math_mla_moe`'s count.)

Conventions as in `kernel_math`: one multiply-add is two operations;
nothing recomputed or padded is counted; a weight is read once per program
run however many rows use it."""

from __future__ import annotations

from .kernel_math_mla_moe import roofline_seconds  # noqa: F401


def kda_state_bytes(heads: int, head_dim: int, itemsize: int = 4) -> int:
    """One slot's recurrent state in one layer: float32 [H, d, d]."""
    return heads * head_dim * head_dim * itemsize


def kda_decode_work(slot_steps: float, heads: int, head_dim: int
                    ) -> tuple[float, float]:
    """(operations, bytes) of the one-token per-channel delta-rule
    updates: every live slot's state in every delta-rule layer is read
    once and written once, and beside it the step's own rows are read in
    float32: q, k, v and the decay, `heads x head_dim` each (the decay is
    a VECTOR a head here; a scalar gate's would be `heads`), and beta a
    head (`slot_steps` is live slots x layers, summed over program runs).
    An element of the state costs the decay (1), its part of the read
    `S^T k` (2), the rank-one write `k r^T` (2) and its part of the
    read-out `S^T q` (2)."""
    elems = heads * head_dim * head_dim
    rows = (4 * heads * head_dim + heads) * 4
    return (7.0 * slot_steps * elems,
            slot_steps * (2.0 * kda_state_bytes(heads, head_dim) + rows))


def gqa_paged_decode_bytes(live_tokens: float, kv_heads: int, head_dim: int,
                           attention_layers: int, itemsize: int = 2
                           ) -> float:
    """Bytes the paged decode attention must read: one K and one V row of
    `kv_heads x head_dim` for every live token of every active sequence,
    once in each layer THAT CACHES THEM (a hybrid's attention layers).
    The query heads of a group share what is read."""
    return (2.0 * live_tokens * kv_heads * head_dim * itemsize
            * attention_layers)
