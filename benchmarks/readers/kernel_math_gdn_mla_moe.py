"""Operations and bytes the delta-rule decode update and the latent decode
attention of a hybrid need, from shapes alone. Each is checked against a
hand count in `tests/test_gdn_mla_moe.py`. (A held SwiGLU expert is the
accepted expert cell's expert, three matrices: `moe_held_swiglu_roofline`
is read by `trace_kernel_mla_moe` with `kernel_math_mla_moe`'s count.)

Conventions as in `kernel_math`: one multiply-add is two operations;
nothing recomputed or padded is counted; a weight is read once per program
run however many rows use it."""

from __future__ import annotations

from .kernel_math_mla_moe import mla_decode_bytes, roofline_seconds  # noqa: F401


def gdn_state_bytes(value_heads: int, key_dim: int, value_dim: int,
                    itemsize: int = 4) -> int:
    """One slot's recurrent state in one layer: float32 [Hv, dk, dv]."""
    return value_heads * key_dim * value_dim * itemsize


def gdn_decode_work(slot_steps: float, value_heads: int, key_dim: int,
                    value_dim: int) -> tuple[float, float]:
    """(operations, bytes) of the one-token delta-rule updates: every live
    slot's state in every linear layer is read once and written once
    (`slot_steps` is live slots x layers, summed over program runs); an
    element costs the decay (1), its part of the read `S^T k` (2), the
    rank-one write `k r^T` (2) and its part of the read-out `S^T q`
    (2)."""
    elems = value_heads * key_dim * value_dim
    return (7.0 * slot_steps * elems,
            2.0 * slot_steps * gdn_state_bytes(value_heads, key_dim,
                                               value_dim))


def mla_hybrid_decode_bytes(live_tokens: float, kv_lora_rank: int,
                            rope_dim: int, latent_layers: int,
                            itemsize: int = 2) -> float:
    """Bytes the latent decode attention must read: the latent row and the
    shared rotary key of every live token of every active sequence, once
    in each layer THAT CACHES THEM (a hybrid's full-attention layers, not
    every layer: `mla_decode_bytes` with the layers counted by the
    caller)."""
    return mla_decode_bytes(live_tokens, kv_lora_rank, rope_dim,
                            latent_layers, itemsize)
