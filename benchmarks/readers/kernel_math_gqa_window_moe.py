"""Bytes the two paged decode attentions of a model with sliding-window
layers beside global ones must read, from shapes alone. Each is checked
against a hand count in `tests/test_gqa_window_moe.py`. (Its experts'
grouped product is the accepted expert cell's: `moe_experts_roofline`,
read by `trace_kernel_mla_moe` with `kernel_math_mla_moe`'s count.)

Conventions as in `kernel_math`: nothing recomputed or padded is counted;
the query heads of a group share what is read."""

from __future__ import annotations

from .kernel_math_mla_moe import roofline_seconds  # noqa: F401


def kv_row_bytes(kv_heads: int, head_dim: int, itemsize: int = 2) -> int:
    """One token's K row and V row in one layer."""
    return 2 * kv_heads * head_dim * itemsize


def gqa_window_decode_bytes(window_live_tokens: float, kv_heads: int,
                            head_dim: int, window_layers: int,
                            itemsize: int = 2) -> float:
    """What the window layers MUST read: the K and V rows of `min(context,
    window)` tokens a live slot a decode step (`window_live_tokens`, the
    program's counter `serve.kv.window.live_tokens`, summed over the
    steps), once in each sliding layer."""
    return (float(window_live_tokens) * kv_row_bytes(kv_heads, head_dim,
                                                     itemsize)
            * window_layers)


def gqa_full_decode_bytes(live_tokens: float, kv_heads: int, head_dim: int,
                          full_layers: int, itemsize: int = 2) -> float:
    """What the global layers must read: the K and V rows of EVERY live
    token of every active sequence, once in each full-attention layer."""
    return (float(live_tokens) * kv_row_bytes(kv_heads, head_dim, itemsize)
            * full_layers)
