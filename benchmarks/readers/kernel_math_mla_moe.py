"""Operations and bytes the routed-expert layer and the latent decode
attention need, from shapes alone. Each is checked against a hand count in
`tests/test_kernel_math_mla_moe.py`.

Conventions as in `kernel_math`: one multiply-add is two operations;
nothing recomputed or padded is counted; a weight is read once per program
run however many rows use it."""

from __future__ import annotations


def expert_params(hidden: int, width: int) -> int:
    """One routed expert: the gate, up and down matrices of a SwiGLU."""
    return 3 * hidden * width


def moe_experts_work(rows: float, experts_touched: float, hidden: int,
                     width: int, itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of the grouped products: every routed row goes
    through one expert's three matrices (2 operations a parameter); every
    touched expert's three matrices are read once. `rows` and
    `experts_touched` are sums over layers and program runs."""
    per = expert_params(hidden, width)
    return 2.0 * rows * per, float(experts_touched) * per * itemsize


def mla_decode_bytes(live_tokens: float, kv_lora_rank: int, rope_dim: int,
                     n_layer: int, itemsize: int = 2) -> float:
    """Bytes the latent decode attention must read in every layer: the
    latent row and the shared rotary key of every live token of every
    active sequence, once (the same latent rows serve the scores and the
    output)."""
    return float(live_tokens) * (kv_lora_rank + rope_dim) * itemsize * n_layer


def roofline_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak rate and bytes over peak bandwidth."""
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
