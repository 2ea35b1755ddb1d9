"""Operations and bytes the state-space decode update and the latent
routed-expert layer need, from shapes alone. Each is checked against a hand
count in `tests/test_ssm_moe.py`.

Conventions as in `kernel_math`: one multiply-add is two operations;
nothing recomputed or padded is counted; a weight is read once per program
run however many rows use it."""

from __future__ import annotations


def ssm_state_bytes(heads: int, head_dim: int, state: int,
                    itemsize: int = 4) -> int:
    """One slot's recurrent state in one layer: float32 [H, P, N]."""
    return heads * head_dim * state * itemsize


def ssm_decode_work(slot_steps: float, heads: int, head_dim: int,
                    state: int) -> tuple[float, float]:
    """(operations, bytes) of the one-token state updates: every live
    slot's state in every state-space layer is read once and written once
    (`slot_steps` is live slots x layers, summed over program runs); an
    element costs a decay, an outer-product term and its part of the
    read-out (h*a + x*b: 3, h*c summed: 2)."""
    elems = heads * head_dim * state
    return (5.0 * slot_steps * elems,
            2.0 * slot_steps * ssm_state_bytes(heads, head_dim, state))


def latent_expert_params(latent: int, width: int) -> int:
    """One routed expert in the latent: the up and down matrices of a
    squared-ReLU pair (no gate)."""
    return 2 * latent * width


def moe_latent_work(rows: float, experts_touched: float, latent: int,
                    width: int, itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of the grouped products: every row computed
    HERE goes through one expert's two matrices (2 operations a
    parameter); every touched expert's two matrices are read once. `rows`
    and `experts_touched` are sums over layers and program runs."""
    per = latent_expert_params(latent, width)
    return 2.0 * rows * per, float(experts_touched) * per * itemsize


def roofline_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak rate and bytes over peak bandwidth."""
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
