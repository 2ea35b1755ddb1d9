"""Operations and bytes the algorithm needs, from shapes alone. Each is
checked against a hand count in `tests/test_kernel_math.py`.

Conventions: one multiply-add is two operations; attention is counted
CAUSAL (half of the square), which is what the algorithm needs and keeps a
share of the peak from being flattered; recomputation (remat) is never
counted."""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix product, once each: the
    blocks' four matrices and the (tied) head. Embedding rows are looked
    up, not multiplied."""
    E, L = cfg["n_embd"], cfg["n_layer"]
    return L * 12 * E * E + cfg["padded_vocab"] * E


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward: 6 per matrix parameter, and causal
    attention's two products (QK^T, PV) at 2*T*E/2 each forward, three
    times that with the backward pass: 6*T*E per layer."""
    E, L = cfg["n_embd"], cfg["n_layer"]
    return 6.0 * matmul_params(cfg) + 6.0 * L * seq_len * E


def flash_attention_call(batch: int, seq_len: int, n_embd: int,
                         backward: bool, itemsize: int = 2
                         ) -> tuple[float, float]:
    """(operations, bytes) of one causal flash-attention kernel call over
    [batch, seq_len, n_embd]. Forward: QK^T and PV, 2*B*T*T*E each over the
    full square, halved by causality = 2*B*T^2*E. Backward: five such
    products (scores again, dV, dP, dQ, dK) = 5*B*T^2*E. Bytes: forward
    reads q, k, v and writes o; backward reads q, k, v, o, do and writes
    dq, dk, dv."""
    ops = (5.0 if backward else 2.0) * batch * seq_len * seq_len * n_embd
    tensors = 8 if backward else 4
    return ops, float(tensors * batch * seq_len * n_embd * itemsize)


def paged_decode_bytes(live_tokens: float, n_embd: int, itemsize: int = 2
                       ) -> float:
    """Bytes one layer's decode attention must read: K and V of every live
    token of every active sequence."""
    return 2.0 * live_tokens * n_embd * itemsize


def roofline_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak rate and bytes over peak bandwidth."""
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
