"""Operations and bytes a train step of the LFM2-MoE family needs, from
shapes and the step's own counters alone. Each is checked against a hand
count in `tests/test_lfm2_moe.py`.

Conventions as in `kernel_math`: one multiply-add is two operations; a
train step is 6 operations a multiplied parameter a token (forward 2,
backward 4); attention is counted CAUSAL and INSIDE documents (a packed
row's documents need the sum of their own lengths squared, not the row's);
nothing recomputed (remat) or padded is counted; expert rows are the rows
the step COUNTED here (`train.moe.rows`), not an expectation."""

from __future__ import annotations

from .kernel_math import roofline_seconds  # noqa: F401  (the readers')


def expert_params(hidden: int, width: int) -> int:
    """One routed SwiGLU expert: gate, up and down matrices."""
    return 3 * hidden * width


def dense_matmul_params(cfg: dict) -> int:
    """Parameters every token is multiplied by, outside the routed experts:
    the mixers' matrices, the dense FFNs, the routers (all their outputs),
    and the tied head (the lookup is not a product). Convolution taps and
    norm gains are elementwise and not counted."""
    E = cfg["hidden_size"]
    D = E // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * D, cfg["num_key_value_heads"] * D
    router = cfg.get("published", {}).get("num_experts", cfg["num_experts"])
    total = cfg["vocab_size"] * E
    for i, kind in enumerate(cfg["layer_types"]):
        total += (4 * E * E if kind == "conv"          # in_proj 3E, out_proj
                  else 2 * E * q + 2 * E * kv)         # q, out; k, v
        total += (3 * E * cfg["intermediate_size"]
                  if i < cfg["num_dense_layers"] else E * router)
    return total


def attention_layers(cfg: dict) -> int:
    return sum(kind == "full_attention" for kind in cfg["layer_types"])


def train_step_flops(cfg: dict, tokens: float, expert_rows: float,
                     doc_sq: float) -> float:
    """Forward plus backward of one step: 6 a dense parameter a token, 6 an
    expert parameter a ROW computed here (`expert_rows`, summed over the
    routed layers), and causal attention inside documents: its two
    products at 2 x len^2 x (heads x head_dim) / 2 each forward, three
    times that with the backward = 6 x `doc_sq` x hidden a layer
    (`doc_sq`: the sum of the documents' lengths squared)."""
    E = cfg["hidden_size"]
    return (6.0 * dense_matmul_params(cfg) * tokens
            + 6.0 * expert_params(E, cfg["moe_intermediate_size"])
            * expert_rows
            + 6.0 * attention_layers(cfg) * doc_sq * E)


def moe_train_work(rows: float, stacks: float, hidden: int, width: int,
                   itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of a train step's grouped products: every row
    computed here goes through one expert's three matrices three times
    (forward, the rows' gradient, the matrices' gradient: 2 operations a
    parameter each); every TOUCHED expert's matrices are read twice and
    their gradient written once (an expert without rows is skipped, and
    its gradient's zeros are not counted). `rows` and `stacks` (the
    counters `train.moe.rows` and `train.moe.experts_touched`: sums over
    routed layers and steps) are those of the traced steps."""
    per = expert_params(hidden, width)
    return 3.0 * 2.0 * rows * per, 3.0 * stacks * per * itemsize


def flash_packed_work(doc_sq: float, tokens: float, n_head: int, n_kv: int,
                      head_dim: int, backward: bool, itemsize: int = 2
                      ) -> tuple[float, float]:
    """(operations, bytes) of causal flash attention over PACKED rows, as
    `kernel_math.flash_attention_call` counts an unpacked one: forward two
    products, 2 x len^2 x heads x head_dim a document; backward five.
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v, o,
    do and writes dq, dk, dv; k and v have `n_kv` heads."""
    ops = (5.0 if backward else 2.0) * doc_sq * n_head * head_dim
    q_like, kv_like = (4, 4) if backward else (2, 2)
    nbytes = tokens * head_dim * itemsize * (q_like * n_head + kv_like * n_kv)
    return ops, float(nbytes)
