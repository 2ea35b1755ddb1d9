"""A dropless routed-expert layer: route, sort by expert, two grouped
products, weighted un-sort and sum.

One function serves a prefill's thousands of rows and a decode step's few
hundred (``slots x top_k``): the ``tokens x top_k`` rows are sorted by
their expert, so each expert's rows lie together and a GROUPED product
multiplies every group by its own expert's matrix. Each touched expert's
weights are read once per call; an expert that no row chose is not read
(the Pallas path) and no row is dropped, whatever the skew: there is no
capacity factor anywhere.

The grouped product, by a rule over backend and shape (never a probe):
JAX's own Pallas grouped matmul
(``jax.experimental.pallas.ops.tpu.megablox.gmm``) on a TPU at shapes its
tiling takes, ``jax.lax.ragged_dot`` otherwise (the CPU path, and what
the kernel is tested against). On the chip at the decode shape (384 rows
over 128 experts) the kernel takes 1.07 + 0.57 ms a layer where
``ragged_dot`` takes 1.42 + 0.94 (PERF.md, PR 27).

The router is DeepSeek-V3's ``noaux_tc`` with one group: sigmoid scores
in float32, the choice made on ``scores + bias`` (the bias moves the
choice and never the weights), the chosen scores normalised to sum to one
and scaled.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the Pallas grouped product's tiles. Rows are padded up to a multiple
# of the m-tile; a decode step has ~3 rows an expert, so every visited
# (expert, tile) pair multiplies a whole m-tile for a few live rows, and
# 128 keeps that under the time the expert's weight read takes. The whole
# contraction in one k-tile and a 512- or 1,024-wide n-tile read the
# touched experts at 704 / 664 GB/s (gate+up / down) at the decode shape,
# where (128, 512, 768) read 636 and jax.lax.ragged_dot 531 / 400
# (benchmarks/tools/ab_mla_moe.py on the chip, PERF.md PR 27)
GMM_TILE_M = 128
_GMM_TILE_K = 2048
_HIGHEST = jax.lax.Precision.HIGHEST


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def route(h: jax.Array, w_router: jax.Array, bias: jax.Array, top_k: int,
          scale: float, norm: bool = True) -> tuple[jax.Array, jax.Array]:
    """``h`` [N, E], ``w_router`` [E, G], ``bias`` [G] -> (choice
    [N, top_k] int32, weights [N, top_k] float32). Scores are float32
    whatever the activations' dtype: a near-tie between the k-th and the
    next expert is decided here."""
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(jnp.dot(
            h.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=_HIGHEST))
        _, choice = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        picked = jnp.take_along_axis(scores, choice, axis=-1)
        if norm:
            picked = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                               + 1e-20)
        return choice.astype(jnp.int32), picked * scale


def gmm_supports(k: int, n: int, dtype) -> bool:
    """The shapes the Pallas grouped matmul is given: bfloat16 operands
    (its tiles are laid out for them) and k, n that its tiles divide or
    cover in whole lane tiles."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and k % 128 == 0
            and n % 128 == 0)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, impl: str | None = None) -> jax.Array:
    """``lhs`` [m, k] with rows sorted by group, ``rhs`` [G, k, n],
    ``group_sizes`` [G] -> [m, n]: rows of group g times ``rhs[g]``.
    ``impl`` is for the tests and the chip's A/B; None is the rule."""
    m, k = lhs.shape
    n = rhs.shape[-1]
    if impl is None:
        impl = ("gmm" if _on_tpu() and gmm_supports(k, n, lhs.dtype)
                else "ragged_dot")
    if impl == "ragged_dot":
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes,
            precision=_HIGHEST if lhs.dtype == jnp.float32 else None,
            preferred_element_type=lhs.dtype)
    if impl not in ("gmm", "gmm_interpret"):
        raise ValueError(f"unknown grouped product {impl!r}")
    from jax.experimental.pallas.ops.tpu import megablox
    pad = -m % GMM_TILE_M
    if pad:
        # rows past sum(group_sizes) belong to no group: the kernel
        # leaves them alone and the caller never reads them
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tiling = (GMM_TILE_M, min(_GMM_TILE_K, k),
              1024 if n % 1024 == 0 else 512 if n % 512 == 0 else 128)
    # positional: the public gmm is a custom_vjp
    out = megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tiling, None, None,
                       False, impl == "gmm_interpret")
    return out[:m] if pad else out


def _experts_sorted(x_sorted, w_gate_up, w_down, group_sizes, impl):
    """The two grouped products over rows already sorted by expert:
    gate and up fused as one ``[G, E, 2F]`` product, SwiGLU, down."""
    F = w_down.shape[1]
    gu = grouped_matmul(x_sorted, w_gate_up, group_sizes, impl=impl)
    act = (jax.nn.silu(gu[:, :F].astype(jnp.float32))
           * gu[:, F:].astype(jnp.float32)).astype(x_sorted.dtype)
    return grouped_matmul(act, w_down, group_sizes, impl=impl)


def routed_experts(h: jax.Array, choice: jax.Array, weights: jax.Array,
                   w_gate_up: jax.Array, w_down: jax.Array, *,
                   live: jax.Array | None = None,
                   impl: str | None = None) -> tuple[jax.Array, dict]:
    """``sum_e w_e E_e(h)`` for the chosen experts of every row.

    ``h`` [N, E]; ``choice``/``weights`` [N, k]; ``w_gate_up`` [G, E, 2F]
    (gate columns first); ``w_down`` [G, F, E]. ``live`` [N] bool marks
    the rows that are not padding: every row is computed (shapes are
    static) and only live ones are counted. Returns ([N, E] in ``h``'s
    dtype, {"moe_rows", "moe_experts_touched"} int32 scalars)."""
    N, k = choice.shape
    G = w_gate_up.shape[0]
    flat = choice.reshape(-1)                            # [N*k]
    order = jnp.argsort(flat, stable=True)
    group_sizes = jnp.bincount(flat, length=G).astype(jnp.int32)
    with jax.named_scope("moe.experts"):
        x_sorted = jnp.take(h, order // k, axis=0)
        y = _experts_sorted(x_sorted, w_gate_up, w_down, group_sizes, impl)
        w_sorted = jnp.take(weights.reshape(-1), order)
        y = y.astype(jnp.float32) * w_sorted[:, None]
        # un-sort: row r of the sorted order came from flat row order[r]
        y = jnp.take(y, jnp.argsort(order), axis=0)
        out = jnp.sum(y.reshape(N, k, -1), axis=1).astype(h.dtype)
    if live is None:
        rows, touched = jnp.int32(N * k), jnp.sum(group_sizes > 0)
    else:
        live_flat = jnp.repeat(live.astype(jnp.int32), k)
        rows = jnp.sum(live_flat)
        touched = jnp.sum(jnp.zeros((G,), jnp.int32).at[flat].add(live_flat)
                          > 0)
    return out, {"moe_rows": rows.astype(jnp.int32),
                 "moe_experts_touched": touched.astype(jnp.int32)}
