"""Loss functions for causal LM training/eval.

Reproduces the reference's HF-style ``labels=input_ids`` shifted
cross-entropy (hivetrain/training_manager.py:380-385,
hivetrain/validation_logic.py:86-91) as explicit jittable functions, with
fp32 log-softmax over bf16 logits and padding-aware token counting (the
reference masks pad via HF's internal -100 handling; here the mask is an
explicit argument).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def cross_entropy_with_logits(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-token CE, fp32. logits [..., V], labels [...] int."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - label_logits


def causal_lm_loss(logits: jax.Array, input_ids: jax.Array,
                   loss_mask: Optional[jax.Array] = None
                   ) -> tuple[jax.Array, jax.Array]:
    """Shifted next-token loss.

    logits: [B, T, V]; input_ids: [B, T]; loss_mask: [B, T] 1.0 where the
    *label* token is real (pad and cross-segment boundaries excluded by the
    data pipeline).

    Returns (mean_loss, token_count) — token_count lets callers aggregate
    exactly across shards/batches (sum(loss*count)/sum(count)).
    """
    shift_logits = logits[:, :-1, :]
    shift_labels = input_ids[:, 1:]
    per_tok = cross_entropy_with_logits(shift_logits, shift_labels)
    if loss_mask is not None:
        m = loss_mask[:, 1:].astype(per_tok.dtype)
    else:
        m = jnp.ones_like(per_tok)
    total = jnp.sum(per_tok * m)
    count = jnp.maximum(jnp.sum(m), 1.0)
    return total / count, count


def perplexity(mean_loss: jax.Array) -> jax.Array:
    """The validator's second metric (hivetrain/validation_logic.py:93-97)."""
    return jnp.exp(mean_loss)


def fused_linear_cross_entropy(hidden: jax.Array, head_kernel: jax.Array,
                               labels: jax.Array,
                               loss_mask: Optional[jax.Array] = None,
                               *, chunk: int = 4096, impl: str = "auto",
                               interpret: Optional[bool] = None,
                               mesh=None
                               ) -> tuple[jax.Array, jax.Array]:
    """Shifted-label CE of ``logits = hidden @ head_kernel.T`` WITHOUT ever
    materializing the [N, V] logits tensor.

    The standard path materializes f32 logits (GPT-2-124M at B8/T1024:
    ~1.6 GB per traversal, several traversals per step). Two spellings of
    the fix:

    - ``impl="pallas"`` (ops/pallas_ce.py): hand-written forward/backward
      kernels with the logits tiles living in VMEM only — the preferred
      path on TPU.
    - ``impl="scan"``: vocabulary scanned in ``chunk``-column tiles with a
      running (max, sumexp, label-logit) online softmax — the same trick
      flash attention plays on the sequence axis, applied to the vocab
      axis — the backward recomputing each tile via jax.checkpoint.
      Portable (any backend), but pays scan/checkpoint overhead.

    ``impl="auto"`` picks pallas when the backend/shape supports it, else
    scan. hidden: [..., E] activations ALREADY shifted/aligned to
    ``labels`` [...]; head_kernel: [V, E] (the tied wte); loss_mask like
    labels. Returns (mean_loss, token_count), the causal_lm_loss contract.
    """
    if impl == "auto":
        from .pallas_ce import pallas_ce_available
        impl = "pallas" if pallas_ce_available(hidden, head_kernel) else "scan"
    if impl not in ("pallas", "scan"):
        raise ValueError(f"unknown fused-CE impl {impl!r}")
    if impl == "scan" and interpret is not None:
        # interpret is a Pallas-only knob; silently dropping it would let
        # an off-TPU cross-check (impl left at "auto" -> scan) compare
        # the scan path against itself and prove nothing — same guard on
        # both the mesh and single-device routes
        raise ValueError("interpret= applies only to impl='pallas'; "
                         f"this call resolved to impl={impl!r}")
    if mesh is not None:
        # EVERY mesh run goes through the shard_map wrapper: local shapes
        # keep the vocab tiling intact under partitioning (GSPMD undoes
        # the plain scan's tiling at scale — full-vocab [N, V] buffers
        # measured at 8B, scripts/scale_aot.py) and the collectives are
        # explicit. ``inner`` picks pallas kernels (TPU) or the lax scan.
        from .pallas_ce import fused_ce_loss_sharded
        return fused_ce_loss_sharded(hidden, head_kernel, labels,
                                     loss_mask, mesh=mesh,
                                     interpret=interpret, inner=impl)
    if impl == "pallas":
        # ``interpret=True`` acknowledges a deliberate off-TPU run (numeric
        # cross-checks); None lets the kernel resolve the backend and warn
        # if that lands it in interpret mode
        from .pallas_ce import fused_ce_loss
        return fused_ce_loss(hidden, head_kernel, labels, loss_mask,
                             interpret=interpret)
    h = hidden.reshape(-1, hidden.shape[-1])
    y = labels.reshape(-1)
    m = (jnp.ones_like(y, jnp.float32) if loss_mask is None
         else loss_mask.reshape(-1))
    total, count = _scan_ce_totals(h, head_kernel, y, m, chunk=chunk)
    count = jnp.maximum(count, 1.0)
    return total / count, count


def _scan_ce_totals(h: jax.Array, w: jax.Array, y: jax.Array,
                    m: jax.Array, *, chunk: int = 4096
                    ) -> tuple[jax.Array, jax.Array]:
    """(masked total CE, masked token count) of ``h @ w.T`` vs ``y`` by
    the vocab-tiled online softmax — the lax.scan twin of
    pallas_ce._fused_ce_totals, shaped for shard_map bodies: everything
    here is LOCAL (no collectives; the caller psums). h: [N, E], w:
    [V, E] (already gathered), y/m: [N]. Inside shard_map the shapes XLA
    sees are per-device, so GSPMD cannot undo the tiling the way it does
    when this scan is left to the partitioner at 8B scale (the round-5
    SCALE artifact measured full-vocab [N, V] buffers materializing)."""
    E = h.shape[-1]
    V = w.shape[0]
    n_chunks = -(-V // chunk)
    v_pad = n_chunks * chunk
    wt = w
    if v_pad > V:
        wt = jnp.concatenate(
            [wt, jnp.zeros((v_pad - V, E), wt.dtype)], axis=0)
    wt = wt.reshape(n_chunks, chunk, E).astype(h.dtype)
    N = h.shape[0]
    neg = jnp.float32(-1e30)

    def tile(carry, xs):
        mx, s, ll = carry
        idx, w_c = xs
        logits = jnp.einsum("ne,ce->nc", h, w_c,
                            preferred_element_type=jnp.float32)
        col = idx * chunk + jnp.arange(chunk)
        logits = jnp.where(col[None, :] < V, logits, neg)
        m_new = jnp.maximum(mx, jnp.max(logits, axis=-1))
        s = s * jnp.exp(mx - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1)
        ll = ll + jnp.sum(
            jnp.where(col[None, :] == y[:, None], logits, 0.0), axis=-1)
        return (m_new, s, ll), None

    init = (jnp.full((N,), neg, jnp.float32),
            jnp.zeros((N,), jnp.float32),
            jnp.zeros((N,), jnp.float32))
    (mx, s, ll), _ = jax.lax.scan(
        jax.checkpoint(tile), init, (jnp.arange(n_chunks), wt))
    per_tok = mx + jnp.log(s) - ll
    msk = m.astype(per_tok.dtype)
    return jnp.sum(per_tok * msk), jnp.sum(msk)


def classification_loss(logits: jax.Array, labels: jax.Array
                        ) -> tuple[jax.Array, jax.Array]:
    """Mean CE for the toy classification harnesses (the reference's MNIST
    smoke path, hivetrain/training_manager.py:462-644). logits [B, C],
    labels [B] int. Returns (mean_loss, example_count) with the same
    aggregation contract as causal_lm_loss."""
    per_ex = cross_entropy_with_logits(logits, labels)
    count = jnp.asarray(per_ex.shape[0], jnp.float32)
    return jnp.sum(per_ex) / count, count


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
