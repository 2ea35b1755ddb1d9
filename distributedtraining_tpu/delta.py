"""Pytree weight-delta algebra.

The smallest, most-depended-on layer of the framework: a *delta* is the
per-parameter difference ``trained - base`` between two structurally identical
parameter pytrees. Miners ship deltas, validators apply them for scoring, the
averager merges stacks of them.

Reference behavior being reproduced (TPU-idiomatically):
- delta computation: hivetrain/training_manager.py:417-422
- delta application: hivetrain/validation_logic.py:251-259
- NaN screening of untrusted submissions: hivetrain/averaging_logic.py:121-127
- shape screening of untrusted submissions: hivetrain/averaging_logic.py:404-410

Everything here is a pure function on pytrees; the heavy ones are jittable.
"""

from __future__ import annotations

import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .utils import devprof, obs

Params = Any  # a pytree of arrays


def tree_sub(a: Params, b: Params) -> Params:
    """Elementwise ``a - b`` over structurally identical pytrees."""
    return jax.tree_util.tree_map(lambda x, y: x - y, a, b)


def tree_add(a: Params, b: Params) -> Params:
    """Elementwise ``a + b`` over structurally identical pytrees."""
    return jax.tree_util.tree_map(lambda x, y: x + y, a, b)


def compute_delta(trained: Params, base: Params,
                  wire_dtype: str | None = None) -> Params:
    """delta = trained - base (the artifact a miner uploads).

    ``wire_dtype="bfloat16"`` casts the result for the wire: half the
    artifact bytes, transport bandwidth, and merge HBM. The precision
    cost is bf16 rounding of the DELTA (not the weights) — ~0.4% relative
    on an update the averager then mixes at f32 (weighted_merge upcasts).
    A documented extension over the reference, which ships f32 torch
    tensors (training_manager.py:417-422); receivers accept both
    spellings (screen_delta ``extra_dtypes``), so publishers opt in
    per-miner without a fleet-wide flag."""
    d = tree_sub(trained, base)
    if wire_dtype is None:
        return d
    dt = jnp.dtype(wire_dtype)
    return jax.tree_util.tree_map(
        lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, d)


def apply_delta(base: Params, delta: Params) -> Params:
    """Reconstruct trained params from base + delta."""
    return tree_add(base, delta)


def tree_scale(a: Params, s) -> Params:
    return jax.tree_util.tree_map(lambda x: x * s, a)


def zeros_like(a: Params) -> Params:
    return jax.tree_util.tree_map(jnp.zeros_like, a)


# ---------------------------------------------------------------------------
# Screening of untrusted submissions
# ---------------------------------------------------------------------------

def tree_finite(tree: Params) -> jax.Array:
    """Scalar bool array: True when EVERY leaf is finite. The jittable
    body of the finiteness screen — publishers fuse it into their jitted
    snapshot programs (MinerLoop's delta+wire+compress program returns the
    delta AND this flag from ONE program), so the screen costs no separate
    dispatch or host round-trip on the push path. Float leaves only are
    screened; integer leaves are finite by construction."""
    flags = [jnp.any(~jnp.isfinite(leaf))
             for leaf in jax.tree_util.tree_leaves(tree)
             if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact)]
    if not flags:
        return jnp.asarray(True)
    return jnp.logical_not(jnp.any(jnp.stack(flags)))


_tree_finite_jit = devprof.wrap("delta.finite", jax.jit(tree_finite))


def has_nonfinite(tree: Params) -> bool:
    """True if any leaf contains NaN/Inf. Host-side screen for untrusted
    deltas. One jitted program, NOT an eager per-leaf loop: on a
    cross-process mesh each eager op is its own collective program, and a
    ~150-leaf model would issue ~150 gloo/ICI round-trips per screen."""
    if not jax.tree_util.tree_leaves(tree):
        return False
    return not bool(jax.device_get(_tree_finite_jit(tree)))


def shapes_match(tree: Params, reference: Params, *, check_dtype: bool = False,
                 extra_dtypes: Sequence[str] = ()) -> bool:
    """True iff ``tree`` has the same structure and per-leaf shapes as ``reference``.

    Used to reject malformed miner submissions before any compute touches
    them. ``extra_dtypes`` lists alternate dtypes a FLOAT leaf may carry in
    addition to the reference's own (the bf16 wire-delta spelling) — f64 or
    integer substitutions stay rejected.
    """
    ts = jax.tree_util.tree_structure(tree)
    rs = jax.tree_util.tree_structure(reference)
    if ts != rs:
        return False
    extra = {np.dtype(d) for d in extra_dtypes}
    for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(reference)):
        if tuple(np.shape(a)) != tuple(np.shape(b)):
            return False
        if check_dtype:
            # numpy-side comparison: jnp.asarray would silently downcast a
            # hostile f64 wire tensor to f32 under x64-disabled JAX and the
            # check would pass vacuously.
            da = a.dtype if hasattr(a, "dtype") else np.asarray(a).dtype
            db = b.dtype if hasattr(b, "dtype") else np.asarray(b).dtype
            if np.dtype(da) != np.dtype(db) and not (
                    np.dtype(da) in extra
                    and np.issubdtype(np.dtype(db), np.floating)):
                return False
    return True


def screen_delta(delta: Params, base: Params, *, max_abs: float | None = None,
                 check_dtype: bool = True,
                 extra_dtypes: Sequence[str] = ("bfloat16",)
                 ) -> tuple[bool, str]:
    """Full admission screen for an untrusted delta.

    Returns (ok, reason). Checks structure/shape/dtype parity with the base,
    finiteness, and an optional magnitude cap (a crude poisoning guard the
    reference lacks). dtype parity matters: a f64/i64 submission would
    silently promote the merge and double its memory. bf16 is accepted by
    default wherever the base leaf is floating (the half-bytes wire
    spelling of compute_delta(wire_dtype=...) — it cannot promote or grow
    anything).
    """
    if not shapes_match(delta, base, check_dtype=check_dtype,
                        extra_dtypes=extra_dtypes):
        return False, "shape_mismatch"
    if has_nonfinite(delta):
        return False, "nonfinite"
    # <= 0 disables, exactly like None: this is THE home of that rule so
    # callers wiring a config value through never reinvent (or forget)
    # the translation — max_abs=0 rejecting everything would zero a whole
    # subnet's scores
    if max_abs is not None and max_abs > 0:
        m = global_max_abs(delta)
        if m > max_abs:
            return False, f"magnitude_exceeded({m:.3e}>{max_abs:.3e})"
    return True, "ok"


def _cohort_screen_stats(*deltas: Params) -> tuple[jax.Array, jax.Array]:
    """Per-tree (finite flag, max |value|) for a cohort of structurally
    identical deltas — the jittable body of the batched admission screen.
    ONE program computes what the serial path dispatches as two programs
    PER MINER (``has_nonfinite`` + ``global_max_abs``), so screening cost
    stays ~flat in cohort size. Returns ([K] bool, [K] f32)."""
    fins, maxs = [], []
    for d in deltas:
        leaves = jax.tree_util.tree_leaves(d)
        flags = [jnp.any(~jnp.isfinite(l)) for l in leaves
                 if jnp.issubdtype(jnp.asarray(l).dtype, jnp.inexact)]
        fins.append(jnp.logical_not(jnp.any(jnp.stack(flags)))
                    if flags else jnp.asarray(True))
        maxs.append(jnp.max(jnp.stack(
            [jnp.max(jnp.abs(l.astype(jnp.float32))) for l in leaves]))
            if leaves else jnp.asarray(0.0, jnp.float32))
    return jnp.stack(fins), jnp.stack(maxs)


_cohort_screen_stats_jit = devprof.wrap(
    "delta.screen", jax.jit(_cohort_screen_stats),
    bucket=lambda a, kw: len(a))  # screen arity (bucket-padded chunk)

# device memory per screen dispatch is bounded at SCREEN_CHUNK x params
# (the chunked_weighted_merge discipline — an averager may gather ~100
# full deltas and must not stage them all on one chip at once); arity is
# bucket-padded (repeat, not zero-alloc) so recompiles are bounded too
SCREEN_CHUNK = 8
_SCREEN_BUCKETS = (1, 2, 4, 8)


def _screen_arity(k: int) -> int:
    for b in _SCREEN_BUCKETS:
        if k <= b:
            return b
    return SCREEN_CHUNK


# (arity, leaf shape/dtype signature) combinations already dispatched —
# a NEW one means jit traces + compiles a fresh screen program, whose
# cost is recorded in the shared ``compile.ms`` histogram (the
# compile-time accounting the recompile counters alone don't give)
_SCREEN_COMPILED: set = set()


def screen_deltas(deltas: Sequence[Params], base: Params, *,
                  max_abs: float | None = None, check_dtype: bool = True,
                  extra_dtypes: Sequence[str] = ("bfloat16",),
                  chunk: int = SCREEN_CHUNK) -> list[tuple[bool, str]]:
    """Batched ``screen_delta``: identical per-delta verdicts (same
    reasons, same check order — shape, finiteness, magnitude), with the
    finite/max-abs device work fused into one jitted program per chunk of
    ``chunk`` deltas instead of two dispatches per miner.

    Shape/dtype parity is checked host-side per delta first (pure
    metadata); survivors are grouped by leaf-dtype signature (a mixed
    f32/bf16-wire fleet must not stack into one promoted program) and
    screened ``chunk`` at a time. Short chunks are arity-padded by
    REPEATING a member (no zero-tree allocation) up to a small bucket
    ladder so a wobbling cohort size hits cached compiles.

    v2 PACKED deltas (is_packed_v2) screen in their packed form — no
    densify: admission is ``packed_matches`` (the field-wise analogue of
    the shape check), then a fused ``_packed_screen_stats`` program per
    chunk whose finite/max verdicts equal the dense screen's on the
    densified tree. Packed entries group by their full leaf
    shape/dtype signature (k varies per publisher, so shapes do too).
    """
    results: list[tuple[bool, str] | None] = [None] * len(deltas)
    by_sig: dict[tuple, list[int]] = {}
    packed_by_sig: dict[tuple, list[int]] = {}
    for i, d in enumerate(deltas):
        if is_packed_v2(d):
            if not packed_matches(d, base):
                results[i] = (False, "shape_mismatch")
                continue
            sig = ("packed",) + tuple(
                (tuple(np.shape(l)), str(np.asarray(l).dtype))
                for l in jax.tree_util.tree_leaves(d["leaves"]))
            packed_by_sig.setdefault(sig, []).append(i)
            continue
        if not shapes_match(d, base, check_dtype=check_dtype,
                            extra_dtypes=extra_dtypes):
            results[i] = (False, "shape_mismatch")
            continue
        sig = tuple(str(np.asarray(l).dtype)
                    for l in jax.tree_util.tree_leaves(d))
        by_sig.setdefault(sig, []).append(i)
    cap = max_abs is not None and max_abs > 0

    def run_chunks(idx_groups, stats_fn, tree_of):
        for idxs in idx_groups:
            for c in range(0, len(idxs), max(1, chunk)):
                part = idxs[c:c + max(1, chunk)]
                arity = _screen_arity(len(part))
                args = [tree_of(deltas[i]) for i in part]
                args += [args[0]] * (arity - len(args))
                ckey = (stats_fn is _packed_screen_stats_jit, arity, tuple(
                    (tuple(np.asarray(l).shape), str(np.asarray(l).dtype))
                    for l in jax.tree_util.tree_leaves(args[0])))
                fresh = ckey not in _SCREEN_COMPILED
                if fresh:
                    _SCREEN_COMPILED.add(ckey)
                    obs.count("screen.fresh_compiles")
                    t0 = time.perf_counter()
                stats = stats_fn(*args)
                if fresh:
                    # first-dispatch wall time: trace + compile (+ the
                    # async dispatch); the fused program's execution
                    # overlaps
                    obs.observe("compile.ms",
                                (time.perf_counter() - t0) * 1e3)
                finite, mags = jax.device_get(stats)
                for slot, i in enumerate(part):
                    if not bool(finite[slot]):
                        results[i] = (False, "nonfinite")
                    elif cap and float(mags[slot]) > max_abs:
                        results[i] = (
                            False,
                            f"magnitude_exceeded({float(mags[slot]):.3e}"
                            f">{max_abs:.3e})")
                    else:
                        results[i] = (True, "ok")

    run_chunks(by_sig.values(), _cohort_screen_stats_jit, lambda d: d)
    run_chunks(packed_by_sig.values(), _packed_screen_stats_jit,
               lambda d: d["leaves"])
    return results  # type: ignore[return-value]


def global_max_abs(tree: Params) -> float:
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return 0.0
    return float(jax.device_get(jnp.max(jnp.stack([jnp.max(jnp.abs(l)) for l in leaves]))))


def global_norm(tree: Params) -> float:
    """L2 norm over all leaves (delta-magnitude diagnostic)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return 0.0
    sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    return float(jax.device_get(jnp.sqrt(sq)))


# ---------------------------------------------------------------------------
# int8 wire quantization (an opt-in WIRE format, like the bf16 cast above
# but 4x: per-tensor symmetric scales, error feedback at the publisher)
# ---------------------------------------------------------------------------

def _is_qleaf(node) -> bool:
    return isinstance(node, dict) and set(node) == {"q", "scale"}


def quantize_delta(delta: Params) -> Params:
    """Float delta -> int8 wire tree: every leaf becomes
    ``{"q": int8, "scale": f32 scalar}`` (symmetric, scale = max|x|/127).

    A wire format only: receivers dequantize at ingest
    (``dequantize_delta``) and everything downstream — screens, apply,
    merge — runs on the float tree, so the scale being attacker-controlled
    adds nothing the magnitude/finiteness screens don't already catch.
    Per-artifact rounding error is bounded by one step (max|x|/127 per
    tensor); NOTE this protocol's artifacts REPLACE each other (each push
    re-publishes the whole cumulative delta), so error-feedback-style
    residual carrying would ADD error here, not cancel it — don't.
    All-float trees only (matching quantized_template), enforced loudly.
    Jittable."""
    def leaf(x):
        if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            raise ValueError(
                "quantize_delta: non-float leaf of dtype "
                f"{jnp.asarray(x).dtype} — the int8 wire format covers "
                "all-float delta trees only")
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        return {"q": q, "scale": scale.astype(jnp.float32)}
    return jax.tree_util.tree_map(leaf, delta)


def dequantize_delta(qtree: Params) -> Params:
    """Inverse of quantize_delta (f32 out). Jittable."""
    return jax.tree_util.tree_map(
        lambda d: d["q"].astype(jnp.float32) * d["scale"],
        qtree, is_leaf=_is_qleaf)


def quantized_template(base_template: Params) -> Params:
    """Host-side zeros tree in the int8 wire structure — the
    template-restoring load's discriminator for quantized submissions
    (engine/lora_train.py fetch_delta_any's try-chain)."""
    return jax.tree_util.tree_map(
        lambda x: {"q": np.zeros(np.shape(x), np.int8),
                   "scale": np.zeros((), np.float32)},
        base_template)


# ---------------------------------------------------------------------------
# Stacking: the averager's miner axis
# ---------------------------------------------------------------------------

def stack_deltas(deltas: Sequence[Params]) -> Params:
    """Stack M structurally identical deltas into one pytree with a leading
    miner axis on every leaf: leaf shape (s0, ...) -> (M, s0, ...).

    This is the TPU-native answer to the reference's per-batch disk reload of
    every cached delta (hivetrain/averaging_logic.py:450-470): one stacked
    pytree makes the merge a single einsum-like jitted computation and lets the
    miner axis be sharded across devices.
    """
    if not deltas:
        raise ValueError("stack_deltas: empty sequence")
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *deltas)


def miner_axis_size(stacked: Params) -> int:
    """Leading-axis length of a stacked-delta tree (may exceed the real miner
    count when the stack was zero-padded for even sharding)."""
    return jax.tree_util.tree_leaves(stacked)[0].shape[0]


def pad_merge_weights(weights: jax.Array, m_padded: int) -> jax.Array:
    """Zero-pad a (M,) mixing vector to a zero-padded stack's leading size:
    padding slots weigh nothing, so the merge is unchanged. Normalize
    (softmax etc.) over the REAL M before padding — normalizing after would
    leak probability mass onto the zero deltas and shrink the update."""
    m = weights.shape[0]
    if m == m_padded:
        return weights
    if m > m_padded:
        raise ValueError(f"{m} weights for a {m_padded}-entry stack")
    return jnp.concatenate(
        [weights, jnp.zeros((m_padded - m,), weights.dtype)])


def pad_stack(stacked: Params, k_pad: int) -> Params:
    """Zero-pad a stacked-delta tree's leading (miner/candidate) axis up to
    ``k_pad``. Padded slots are zero deltas: applied to a base they
    reproduce the base exactly, so a batched evaluator's padded candidates
    cost compute but never perturb real slots (the bucket-padding
    discipline of engine/batched_eval.py, mirroring pad_merge_weights for
    merges). Jittable for any fixed k_pad."""
    k = miner_axis_size(stacked)
    if k == k_pad:
        return stacked
    if k > k_pad:
        raise ValueError(f"cannot pad a {k}-entry stack down to {k_pad}")

    def pad_leaf(x):
        return jnp.concatenate(
            [x, jnp.zeros((k_pad - k,) + x.shape[1:], x.dtype)], axis=0)

    return jax.tree_util.tree_map(pad_leaf, stacked)


def combine_candidate_deltas(stacked: Params, weight_matrix: jax.Array
                             ) -> Params:
    """[P, M] mixing matrix x [M, ...]-stacked deltas -> [P, ...]-stacked
    CANDIDATE deltas: candidate p's delta is ``sum_i W[p, i] * delta_i``.

    This is how a population of merge-weight vectors (GeneticMerge) becomes
    one cohort for the batched evaluator: every row is the delta of one
    candidate mixture, and ``base + candidate_delta[p]`` equals
    ``weighted_merge(base, stacked, W[p])`` exactly (same contraction, f32
    accumulation against a f32 base happens at apply time). Jittable;
    materializes P x params, so single-device use only at small P."""
    def leaf(d):
        # contract in f32 and KEEP f32: rounding the combined delta back to
        # a bf16 wire stack's dtype would perturb the candidate relative to
        # weighted_merge's f32-accumulated result
        w = weight_matrix.astype(jnp.float32)
        return jnp.einsum("pm,m...->p...", w, d.astype(jnp.float32))

    return jax.tree_util.tree_map(leaf, stacked)


def unstack_deltas(stacked: Params) -> list[Params]:
    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    return [jax.tree_util.tree_map(lambda x: x[i], stacked) for i in range(n)]


def weighted_merge(base: Params, stacked_deltas: Params, weights: jax.Array) -> Params:
    """merged = base + sum_i softmax-free weights[i] * delta_i.

    ``weights`` has shape (M,). Jittable; differentiable w.r.t. ``weights``,
    which is how the parameterized averager gets its meta-gradient for free
    (replacing the manual inner-product formula at
    hivetrain/averaging_logic.py:513-528).
    """
    def merge_leaf(b, d):
        # accumulate in the BASE's dtype (f32 for f32 params): a bf16 wire
        # stack must not drag the weighted sum down to bf16. The upcast
        # fuses into the multiply — no extra materialization.
        w = weights.reshape((-1,) + (1,) * (d.ndim - 1)).astype(b.dtype)
        return b + jnp.sum(w * d.astype(b.dtype), axis=0)

    return jax.tree_util.tree_map(merge_leaf, base, stacked_deltas)


# jitted once at module level: per-call jax.jit(weighted_merge) creates a
# fresh function identity each time and retraces/recompiles every round
weighted_merge_jit = devprof.wrap(
    "delta.merge", jax.jit(weighted_merge),
    # (base, stacked, weights) -> miner-axis size, the compiled variant
    # the executable cache keys this merge on
    bucket=lambda a, kw: jax.tree_util.tree_leaves(a[1])[0].shape[0])


def weighted_merge_flat(base: Params, stacked_deltas: Params,
                        weights: jax.Array) -> Params:
    """``weighted_merge`` computed over one raveled buffer instead of
    leaf-by-leaf.

    A GPT-2-124M tree has ~150 leaves; merging per leaf dispatches ~150
    small bandwidth-bound kernels whose edge/launch overheads cap the merge
    well under HBM peak (292 GB/s on v5e is an unverified figure from an
    earlier machine). Raveling
    turns the whole merge into ONE [M] x [M, N] contraction plus an [N]
    add — a single kernel XLA tiles at near peak — and the unravel back to
    the tree is slice+reshape views fused into the same program. Same
    result, same differentiability w.r.t. ``weights``.

    Transient-memory cost: the ``jnp.concatenate`` materializes a second
    full [M, N] buffer (plus the f32 upcast of each row), roughly DOUBLING
    peak HBM during the merge versus the leafwise spelling. Fine at the
    124M scale it serves; do not promote it into the averager for
    7B/8B full-delta merges without a per-leaf-group variant.
    """
    from jax.flatten_util import ravel_pytree

    base_flat, unravel = ravel_pytree(base)
    # ravel each miner's delta row with the same leaf order as the base
    leaves = jax.tree_util.tree_leaves(stacked_deltas)
    m = leaves[0].shape[0]
    stacked_flat = jnp.concatenate(
        [l.reshape(m, -1).astype(base_flat.dtype) for l in leaves], axis=1)
    merged_flat = base_flat + jnp.einsum(
        "m,mn->n", weights.astype(base_flat.dtype), stacked_flat)
    return unravel(merged_flat)


def chunked_weighted_merge(base: Params, deltas: Sequence[Params],
                           weights: jax.Array, *, chunk: int = 8) -> Params:
    """``weighted_merge`` over a HOST-side delta list with bounded device
    memory: at most ``chunk`` deltas are stacked on-device at a time.

    Why it exists: the reference merges up to a whole subnet's submissions
    (100 uids) by re-reading each from disk per batch
    (averaging_logic.py:450-470) — unbounded M, terrible bandwidth. The
    stacked merge is the fast spelling but materializes M x params on one
    device: ~90 full GPT-2-124M deltas is ~45 GB, past any single chip's
    HBM. This path accumulates chunk partial sums instead —
    O(chunk x params) device memory, one compiled program for every chunk
    (the last one is zero-padded to the same shape), identical math.
    The mesh averager doesn't need it (the miner axis is ingest-sharded
    across devices, parallel/collectives.py).
    """
    m = len(deltas)
    if m == 0:
        raise ValueError("chunked_weighted_merge: empty delta list")
    if weights.shape[0] != m:
        raise ValueError(f"{weights.shape[0]} weights for {m} deltas")
    chunk = max(1, min(chunk, m))
    # the accumulator step IS weighted_merge (acc + sum w_i d_i), reused
    # through the module-level jitted spelling so repeated averaging
    # rounds hit the same compiled program instead of retracing
    merged = base
    zero = None
    for i in range(0, m, chunk):
        part = list(deltas[i:i + chunk])
        if len(part) < chunk:
            # pad with zero deltas so every chunk compiles to ONE program
            if zero is None:
                zero = zeros_like(part[0])
            part = part + [zero] * (chunk - len(part))
        merged = weighted_merge_jit(merged, stack_deltas(part),
                                    pad_merge_weights(weights[i:i + chunk],
                                                      chunk))
    return merged


def per_tensor_weighted_merge(base: Params, stacked_deltas: Params, weights: Params) -> Params:
    """Merge with per-miner *and* per-tensor mixing weights.

    ``weights`` is a pytree matching ``base``'s structure whose leaves have
    shape (M,) — one mixing vector per parameter tensor. This is the
    production merge of the reference (ParameterizedAverager,
    hivetrain/averaging_logic.py:422-448, where ``self.weights`` is
    (num_models, num_params)).
    """
    def merge_leaf(b, d, w):
        wv = w.reshape((-1,) + (1,) * (d.ndim - 1)).astype(b.dtype)
        return b + jnp.sum(wv * d.astype(b.dtype), axis=0)

    return jax.tree_util.tree_map(merge_leaf, base, stacked_deltas, weights)


def init_merge_weights(base: Params, num_miners: int, *, per_tensor: bool = True,
                       value: float | None = None) -> Params | jax.Array:
    """Uniform initial mixing weights (1/M each, like the reference's
    torch.ones/num_models at hivetrain/averaging_logic.py:363)."""
    v = (1.0 / num_miners) if value is None else value
    if not per_tensor:
        return jnp.full((num_miners,), v, dtype=jnp.float32)
    return jax.tree_util.tree_map(
        lambda _: jnp.full((num_miners,), v, dtype=jnp.float32), base
    )


def normalized_merge_weights(miner_ids: Sequence[str],
                             consensus: dict[str, float] | None
                             ) -> jax.Array:
    """Consensus scores -> normalized (M,) mixing vector — THE home of
    the consensus→weights rule so every merge path normalizes the same
    way: negative scores clamp to zero, an all-zero (or absent) score
    set falls back to uniform, and normalization ALWAYS runs over the
    REAL, unpadded miner count. Padding to a mesh axis or a compile
    bucket happens AFTER, via :func:`pad_merge_weights`, whose padded
    slots weigh nothing — normalizing by a padded m would shrink every
    real miner's weight by the padding ratio (a 1-miner cohort padded to
    an 8-wide mesh axis would publish 1/8th of the update)."""
    m = len(miner_ids)
    if m == 0:
        raise ValueError("normalized_merge_weights: empty cohort")
    if not consensus:
        return jnp.full((m,), 1.0 / m, jnp.float32)
    raw = np.asarray([max(float(consensus.get(h, 0.0)), 0.0)
                      for h in miner_ids], np.float32)
    total = float(raw.sum())
    if not np.isfinite(total) or total <= 0:
        return jnp.full((m,), 1.0 / m, jnp.float32)
    return jnp.asarray(raw / total)


# ---------------------------------------------------------------------------
# top-k sparse wire compression (the >=8x-beyond-int8 format for the 7B/8B
# configs: 1.42 GB f32 at 355M, ~8 GB/push/miner at 8B — sparse8 at the
# default density ships the same push in ~2% of the f32 bytes)
# ---------------------------------------------------------------------------

# Self-describing wire format "sparse8": a msgpack dict
#   {"__delta_format__": 1, "leaves": {<state-dict path>: 
#       {"idx": int32[k], "q": int8[k], "scale": f32 scalar}}}
# per-leaf top-k by |value| with the kept values int8-quantized. Unlike
# the dense int8 tree it is NOT template-discriminable (k varies with the
# publisher's density flag), so receivers detect it by the format marker
# and validate it field-by-field against the BASE template
# (sparse_delta_from_bytes) — bounds-checked indices, pinned dtypes,
# capped k. Like every wire format here: NO error feedback — pushes
# REPLACE each other (each one re-publishes the whole cumulative delta),
# so carrying a residual into the next push would add the superseded
# push's rounding error (see MinerLoop._push_delta).

SPARSE_FORMAT_KEY = "__delta_format__"
SPARSE_FORMAT_TOPK8 = 1
# leaves at or below this size ship dense (k = n): biases and layernorm
# scales are a rounding error of the artifact bytes but carry outsized
# loss impact, so sparsifying them buys nothing and costs trajectory
SPARSE_DENSE_CUTOFF = 4096


def sparse_k(n: int, density: float) -> int:
    """Per-leaf kept-coordinate count: dense below the cutoff, else
    ceil(n * density) — at LEAST the density fraction, never 0."""
    if n <= SPARSE_DENSE_CUTOFF:
        return n
    return max(1, -int(-n * density // 1))


def sparsify_delta(delta: Params, *, density: float = 1.0 / 64.0) -> Params:
    """Float delta -> sparse8 wire tree (jittable; k is static per leaf).

    Keeps the k largest-|value| coordinates per tensor, int8-quantized
    against that tensor's own max (scale = max|kept|/127). density=1/64
    is ~51x smaller than f32 / ~13x smaller than the dense int8 wire at
    124M (5 bytes per kept coordinate: int32 idx + int8 q)."""
    if not 0.0 < density <= 1.0:
        raise ValueError(f"density must be in (0, 1], got {density}")

    def leaf(x):
        if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            raise ValueError(
                "sparsify_delta: non-float leaf of dtype "
                f"{jnp.asarray(x).dtype} — sparse8 covers all-float "
                "delta trees only")
        flat = jnp.asarray(x).reshape(-1).astype(jnp.float32)
        n = flat.shape[0]
        k = sparse_k(n, density)
        if k >= n:
            idx = jnp.arange(n, dtype=jnp.int32)
            kept = flat
            top_mag = jnp.max(jnp.abs(flat), initial=0.0)
        else:
            top_mag_all, idx = jax.lax.top_k(jnp.abs(flat), k)
            idx = idx.astype(jnp.int32)
            kept = flat[idx]
            top_mag = top_mag_all[0]
        scale = jnp.maximum(top_mag, 1e-12) / 127.0
        q = jnp.clip(jnp.round(kept / scale), -127, 127).astype(jnp.int8)
        return {"idx": idx, "q": q, "scale": scale.astype(jnp.float32)}

    return {SPARSE_FORMAT_KEY: np.int32(SPARSE_FORMAT_TOPK8),
            "leaves": jax.tree_util.tree_map(leaf, delta)}


def _walk_state_dict(tree, path=()):
    """Yield (path tuple, leaf) for a nested state dict."""
    if isinstance(tree, dict):
        for key in sorted(tree):
            yield from _walk_state_dict(tree[key], path + (key,))
    else:
        yield path, tree


# kept-value dtypes a packed entry's "q" may carry: int8 (the quantized
# wire) or f32 (--wire-quant none — kept values ship unquantized, scale
# pinned to 1). Anything else is a hostile substitution (f64 parses at
# 8x the advertised bytes) and fails validation.
_PACKED_Q_DTYPES = (np.int8, np.float32)


def _validate_packed_entry(entry, n: int, *,
                           q_dtypes: tuple = (np.int8,)) -> tuple | None:
    """Field-wise validation of one top-k packed leaf entry
    ``{"idx", "q", "scale"}`` against a template leaf of ``n`` elements —
    everything an attacker controls: key set, dtypes (idx int32, q in
    ``q_dtypes``, scale f32 scalar), k <= n, finite non-negative scale,
    index bounds. Returns host ``(idx, q, scale)`` or None. Shared by the
    sparse8 densifier (int8 q only, its historical contract) and the v2
    packed wire (int8 or f32 kept values), so the formats cannot drift
    apart in what they accept."""
    if not isinstance(entry, dict) or set(entry) != {"idx", "q", "scale"}:
        return None
    idx, q, scale = (np.asarray(entry["idx"]), np.asarray(entry["q"]),
                     np.asarray(entry["scale"]))
    if (idx.dtype != np.int32 or q.dtype not in q_dtypes
            or scale.dtype != np.float32):
        return None
    if idx.ndim != 1 or q.ndim != 1 or scale.shape != ():
        return None
    if not np.isfinite(scale) or scale < 0:
        # every honest encoder emits scale >= 0 (max|kept|/127, or the
        # pinned 1.0 under quant="none"); a negative scale would flip the
        # sign of max|q|*scale in the packed magnitude screen and smuggle
        # arbitrarily large decoded values past the max_delta_abs cap
        return None
    if idx.shape[0] == 0 and q.shape[0] == n and n > 0:
        # DENSE-form entry (k == n): the index array would be arange(n),
        # pure redundancy at 4 bytes/coordinate — below-cutoff tensors
        # ship empty-idx + full q instead (1 byte/element under int8,
        # vs 5 for the indexed spelling)
        return idx, q, scale
    if q.shape != idx.shape or idx.shape[0] > n:
        return None
    if idx.shape[0] and (idx.min() < 0 or idx.max() >= n):
        return None
    return idx, q, scale


def _densify_packed_entry(idx, q, scale, shape) -> np.ndarray:
    """Validated entry -> dense f32 host array. Duplicate indices resolve
    last-wins (deterministic; screens run on the result regardless)."""
    n = int(np.prod(shape, dtype=np.int64))
    if idx.shape[0] == 0 and q.shape[0] == n and n > 0:
        # dense-form entry (empty idx, full q — see _validate_packed_entry)
        return (q.astype(np.float32) * float(scale)).reshape(shape)
    dense = np.zeros((n,), np.float32)
    dense[idx] = q.astype(np.float32) * float(scale)
    return dense.reshape(shape)


def _packed_tree_fields(leaves, template, *, q_dtypes: tuple = (np.int8,)):
    """Validate a packed-leaves tree against ``template`` leaf-by-leaf:
    path parity (each template leaf maps to exactly one
    ``{"idx","q","scale"}`` entry), then :func:`_validate_packed_entry`
    per entry. Returns ``[(path, shape, (idx, q, scale)), ...]`` in
    template walk order, or None on any mismatch — the one validator
    behind the sparse8 densifier, the v2 packed densifier, and the
    packed-form admission screen, so a payload accepted by one is
    accepted by all."""
    import flax.serialization as flax_ser

    if not isinstance(leaves, dict):
        return None
    t_flat = list(_walk_state_dict(flax_ser.to_state_dict(template)))
    s_by_parent: dict = {}
    for path, leaf in _walk_state_dict(leaves):
        if len(path) < 1:
            return None
        s_by_parent.setdefault(path[:-1], {})[path[-1]] = leaf
    if len(s_by_parent) != len(t_flat):
        return None
    out = []
    for path, t_leaf in t_flat:
        entry = s_by_parent.get(path)
        if entry is None:
            return None
        fields = _validate_packed_entry(
            entry, int(np.prod(np.shape(t_leaf), dtype=np.int64)),
            q_dtypes=q_dtypes)
        if fields is None:
            return None
        out.append((path, np.shape(t_leaf), fields))
    return out


def densify_sparse_delta(sparse: Params, template: Params) -> Params:
    """sparse8 wire tree -> dense f32 HOST delta shaped like ``template``.

    Validates everything an attacker controls: format marker, leaf-path
    parity with the template, dtypes (int32/int8/f32 pinned), k <= n,
    and index bounds. Returns None on any mismatch — same contract as
    the other wire-format decoders in the fetch try-chain. Duplicate
    indices resolve last-wins (deterministic; the magnitude/finiteness
    screens run on the densified tree regardless)."""
    import flax.serialization as flax_ser

    if not isinstance(sparse, dict):
        return None
    # The marker is attacker-controlled bytes: a string/array/NaN marker
    # must read as "not sparse8", not raise out of the decoder (a raised
    # TypeError here used to escape the fetch try-chain and abort the
    # whole scoring round — one hostile artifact silencing every miner).
    marker = sparse.get(SPARSE_FORMAT_KEY)
    try:
        marker_arr = np.asarray(marker)
        if marker_arr.shape != () or not np.issubdtype(
                marker_arr.dtype, np.integer):
            return None
        if int(marker_arr) != SPARSE_FORMAT_TOPK8:
            return None
    except (TypeError, ValueError):
        return None
    leaves = sparse.get("leaves")
    if not isinstance(leaves, dict) or set(sparse) != {
            SPARSE_FORMAT_KEY, "leaves"}:
        return None
    # sparse8 pins q to int8 exactly (its historical wire contract); the
    # v2 packed wire additionally admits f32 kept values (--wire-quant)
    fields = _packed_tree_fields(leaves, template, q_dtypes=(np.int8,))
    if fields is None:
        return None
    return _densify_fields(fields, template)


def _densify_fields(fields, template) -> Params:
    """Validated ``_packed_tree_fields`` output -> dense f32 host tree
    shaped like ``template``."""
    import flax.serialization as flax_ser

    out_state = flax_ser.to_state_dict(template)
    for path, shape, entry_fields in fields:
        node = out_state
        for key in path[:-1]:
            node = node[key]
        node[path[-1]] = _densify_packed_entry(*entry_fields, shape)
    return flax_ser.from_state_dict(template, out_state)


# ---------------------------------------------------------------------------
# Wire v2: packed per-layer top-k form (the shard-addressed publication
# channel). Same per-leaf layout as sparse8 ({"idx","q","scale"}) and the
# same top-k/quantization math, but (a) the tree stays split per WIRE
# TENSOR so engine/publish.py can ship each layer as its own
# content-addressed shard and engine/ingest.py can dedupe/fetch at shard
# granularity, (b) the encoder carries an ERROR-FEEDBACK residual, and
# (c) the cohort screen runs directly on the packed form (no densify).
#
# On error feedback vs the replace-don't-accumulate rule above: v1
# artifacts replace each other, so carrying a residual into the next v1
# push would re-add a superseded push's rounding error. The v2 regime is
# different in kind: top-k sparsification DROPS coordinates outright
# (not rounds them), and a coordinate that stays small forever would
# otherwise never ship at all — the residual accumulates exactly that
# dropped mass until it crosses the top-k threshold, so repeated lossy
# publishes converge on the true cumulative delta instead of drifting
# (the NeuronFabric Local-Adam regime: fewer, fatter, compressed
# publishes). The residual lives at the MINER and resets on base pulls
# (the cumulative delta it tracks resets there too).
# ---------------------------------------------------------------------------

WIRE_V2_KEY = "__wire_v2__"
WIRE_V2_FORMAT = 2
# --wire-quant vocabulary: int8 kept values (scale = max|kept|/127, the
# sparse8 math) or unquantized f32 kept values (scale pinned to 1)
WIRE_QUANTS = ("int8", "none")


def is_packed_entry(node) -> bool:
    """True for one packed per-tensor entry ``{"idx","q","scale"}`` (the
    is_leaf predicate for tree_map/tree_leaves over packed trees)."""
    return isinstance(node, dict) and set(node) == {"idx", "q", "scale"}


def is_packed_v2(tree) -> bool:
    """True when ``tree`` is a v2 packed delta (marker + leaves keys and
    an integer format-2 marker). Defensive like the sparse8 marker check:
    hostile marker types read as "not v2", never raise."""
    if not isinstance(tree, dict) or set(tree) != {WIRE_V2_KEY, "leaves"}:
        return False
    try:
        m = np.asarray(tree[WIRE_V2_KEY])
        return (m.shape == () and np.issubdtype(m.dtype, np.integer)
                and int(m) == WIRE_V2_FORMAT)
    except (TypeError, ValueError):
        return False


def pack_delta_v2(delta: Params, *, density: float = 1.0 / 64.0,
                  quant: str = "int8", residual: Params | None = None
                  ) -> tuple[Params, Params]:
    """Float delta -> (v2 packed tree, new error-feedback residual).

    Per leaf: top-k by |value| (``sparse_k`` — the sparse8 selection, so
    the parity pin vs ``sparsify_delta`` holds exactly), kept values
    int8-quantized against the tensor's own max (or shipped f32 under
    ``quant="none"``). ``residual`` is the previous publish's unsent
    mass, ADDED to the delta before selection; the returned residual is
    ``(delta + residual) - decode(packed)`` — what this publish still
    failed to ship. Pass ``residual=None`` for a residual of zeros (the
    first publish, and the stateless reference spelling the parity test
    pins). Jittable: k is static per leaf, both outputs are fresh
    buffers."""
    if not 0.0 < density <= 1.0:
        raise ValueError(f"density must be in (0, 1], got {density}")
    if quant not in WIRE_QUANTS:
        raise ValueError(f"quant must be one of {WIRE_QUANTS}, got {quant!r}")

    def leaf(x, r):
        if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            raise ValueError(
                "pack_delta_v2: non-float leaf of dtype "
                f"{jnp.asarray(x).dtype} — the v2 wire covers all-float "
                "delta trees only")
        shape = jnp.shape(x)
        flat = jnp.asarray(x).reshape(-1).astype(jnp.float32)
        if r is not None:
            flat = flat + jnp.asarray(r).reshape(-1).astype(jnp.float32)
        n = flat.shape[0]
        k = sparse_k(n, density)
        dense_form = k >= n
        if dense_form:
            # DENSE-form entry: empty idx, full q (the idx array would be
            # arange(n) — 4 redundant bytes per coordinate on exactly the
            # below-cutoff tensors where every coordinate ships).
            # initial=0 keeps the max defined on zero-element leaves
            idx = jnp.zeros((0,), jnp.int32)
            kept = flat
            top_mag = jnp.max(jnp.abs(flat), initial=0.0)
        else:
            top_mag_all, idx = jax.lax.top_k(jnp.abs(flat), k)
            idx = idx.astype(jnp.int32)
            kept = flat[idx]
            top_mag = top_mag_all[0]
        if quant == "int8":
            scale = jnp.maximum(top_mag, 1e-12) / 127.0
            q = jnp.clip(jnp.round(kept / scale), -127, 127).astype(jnp.int8)
            decoded = q.astype(jnp.float32) * scale
        else:
            scale = jnp.asarray(1.0, jnp.float32)
            q = kept
            decoded = kept
        if dense_form:
            res = (flat - decoded).reshape(shape)
        else:
            # top-k indices are unique: scatter-add == flat - densify
            res = flat.at[idx].add(-decoded).reshape(shape)
        return {"idx": idx, "q": q,
                "scale": scale.astype(jnp.float32)}, res

    leaves, treedef = jax.tree_util.tree_flatten(delta)
    rleaves = (jax.tree_util.tree_leaves(residual)
               if residual is not None else [None] * len(leaves))
    if len(rleaves) != len(leaves):
        raise ValueError("pack_delta_v2: residual/delta structure mismatch")
    entries, res = [], []
    for x, r in zip(leaves, rleaves):
        e, rr = leaf(x, r)
        entries.append(e)
        res.append(rr)
    packed = {WIRE_V2_KEY: jnp.asarray(WIRE_V2_FORMAT, jnp.int32),
              "leaves": jax.tree_util.tree_unflatten(treedef, entries)}
    return packed, jax.tree_util.tree_unflatten(treedef, res)


def packed_matches(packed: Params, base: Params) -> bool:
    """Admission check for an untrusted packed v2 tree: marker, per-leaf
    path parity with ``base``, pinned field dtypes, k <= n, finite
    scales, index bounds — the packed analogue of ``shapes_match``
    (validated field-by-field because k varies per publisher, so there
    is no fixed template to restore against)."""
    if not is_packed_v2(packed):
        return False
    try:
        return _packed_tree_fields(packed["leaves"], base,
                                   q_dtypes=_PACKED_Q_DTYPES) is not None
    except (TypeError, ValueError, KeyError):
        return False


def densify_packed_v2(packed: Params, template: Params) -> Params:
    """v2 packed tree -> dense f32 HOST delta shaped like ``template``,
    or None on any validation failure (same contract as
    ``densify_sparse_delta``; accepts int8 AND f32 kept values)."""
    if not is_packed_v2(packed):
        return None
    # host phase in the device observatory: full-tensor writes per
    # contribution — the measured cost the ROADMAP's fused
    # dequant-scatter-add kernel is meant to delete
    with devprof.track("delta.densify"):
        try:
            fields = _packed_tree_fields(packed["leaves"], template,
                                         q_dtypes=_PACKED_Q_DTYPES)
        except (TypeError, ValueError, KeyError):
            return None
        if fields is None:
            return None
        return _densify_fields(fields, template)


def packed_layer_entries(packed: Params) -> dict[str, dict]:
    """Host split of a packed v2 tree into its shard units: one
    ``"a/b/c" -> {"idx","q","scale"}`` (np arrays) per wire tensor, keys
    "/"-joined state-dict paths — the layer keys the shard manifest is
    addressed by (serialization.build_wire_manifest). Publisher-side on
    its OWN tree, so malformed input raises instead of returning None."""
    import flax.serialization as flax_ser

    if not is_packed_v2(packed):
        raise ValueError("packed_layer_entries: not a v2 packed tree")
    by_parent: dict = {}
    for path, leaf in _walk_state_dict(
            flax_ser.to_state_dict(packed["leaves"])):
        if any("/" in str(k) for k in path):
            raise ValueError(f"packed_layer_entries: path component with "
                             f"'/' in {path!r} would make layer keys "
                             "ambiguous")
        by_parent.setdefault(path[:-1], {})[path[-1]] = np.asarray(
            jax.device_get(leaf))
    return {"/".join(str(k) for k in p): e for p, e in by_parent.items()}


def packed_from_layer_entries(entries: dict[str, dict]) -> Params:
    """Inverse of ``packed_layer_entries``: reassemble shard entries
    (ingest side, keys from an UNTRUSTED manifest) into a v2 packed tree.
    Purely structural — colliding/hostile keys produce a tree that then
    fails ``packed_matches`` against the template, never an exception
    here."""
    nested: dict = {}
    for key, entry in entries.items():
        parts = str(key).split("/")
        node = nested
        ok = True
        for p in parts[:-1]:
            nxt = node.setdefault(p, {})
            if not isinstance(nxt, dict):
                ok = False
                break
            node = nxt
        if ok:
            node[parts[-1]] = entry
    return {WIRE_V2_KEY: np.int32(WIRE_V2_FORMAT), "leaves": nested}


# ---------------------------------------------------------------------------
# Packed-form merge: scatter-add of idx/q*scale directly into a running
# aggregate. The averager-side half of the v2 wire — a sub-averager (or a
# packed-fleet flat averager) folds M submissions into ONE accumulator
# tree, one miner at a time, so device memory stays O(params + k) and the
# dense M x params stack of stack_deltas never exists. Compile cost is
# bounded by the distinct (leaf-shape, k) signatures in the fleet: every
# miner at the same density shares one compiled accumulate program
# (sparse_k is deterministic in (n, density)).
# ---------------------------------------------------------------------------

def _accum_packed(acc_leaves, entries, w):
    """acc leaves + w * decode(entries), leafwise. The decode is the
    densifier's arithmetic — ``w * (q_f32 * scale)`` — scattered at idx
    (or added wholesale for dense-form entries), so the result matches
    ``acc + w * densify_packed_v2(...)`` to multiply-add fusion
    tolerance (XLA may emit FMA for ``a + w*x``; ~1 ulp) for honest
    (unique-index) encodings; hostile duplicate indices sum here where
    the densifier resolves last-wins (both deterministic, both screened
    upstream). Jittable: dense-form vs indexed is a static shape test."""
    out = []
    for a, e in zip(acc_leaves, entries):
        flat = a.reshape(-1)
        idx, q, scale = e["idx"], e["q"], e["scale"]
        contrib = w * (q.astype(flat.dtype) * scale)
        n = flat.shape[0]
        if idx.shape[0] == 0 and q.shape[0] == n and n > 0:
            flat = flat + contrib        # dense-form entry (k == n)
        else:
            flat = flat.at[idx].add(contrib)
        out.append(flat.reshape(a.shape))
    return out


_accum_packed_jit = devprof.wrap(
    "delta.accumulate", jax.jit(_accum_packed), bucket="packed")


def _accum_packed_kernel(acc_leaves, entries, w):
    """Kernel-backed twin of :func:`_accum_packed`: indexed-form entries
    whose shapes the kernel's layout supports
    (``ops/dequant_scatter.kernel_supports``) route through the
    dequantize->scatter-add Pallas kernel, whose accumulator is aliased
    in place. Other leaves (not whole 128-lane rows, too big for VMEM,
    too many entries) keep the XLA spelling INSIDE the same program, so
    the output is identical leaf-for-leaf either way (parity pinned in
    tests/test_dequant_scatter.py)."""
    from .ops import dequant_scatter as _dsc
    out = []
    for a, e in zip(acc_leaves, entries):
        flat = a.reshape(-1)
        idx, q, scale = e["idx"], e["q"], e["scale"]
        n = flat.shape[0]
        if idx.shape[0] == 0 and q.shape[0] == n and n > 0:
            flat = flat + w * (q.astype(flat.dtype) * scale)
        elif _dsc.kernel_supports(n, idx.shape[0]):
            flat = _dsc.dequant_scatter_add(flat, idx, q, w * scale)
        else:
            flat = flat.at[idx].add(w * (q.astype(flat.dtype) * scale))
        out.append(flat.reshape(a.shape))
    return out


# built lazily: donation (the cross-call half of the in-place story — a
# donated accumulator lets XLA alias the kernel's input_output_aliases
# chain across contributions) is a TPU matter (CPU ignores it with a
# warning), and asking for the backend at import time would force
# backend init on every importer
_ACCUM_KERNEL_PROG = None


def _accum_packed_kernel_prog():
    global _ACCUM_KERNEL_PROG
    if _ACCUM_KERNEL_PROG is None:
        donate = (0,) if jax.default_backend() == "tpu" else ()
        _ACCUM_KERNEL_PROG = devprof.wrap(
            "delta.dequant_scatter",
            jax.jit(_accum_packed_kernel, donate_argnums=donate),
            bucket="packed")
    return _ACCUM_KERNEL_PROG


def _accum_dense(acc, d, w):
    return jax.tree_util.tree_map(
        lambda a, x: a + w * x.astype(a.dtype), acc, d)


_accum_dense_jit = devprof.wrap(
    "delta.accumulate", jax.jit(_accum_dense), bucket="dense")


def accumulate_delta(acc: Params, delta: Params, weight) -> Params:
    """``acc + weight * delta`` where ``delta`` is a dense tree OR a v2
    packed tree (already admitted via ``packed_matches`` — entry order
    and element counts are trusted to line up with ``acc``). Packed
    submissions accumulate by per-tensor scatter-add of ``idx/q*scale``
    without ever densifying; dense ones by one fused add. Both run as
    ONE jitted program per call with the weight traced, so repeated
    rounds and varying weights reuse the compiled programs."""
    w = jnp.asarray(weight, jnp.float32)
    if is_packed_v2(delta):
        from .ops import dequant_scatter as _dsc
        leaves, treedef = jax.tree_util.tree_flatten(acc)
        entries = jax.tree_util.tree_leaves(delta["leaves"],
                                            is_leaf=is_packed_entry)
        if len(entries) != len(leaves):
            raise ValueError(
                f"accumulate_delta: {len(entries)} packed entries for a "
                f"{len(leaves)}-leaf accumulator (run packed_matches "
                "before accumulating)")
        prog = _accum_packed_kernel_prog() if _dsc.enabled() \
            else _accum_packed_jit
        return jax.tree_util.tree_unflatten(
            treedef, prog(leaves, entries, w))
    return _accum_dense_jit(acc, delta, w)


def aggregate_deltas(template: Params, deltas: Sequence[Params],
                     weights) -> Params:
    """``sum_i weights[i] * delta_i`` over a HOST list of mixed
    dense/packed submissions with O(params) device memory: one f32
    accumulator, one contribution folded at a time
    (:func:`accumulate_delta`) — the sub-averager's partial-aggregate
    body (engine/hier_average.py) and the packed twin of
    ``chunked_weighted_merge`` (which needs dense trees to stack).
    ``weights`` are used AS GIVEN (no normalization here — callers
    normalize over the real cohort via normalized_merge_weights)."""
    if not deltas:
        raise ValueError("aggregate_deltas: empty delta list")
    weights = np.asarray(jax.device_get(weights), np.float32).reshape(-1)
    if weights.shape[0] != len(deltas):
        raise ValueError(f"{weights.shape[0]} weights for "
                         f"{len(deltas)} deltas")
    acc = jax.tree_util.tree_map(
        lambda x: jnp.zeros(np.shape(x), jnp.float32), template)
    for d, w in zip(deltas, weights):
        acc = accumulate_delta(acc, d, w)
    return acc


def _packed_screen_stats(*packed_leaves) -> tuple[jax.Array, jax.Array]:
    """Per-tree (finite flag, max |decoded value|) for a cohort of packed
    v2 leaves-trees — the packed twin of ``_cohort_screen_stats``, fused
    the same way. No densify: int8 kept values are finite by
    construction, so finiteness is the scales' (plus f32 kept values',
    under quant="none"); the decoded max is ``max|q| * |scale|`` per
    tensor exactly — the abs covers the scale too, not just q, so a
    hostile negative scale (rejected at admission, but this program must
    not depend on that) cannot drive the verdict negative and under the
    magnitude cap. Matches the dense screen on the densified tree.
    Returns ([K] bool, [K] f32)."""
    fins, maxs = [], []
    for leaves in packed_leaves:
        entries = jax.tree_util.tree_leaves(leaves, is_leaf=is_packed_entry)
        flags, mags = [], []
        for e in entries:
            flags.append(jnp.any(~jnp.isfinite(e["scale"])))
            if jnp.issubdtype(jnp.asarray(e["q"]).dtype, jnp.inexact):
                flags.append(jnp.any(~jnp.isfinite(e["q"])))
            if e["q"].size:
                mags.append(jnp.max(jnp.abs(e["q"].astype(jnp.float32)))
                            * jnp.abs(e["scale"]))
        fins.append(jnp.logical_not(jnp.any(jnp.stack(flags)))
                    if flags else jnp.asarray(True))
        maxs.append(jnp.max(jnp.stack(mags)) if mags
                    else jnp.asarray(0.0, jnp.float32))
    return jnp.stack(fins), jnp.stack(maxs)


_packed_screen_stats_jit = devprof.wrap(
    "delta.screen_packed", jax.jit(_packed_screen_stats),
    bucket=lambda a, kw: len(a))  # screen arity (bucket-padded chunk)


def sparse_delta_from_bytes(data: bytes, template: Params,
                            *, max_bytes: int | None = None) -> Params:
    """Raw artifact bytes -> dense delta if they are a valid sparse8
    artifact, else None (the fetch try-chain's sparse attempt)."""
    from . import serialization as ser

    try:
        kw = {} if max_bytes is None else {"max_bytes": max_bytes}
        raw = ser.from_msgpack(data, None, **kw)
    except ser.PayloadError:
        return None
    # Belt-and-braces: densify validates field-by-field and returns None,
    # but hostile bytes must fail per-miner even if a validation gap lets
    # an exception through (same contract as the other decoders).
    try:
        return densify_sparse_delta(raw, template)
    except (TypeError, ValueError, KeyError, IndexError):
        return None
