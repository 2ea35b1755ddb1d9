"""Explicit-collective merge paths (shard_map over ICI).

BASELINE.json config 3 calls for the averager's weighted merge to run as an
ICI all-reduce over pod chips instead of host tensor arithmetic. The pattern:
each device holds a shard of miners' deltas along the stacked miner axis,
computes its local weighted partial sum, and one ``psum`` over the mesh axis
produces the merged model on every device — the classic
partial-sum/all-reduce recipe from the scaling book.
"""

from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import devprof, obs

Params = Any

# bucket ladder for the MINER axis of averager merges — the same
# elastic-cohort discipline as engine/batched_eval.BUCKETS: a fleet whose
# accepted-delta count wobbles between 5 and 8 hits ONE compiled merge
# program instead of four (each distinct padded M is a fresh XLA compile
# of the full-tree merge). Beyond the top bucket, multiples of it.
MERGE_BUCKETS = (1, 2, 4, 8, 16)

# (mesh, axis, m_pad) bucket sizes a sharded merge has dispatched (mesh
# None = the single-device stacked path): a NEW entry means a fresh
# compile, recorded in merge.bucket_compiles + the shared compile.ms
# histogram. prefer_compiled consults this to pad a not-yet-compiled
# bucket up to a compiled one (padding waste over a compile storm).
_MERGE_BUCKETS_SEEN: set = set()
# (mesh, axis, treedef, ndims) -> the shard_map weighted-merge callable.
# Built once per mesh/tree-structure and jitted, so every averaging
# round reuses ONE compiled program per bucket — the previous spelling
# rebuilt the shard_map closure per call, which hands XLA a fresh
# function identity and retraces the full merge every round.
_MERGE_PROGRAMS: dict = {}


def reset_merge_cache() -> None:
    """Drop the compiled-program + bucket caches (tests)."""
    _MERGE_BUCKETS_SEEN.clear()
    _MERGE_PROGRAMS.clear()


def merge_bucket(m: int, mesh: Mesh | None = None, axis: str | None = None,
                 *, prefer_compiled: bool = True) -> int:
    """Padded miner-axis size for ``m`` accepted deltas: the smallest
    MERGE_BUCKETS rung >= m (multiples of the top bucket beyond it),
    rounded up to a multiple of the mesh's merge axis so the stack
    shards evenly. With ``prefer_compiled`` (the remediation-era elastic
    discipline), a target whose program is not yet compiled pads up to
    the smallest ALREADY-COMPILED larger bucket instead of walking the
    ladder through fresh compiles."""
    if m < 1:
        raise ValueError(f"merge cohort must hold >= 1 delta, got {m}")
    for b in MERGE_BUCKETS:
        if m <= b:
            target = b
            break
    else:
        big = MERGE_BUCKETS[-1]
        target = ((m + big - 1) // big) * big
    if mesh is not None:
        axis = axis or merge_axis(mesh)
        n = mesh.shape[axis]
        target = ((target + n - 1) // n) * n
    key = (mesh, axis if mesh is not None else None)
    if prefer_compiled and (*key, target) not in _MERGE_BUCKETS_SEEN:
        bigger = sorted(t for (mk, ak, t) in _MERGE_BUCKETS_SEEN
                        if (mk, ak) == key and t >= target)
        if bigger:
            target = bigger[0]
    return target


def merge_axis(mesh: Mesh) -> str:
    """The mesh axis the averager shards the miner stack over: the largest
    axis (ties prefer dp — the conventional replica axis of an averager
    eval mesh)."""
    order = {"dp": 0, "fsdp": 1, "sp": 2, "tp": 3}
    names = sorted(mesh.shape.keys(), key=lambda n: order.get(n, 9))
    return max(names, key=lambda n: mesh.shape[n])


def stack_deltas_sharded(deltas, mesh: Mesh, axis: str = "dp",
                         target: int | None = None) -> Params:
    """Stack M deltas into a miner-axis pytree placed with that axis sharded
    over ``axis`` — the ingest path of the ICI merge (BASELINE config 3).

    Leaves are assembled host-side (numpy) and ``device_put`` directly into
    the target sharding, so no single device ever materializes the full
    M x params stack (``delta.stack_deltas`` would). M is padded with
    zero-deltas up to ``target`` (callers pass ``merge_bucket(...)`` so
    elastic fleets reuse compiled merge programs; default: the next
    multiple of the axis size); the padding contributes nothing to any
    weighted merge whose weights are zero-padded to match (strategies
    use ``delta.pad_merge_weights``).
    """
    if not deltas:
        raise ValueError("stack_deltas_sharded: empty sequence")
    import numpy as np
    axis_size = mesh.shape[axis]
    m = len(deltas)
    target = max(target or 0,
                 ((m + axis_size - 1) // axis_size) * axis_size)
    if target % axis_size:
        raise ValueError(f"stack target {target} does not divide the "
                         f"{axis_size}-wide mesh axis {axis!r}")

    def stack_leaf(*xs):
        arrs = [np.asarray(x) for x in xs]
        if target > m:
            arrs.extend(np.zeros_like(arrs[0]) for _ in range(target - m))
        stacked = np.stack(arrs, axis=0)
        spec = P(axis, *([None] * arrs[0].ndim))
        return jax.device_put(stacked, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(stack_leaf, *deltas)


def shard_stacked_deltas(stacked: Params, mesh: Mesh, axis: str = "dp") -> Params:
    """Place a [M, ...]-leaved stacked-delta tree with the miner axis sharded
    over ``axis``. M must divide the axis size evenly (pad with zero-deltas
    and zero weights otherwise)."""
    def place(x):
        spec = P(axis, *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(place, stacked)


def pad_miner_axis(stacked: Params, weights: jax.Array, multiple: int
                   ) -> tuple[Params, jax.Array]:
    """Pad the miner axis up to a multiple of the mesh axis with zero deltas
    + zero weights so sharding divides evenly; padding contributes nothing.
    ``stacked`` and ``weights`` may already disagree (an ingest-sharded stack
    is pre-padded, the weight vector is not); each is padded independently
    to the common target."""
    m_s = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    m_w = weights.shape[0]
    m = max(m_s, m_w)
    target = ((m + multiple - 1) // multiple) * multiple

    if target > m_s:
        pad = target - m_s

        def pad_leaf(x):
            return jnp.concatenate(
                [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)

        stacked = jax.tree_util.tree_map(pad_leaf, stacked)
    if target > m_w:
        weights = jnp.concatenate(
            [weights, jnp.zeros((target - m_w,), weights.dtype)])
    return stacked, weights


def psum_weighted_merge(base: Params, stacked: Params, weights: jax.Array,
                        mesh: Mesh, *, axis: str = "dp") -> Params:
    """merged = base + sum_i w_i * delta_i, with the sum over the miner axis
    executed as local partial sums + one ICI all-reduce.

    ``stacked``/``weights`` may live on host or be pre-sharded; they are
    placed with the miner axis over ``axis``. Result is replicated.
    """
    axis_size = mesh.shape[axis]
    stacked, weights = pad_miner_axis(stacked, weights, axis_size)

    in_specs = (
        P(),                                     # base replicated
        jax.tree_util.tree_map(
            lambda x: P(axis, *([None] * (x.ndim - 1))), stacked),
        P(axis),
    )

    def local_merge(b_tree, d_tree, w):
        def leaf(b, d):
            # accumulate (and psum) in the base's dtype so a bf16 wire
            # stack doesn't degrade the reduction — mirrors weighted_merge
            wv = w.reshape((-1,) + (1,) * (d.ndim - 1)).astype(b.dtype)
            partial = jnp.sum(wv * d.astype(b.dtype), axis=0)
            return b + jax.lax.psum(partial, axis)
        return jax.tree_util.tree_map(leaf, b_tree, d_tree)

    fn = _shard_map(local_merge, mesh=mesh, in_specs=in_specs, out_specs=P())
    return fn(base, stacked, weights)


def sharded_cohort_merge(base: Params, stacked: Params, weights,
                         mesh: Mesh, *, axis: str | None = None) -> Params:
    """The production spelling of :func:`psum_weighted_merge`: identical
    math (local partial sums over the sharded miner axis + one ICI
    all-reduce), but the shard_map program is built ONCE per
    (mesh, axis, tree structure), jitted, and dispatched against
    bucket-padded stacks — so a pod merges a whole cohort in one fused,
    CACHED program round after round. psum_weighted_merge rebuilt its
    closure per call (a fresh trace every averaging round), and padded
    to the raw axis multiple (a fresh compile every time the accepted
    count wobbled); this path pads to ``merge_bucket`` and records fresh
    buckets in merge.bucket_compiles + the shared compile.ms histogram.
    """
    axis = axis or merge_axis(mesh)
    m_s = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    m_w = weights.shape[0]
    m_pad = merge_bucket(max(m_s, m_w), mesh, axis)
    stacked, weights = pad_miner_axis(stacked, weights, m_pad)

    treedef = jax.tree_util.tree_structure(stacked)
    ndims = tuple(l.ndim for l in jax.tree_util.tree_leaves(stacked))
    pkey = (mesh, axis, treedef, ndims)
    program = _MERGE_PROGRAMS.get(pkey)
    if program is None:
        in_specs = (
            P(),
            jax.tree_util.tree_unflatten(
                treedef, [P(axis, *([None] * (nd - 1))) for nd in ndims]),
            P(axis),
        )

        def local_merge(b_tree, d_tree, w):
            def leaf(b, d):
                wv = w.reshape((-1,) + (1,) * (d.ndim - 1)).astype(b.dtype)
                partial = jnp.sum(wv * d.astype(b.dtype), axis=0)
                return b + jax.lax.psum(partial, axis)
            return jax.tree_util.tree_map(leaf, b_tree, d_tree)

        program = devprof.wrap(
            "merge.sharded",
            jax.jit(_shard_map(local_merge, mesh=mesh,
                               in_specs=in_specs, out_specs=P())),
            # (base, stacked, weights) -> padded miner-axis size, the
            # bucket the executable cache keys this merge variant on
            bucket=lambda a, kw: jax.tree_util.tree_leaves(
                a[1])[0].shape[0])
        _MERGE_PROGRAMS[pkey] = program

    bkey = (mesh, axis, m_pad)
    if bkey not in _MERGE_BUCKETS_SEEN:
        _MERGE_BUCKETS_SEEN.add(bkey)
        obs.count("merge.bucket_compiles")
        t0 = time.perf_counter()
        out = program(base, stacked, weights)
        # first-dispatch wall time = trace + compile (+ async dispatch),
        # the same accounting as batched_eval._timed_compile
        obs.observe("compile.ms", (time.perf_counter() - t0) * 1e3)
        return out
    return program(base, stacked, weights)


def mark_merge_bucket(m_pad: int, mesh: Mesh | None = None,
                      axis: str | None = None) -> bool:
    """Record a single-device (mesh=None) merge bucket as compiled;
    returns True when it was fresh. The stacked single-device strategies
    (ParameterizedMerge/GeneticMerge) key their own program caches on
    m_pad — this shared ledger is what lets merge_bucket's
    prefer_compiled avoid walking them through fresh compiles too."""
    key = (mesh, axis if mesh is not None else None, m_pad)
    if key in _MERGE_BUCKETS_SEEN:
        return False
    _MERGE_BUCKETS_SEEN.add(key)
    obs.count("merge.bucket_compiles")
    return True
