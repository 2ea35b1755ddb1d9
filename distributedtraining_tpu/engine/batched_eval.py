"""Batched cohort evaluation: score K candidate param-trees per eval pass.

The validator's hot loop was O(miners x eval_batches) *sequential* device
programs — one full eval pass per miner, each batch read, placed, and
dispatched once per miner (engine/validate.py score_miner). This module
amortizes the replicated work across a stacked **candidate axis**: K
screened deltas are stacked into one pytree with a leading [K] dim (the
same layout ``delta.stack_deltas`` gives the averager's miner axis), and
ONE jitted program computes ``eval(base + stacked[k], batch)`` for every
k per eval batch. Eval batches are read and placed once per cohort
instead of once per miner and the dispatch count drops K-fold; the same
evaluator serves GeneticMerge's population eval (engine/average.py),
which otherwise pays population x generations sequential passes.

Two spellings, chosen by the engine's mesh:

- single device: ``jax.vmap`` over the candidate axis — one fused XLA
  program whose peak memory is K x (params + activations) of the eval
  batch, which is why cohorts are bounded (see BUCKETS).
- mesh: an explicit ``shard_map`` with the CANDIDATE axis sharded over
  the mesh's largest axis (``parallel.collectives.merge_axis``, the same
  axis the averager ingest-shards miners over) — the K x param stack
  shards across devices instead of replicating, each device evaluates
  its local candidates on a replicated batch, and the per-candidate
  totals all-gather at the end. HLO-checked by
  tests/test_batched_eval.py (mirroring
  test_parameterized_mesh_merge_lowers_to_allreduce). The base rides
  replicated into the program: candidate-data-parallelism trades the
  base's fsdp sharding for K-way throughput, so this spelling targets
  eval meshes whose base fits per-device.

Cohorts are zero-padded to bucket sizes (1/2/4/8/16, then multiples of
16) to bound recompiles — a fleet whose miner count wobbles between 5
and 8 hits ONE compiled program, not four. Padded slots evaluate
``base + 0`` (harmless, slightly wasteful); compiled programs are cached
per bucket, mirroring ``ParameterizedMerge._step_cache``. The base model
itself can be folded into slot 0 (``include_base=True``) so a base
refresh re-eval rides the same cached program family as miner scoring.

In front of the evaluator, ``stage_cohorts`` is the fetch/eval pipeline:
a bounded background stager (data/prefetch.py's PrefetchIterator
pattern) runs transport fetch + wire_in + screen_delta of cohort n+1
while the device evaluates cohort n. Multi-host pods must NOT pipeline:
every staged fetch is a coordinator-read + broadcast collective
(fetch_delta_any_broadcast), and collectives issued from a background
thread would interleave nondeterministically with the eval program's —
callers pass ``pipeline=False`` there and only the single-host paths
overlap.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, Iterable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import delta as delta_lib
from ..utils import devprof, obs

# (base, stacked, batch) -> the stacked candidate axis: the bucket the
# jit executable cache keys this dispatch's compiled variant on
_cohort_bucket = lambda a, kw: jax.tree_util.tree_leaves(a[1])[0].shape[0]

logger = logging.getLogger(__name__)

Params = Any

# bucket ladder for cohort padding: recompiles are bounded to
# len(BUCKETS) + (cohorts beyond 16 pad to multiples of 16)
BUCKETS = (1, 2, 4, 8, 16)


def _timed_compile(fn, *args):
    """Call ``fn`` (a jitted program on fresh shapes) and record its
    first-dispatch wall time into the ``compile.ms`` registry histogram —
    the compile-COST half of the recompile counters (which only count
    occurrences). jit compiles synchronously at dispatch, so this wall
    time is trace+compile plus one async dispatch."""
    t0 = time.perf_counter()
    out = fn(*args)
    obs.observe("compile.ms", (time.perf_counter() - t0) * 1e3)
    return out


class BatchedCohortEvaluator:
    """Owns the per-bucket jitted cohort-eval programs for one engine."""

    def __init__(self, engine, *, buckets: Sequence[int] = BUCKETS,
                 prefer_compiled: bool = False):
        bs = tuple(sorted(set(int(b) for b in buckets)))
        if not bs or bs[0] < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets}")
        self.engine = engine
        self.buckets = bs
        # remediation's elastic-cohort discipline (engine/remediate.py):
        # when the ladder bucket for k is NOT yet compiled but a larger
        # one is, pad up to the compiled bucket instead of compiling the
        # exact fit — a fleet whose healthy count wobbles then reuses one
        # program (padding waste) rather than walking the ladder through
        # fresh multi-second compiles (compile storm)
        self.prefer_compiled = prefer_compiled
        # ONE jitted callable, built lazily; jax.jit's executable cache
        # keys on the padded stack's shapes, so the bucket ladder bounds
        # the compile count (the ParameterizedMerge._step_cache
        # discipline: base/stacked/batch flow as ARGUMENTS so an
        # ingest-sharded stack keeps its sharding and rounds reuse the
        # compiled programs instead of retracing the model forward)
        self._jitted: Callable | None = None
        # jitted stack+pad programs keyed (n_real, k_pad, include_base):
        # the naive per-leaf jnp.stack spelling costs one dispatch per
        # PARAM TENSOR per cohort (~3x the eval pass itself at small K,
        # measured on CPU); fusing assembly into one program per bucket
        # makes cohort staging a single dispatch
        self._stack_cache: dict[tuple, Callable] = {}
        # bucket sizes this evaluator has dispatched: a NEW k_pad means a
        # fresh XLA compile of the cohort program (jit keys on the padded
        # stack's shapes) — val.cohort_bucket_compiles counts them, so a
        # wobbling fleet size that defeats the bucket ladder shows up in
        # the registry instead of as mystery multi-second eval stalls
        self._buckets_seen: set[int] = set()

    # -- bucket policy ------------------------------------------------------
    def bucket_for(self, k: int) -> int:
        """Padded cohort size for ``k`` real candidates: the smallest
        bucket >= k (multiples of the top bucket beyond it), rounded up
        to a multiple of the mesh's merge axis so the candidate axis
        shards evenly."""
        if k < 1:
            raise ValueError(f"cohort must hold >= 1 candidate, got {k}")
        for b in self.buckets:
            if k <= b:
                target = b
                break
        else:
            big = self.buckets[-1]
            target = ((k + big - 1) // big) * big
        mesh = getattr(self.engine, "mesh", None)
        if mesh is not None:
            n = mesh.shape[self._axis(mesh)]
            target = ((target + n - 1) // n) * n
        if self.prefer_compiled and target not in self._buckets_seen:
            # compiled buckets satisfied any mesh rounding when they were
            # first dispatched, so they stay valid targets here
            bigger = sorted(b for b in self._buckets_seen if b >= target)
            if bigger:
                target = bigger[0]
        return target

    def compiled_buckets(self) -> frozenset:
        """Bucket sizes with a compiled cohort program (the elastic-cohort
        chooser in engine/remediate.py prefers these)."""
        return frozenset(self._buckets_seen)

    @staticmethod
    def _axis(mesh) -> str:
        from ..parallel.collectives import merge_axis
        return merge_axis(mesh)

    def _loss_fn(self):
        """The engine's PLAIN task loss (no fused shard_map, no ambient
        mesh/rules — see TrainEngine._plain_task_loss): nested sharding
        machinery inside the candidate-sharded program would fight it.
        Fused-loss engines therefore score through the unfused spelling
        here — identical math to fp tolerance (the fused CE is pinned to
        the dense oracle)."""
        fn = getattr(self.engine, "_plain_task_loss", None)
        if fn is None:  # engines predating the attribute / test doubles
            from .train import _default_lm_loss
            fn = _default_lm_loss
        return fn

    # -- programs -----------------------------------------------------------
    def _program(self) -> Callable:
        if self._jitted is None:
            mesh = getattr(self.engine, "mesh", None)
            self._jitted = (self._build_mesh(mesh) if mesh is not None
                            else self._build_single())
        return self._jitted

    def _candidate_eval(self):
        """(stacked_delta_slice, base, batch) -> ([k] loss sums, [k] token
        counts) — the vmapped core shared by both spellings. The delta
        upcasts into the base's dtype exactly like weighted_merge, so a
        bf16 wire cohort cannot drag candidate params to bf16."""
        model = self.engine.model
        loss = self._loss_fn()

        def one(d, base, batch):
            cand = jax.tree_util.tree_map(
                lambda b, x: b + x.astype(b.dtype), base, d)
            l, t = loss(model, cand, batch)
            return l * t, t  # token-weighted, like TrainEngine.eval_step

        return jax.vmap(one, in_axes=(0, None, None))

    def _build_single(self) -> Callable:
        vmapped = self._candidate_eval()

        def eval_k(base, stacked, batch):
            return vmapped(stacked, base, batch)

        return devprof.wrap("eval.cohort", jax.jit(eval_k),
                            bucket=_cohort_bucket)

    def _build_mesh(self, mesh) -> Callable:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        axis = self._axis(mesh)
        vmapped = self._candidate_eval()

        def local_eval(base, stacked, batch):
            # stacked arrives as each device's [k_pad / axis_size, ...]
            # shard; base and batch replicate. The all-gather at the end
            # is the program's ONLY collective — per-candidate totals are
            # scalars, so it is ~free next to the model forward.
            ls, ts = vmapped(stacked, base, batch)
            return (jax.lax.all_gather(ls, axis, tiled=True),
                    jax.lax.all_gather(ts, axis, tiled=True))

        # the replication the trailing all-gather establishes is not
        # statically inferable, so the vma check must be off
        fn = shard_map(local_eval, mesh=mesh, in_specs=(P(), P(axis), P()),
                       out_specs=(P(), P()), check_vma=False)
        return devprof.wrap("eval.cohort", jax.jit(fn),
                            bucket=_cohort_bucket)

    # -- cohort assembly ----------------------------------------------------
    def _zeros_delta_host(self) -> Params:
        """Host zeros tree in the engine's INTERNAL param layout — the
        base's slot-0 delta and the bucket padding filler."""
        return jax.tree_util.tree_map(
            lambda a: np.zeros(a.shape, a.dtype),
            self.engine.abstract_params())

    def stack_cohort(self, deltas: Sequence[Params], *,
                     include_base: bool = False) -> tuple[Params, int]:
        """Host delta trees -> one candidate-stacked device tree padded to
        the bucket size (candidate-sharded on a mesh). Returns
        (stacked, k_real); slot 0 is the zero delta (== the base) when
        ``include_base``."""
        k_real = len(deltas) + (1 if include_base else 0)
        k_pad = self.bucket_for(k_real)

        mesh = getattr(self.engine, "mesh", None)
        if mesh is not None:
            zeros = (self._zeros_delta_host()
                     if include_base or k_pad > len(deltas) else None)
            cohort = ([zeros] if include_base else []) + list(deltas)
            cohort = cohort + [zeros] * (k_pad - len(cohort))
            from jax.sharding import NamedSharding, PartitionSpec as P
            axis = self._axis(mesh)

            def stack_leaf(*xs):
                stacked = np.stack([np.asarray(jax.device_get(x))
                                    for x in xs], axis=0)
                spec = P(axis, *([None] * (stacked.ndim - 1)))
                return jax.device_put(stacked, NamedSharding(mesh, spec))

            return jax.tree_util.tree_map(stack_leaf, *cohort), k_real

        if not deltas:
            # include_base with no candidates (the base-refresh re-eval):
            # nothing real to stack, so the zeros skeleton seeds slot 0
            cohort = [self._zeros_delta_host()] * k_pad
            return jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs, axis=0), *cohort), k_real

        key = (len(deltas), k_pad, include_base)
        assemble = self._stack_cache.get(key)
        if assemble is None:
            obs.count("val.cohort_stack_compiles")
            lead = 1 if include_base else 0

            def assemble(*real):
                def leaf(*xs):
                    s = jnp.stack(xs, axis=0)
                    front = jnp.zeros((lead,) + s.shape[1:], s.dtype)
                    back = jnp.zeros((k_pad - lead - s.shape[0],)
                                     + s.shape[1:], s.dtype)
                    return jnp.concatenate([front, s, back], axis=0)

                return jax.tree_util.tree_map(leaf, *real)

            assemble = self._stack_cache[key] = devprof.wrap(
                "eval.stack", jax.jit(assemble), bucket=k_pad)
            return _timed_compile(assemble, *deltas), k_real
        return assemble(*deltas), k_real

    def _place_batch(self, batch: dict) -> dict:
        mesh = getattr(self.engine, "mesh", None)
        if mesh is None:
            return self.engine.place_batch(batch)
        # REPLICATED, not dp-sharded: the mesh's parallel axis carries
        # candidates in this program, so every device reads the full batch
        from jax.sharding import NamedSharding, PartitionSpec as P
        s = NamedSharding(mesh, P())
        spans = getattr(self.engine, "_mesh_spans_processes", None)
        if spans is not None and spans():
            return {k: jax.make_array_from_process_local_data(
                        s, np.asarray(v)) for k, v in batch.items()}
        return {k: jax.device_put(np.asarray(v), s)
                for k, v in batch.items()}

    # -- evaluation ---------------------------------------------------------
    def evaluate_stacked(self, base: Params, stacked: Params, k_real: int,
                         batches: Iterable[dict]
                         ) -> list[tuple[float, float]]:
        """Per-candidate (mean loss, perplexity) for the first ``k_real``
        slots of an already-stacked candidate-delta tree (padded here to
        the bucket if needed). Accumulation stays on device — ONE host
        sync per cohort, not per candidate or per batch (the same
        discipline as TrainEngine.evaluate)."""
        k_stack = delta_lib.miner_axis_size(stacked)
        k_pad = self.bucket_for(max(k_stack, k_real))
        fresh_bucket = k_pad not in self._buckets_seen
        if fresh_bucket:
            self._buckets_seen.add(k_pad)
            obs.count("val.cohort_bucket_compiles")
        if k_stack != k_pad:
            pad = self._stack_cache.get(("pad", k_pad))
            if pad is None:  # one program, not one concat dispatch per leaf
                pad = self._stack_cache[("pad", k_pad)] = devprof.wrap(
                    "eval.pad",
                    jax.jit(lambda s: delta_lib.pad_stack(s, k_pad)),
                    bucket=k_pad)
                stacked = _timed_compile(pad, stacked)
            else:
                stacked = pad(stacked)
        prog = self._program()
        total = count = None
        for batch in batches:
            placed = self._place_batch(batch)
            if fresh_bucket:
                # the counter above says a compile HAPPENED; this says
                # what it COST — first-dispatch wall time (trace+compile;
                # the jitted call returns before execution finishes, so
                # device time stays out). compile.ms across all sites is
                # what makes a compile storm visible in the fleet report.
                l, t = _timed_compile(prog, base, stacked, placed)
                fresh_bucket = False
            else:
                l, t = prog(base, stacked, placed)
            total = l if total is None else total + l
            count = t if count is None else count + t
        if count is None:
            return [(float("nan"), float("nan"))] * k_real
        total = np.asarray(jax.device_get(total), np.float64)
        count = np.asarray(jax.device_get(count), np.float64)
        out = []
        for i in range(k_real):
            if count[i] == 0:
                out.append((float("nan"), float("nan")))
            else:
                mean = total[i] / count[i]
                out.append((float(mean), float(np.exp(mean))))
        return out

    def evaluate_cohort(self, base: Params, deltas: Sequence[Params],
                        batches: Iterable[dict], *,
                        include_base: bool = False
                        ) -> list[tuple[float, float]]:
        """Score a cohort of host delta trees against ``base`` in one
        program per eval batch. With ``include_base`` the first returned
        entry is the BASE's (loss, ppl) — a zero delta in slot 0, so a
        base-refresh re-eval rides the same bucket-cached program as
        miner scoring instead of a separate engine.evaluate pass."""
        if not deltas and not include_base:
            return []
        stacked, k_real = self.stack_cohort(deltas,
                                            include_base=include_base)
        return self.evaluate_stacked(base, stacked, k_real, batches)


# ---------------------------------------------------------------------------
# Fetch/eval pipelining
# ---------------------------------------------------------------------------

def stage_cohorts(items: Sequence, cohort_size: int, stage_one: Callable,
                  *, pipeline: bool = True, depth: int = 1,
                  stage_many: Callable | None = None) -> Iterator[list]:
    """Group ``items`` into cohorts of ``cohort_size`` and map
    ``stage_one`` over each — on a bounded background thread ``depth``
    cohorts ahead when ``pipeline``, so staging cohort n+1 (transport
    fetch + wire_in + screen) overlaps the caller's device eval of
    cohort n.

    ``stage_many`` (optional) stages a WHOLE cohort in one call instead
    of item-by-item — how the validator routes a cohort through the
    concurrent ingest pool (engine/ingest.py: fetches in flight at once,
    one fused screen program) rather than serial per-miner staging.

    ``pipeline=False`` stages inline in caller order — REQUIRED on
    multi-host pods, where staging contains broadcast collectives that
    must interleave deterministically with the eval program's. The
    returned iterator exposes ``close()`` when pipelined (stop the
    worker early on a failed round).
    """
    if cohort_size < 1:
        raise ValueError(f"cohort_size must be >= 1, got {cohort_size}")
    groups = [list(items[i:i + cohort_size])
              for i in range(0, len(items), cohort_size)]

    def stage_group(group):
        # stager-side occupancy half: time actually spent fetching +
        # screening (the consumer's wait half is val.stage_wait_ms in
        # engine/validate.py) — busy/(busy+wait) is pipeline overlap
        t0 = time.perf_counter()
        if stage_many is not None:
            out = stage_many(group)
        else:
            out = [stage_one(x) for x in group]
        obs.count("val.stage_busy_ms", (time.perf_counter() - t0) * 1e3)
        return out

    if not pipeline:
        return iter(stage_group(group) for group in groups)
    from ..data.prefetch import map_prefetch
    return map_prefetch(stage_group, groups, depth=depth)
