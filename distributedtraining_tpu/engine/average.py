"""Averager engine: merge miner deltas into the next base model.

Rebuild of hivetrain/averaging_logic.py. Strategy inventory and parity:

- WeightedAverage        <- Averager.average_gradients (:129-147), weights
                            from validator consensus scores
- ParameterizedMerge     <- ParameterizedAverager (:335-583), the production
                            merge: per-miner (x per-tensor) mixing weights
                            meta-learned against a validation set
- GeneticMerge           <- GeneticAverager (:830-970): population 10,
                            10 generations, sigma=0.1 Gaussian mutation

The TPU redesign of the hot path: the reference re-reads every cached delta
from disk on every meta-batch (lazy_load_params, :450-470) and computes the
meta-gradient by a manual per-parameter inner-product formula (:513-528).
Here all deltas are stacked once into a miner-axis pytree (delta.stack_deltas)
and the merge+eval is one jitted computation whose weight-gradient comes from
``jax.grad`` — the entire meta-learning epoch never leaves the device. On a
mesh, the merge runs as local partial sums + ICI all-reduce
(parallel/collectives.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import os
from typing import Any, Callable, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .. import delta as delta_lib
from .. import serialization as ser
from ..ops.losses import causal_lm_loss
from ..utils import obs
from .scheduler import Clock, PeriodicAction, RealClock

logger = logging.getLogger(__name__)

Params = Any


# ---------------------------------------------------------------------------
# Strategies
# ---------------------------------------------------------------------------

class WeightedAverage:
    """Fixed-weight merge; weights default to validator consensus scores
    (the reference weighs each miner's delta by its normalized validator
    score, averaging_logic.py:129-147).

    Single-chip ingestion is a HOST delta list merged ``chunk_size``
    deltas at a time (delta.chunked_weighted_merge): device memory stays
    O(chunk x params) however many miners submit — the reference's
    whole-subnet case (up to 100 uids) would otherwise need an M x params
    stack past one chip's HBM. A mesh averager keeps the sharded-stack
    path instead (parallel/collectives.sharded_cohort_merge: one cached,
    bucket-padded fused program per cohort).

    A PACKED host list (wire-v2 submissions staged with densify=False,
    or a mix of packed and dense trees) merges through the scatter-add
    accumulate path (delta.aggregate_deltas) — per-miner idx/q*scale
    folds into one accumulator, never an M x params stack."""

    # tells AveragerLoop to hand over the raw host list on single-chip
    # runs instead of materializing a full device stack
    host_list_ingest = True

    def lineage_weights(self, weights):
        """The merge is linear in these exact normalized weights, so the
        lineage record is replayable (engine/lineage.py): ``new_base =
        base + sum_i w_i d_i`` re-derives bit-for-bit from the record."""
        return weights

    def __init__(self, *, uniform: bool = False, chunk_size: int = 8):
        self.uniform = uniform
        self.chunk_size = chunk_size
        # the consensus→weights normalization is pure host work, but it
        # re-ran every round even when (cohort, scores) had not changed;
        # memoized on exactly that key (satellite of ROADMAP item 2)
        self._weights_cache: tuple | None = None

    def _weights(self, miner_ids: list[str],
                 consensus: dict[str, float] | None) -> jax.Array:
        if self.uniform or not consensus:
            key = (tuple(miner_ids), None)
        else:
            key = (tuple(miner_ids),
                   tuple(float(consensus.get(h, 0.0)) for h in miner_ids))
        if self._weights_cache is not None and self._weights_cache[0] == key:
            obs.count("merge.weights_reused")
            return self._weights_cache[1]
        w = delta_lib.normalized_merge_weights(
            miner_ids, None if self.uniform else consensus)
        self._weights_cache = (key, w)
        return w

    def merge(self, engine, base: Params, stacked: Params, miner_ids: list[str],
              *, val_batches=None, consensus: dict[str, float] | None = None
              ) -> tuple[Params, jax.Array]:
        w = self._weights(miner_ids, consensus)
        if getattr(engine, "mesh", None) is not None:
            # BASELINE config 3: local partial sums over the sharded miner
            # axis + one ICI all-reduce, via the per-bucket CACHED fused
            # program (parallel/collectives.py)
            from ..parallel.collectives import (merge_axis,
                                                sharded_cohort_merge)
            merged = sharded_cohort_merge(base, stacked, w, engine.mesh,
                                          axis=merge_axis(engine.mesh))
        elif isinstance(stacked, list):
            if any(delta_lib.is_packed_v2(d) for d in stacked):
                # wire-v2 packed submissions: scatter-add accumulate —
                # the M x params stack (and the per-miner densify) never
                # happens. The f32 aggregate folds into the base in the
                # BASE's dtype, mirroring weighted_merge's rule.
                agg = delta_lib.aggregate_deltas(base, stacked, w)
                merged = jax.tree_util.tree_map(
                    lambda b, a: b + a.astype(b.dtype), base, agg)
            else:
                merged = delta_lib.chunked_weighted_merge(
                    base, stacked, w, chunk=self.chunk_size)
        else:
            # the stack may be bucket-padded (AveragerLoop's compile
            # ladder); weights normalize over the REAL m above and
            # zero-pad here — the padded slots weigh nothing
            merged = delta_lib.weighted_merge_jit(
                base, stacked,
                delta_lib.pad_merge_weights(
                    w, delta_lib.miner_axis_size(stacked)))
        return merged, w


class OuterOptMerge:
    """Outer-optimizer wrapper around any merge strategy (DiLoCo-family
    local-SGD: an outer Nesterov-momentum step over the merged delta).

    The reference's averagers publish ``base + merged_delta`` directly; the
    local-SGD literature (DiLoCo et al.) shows an outer optimizer over the
    round-to-round delta — velocity accumulation + Nesterov lookahead —
    converges markedly faster under infrequent synchronization, which is
    exactly this protocol's regime (rounds are ~20 min apart). Velocity
    state lives here, across rounds, as a device pytree.

        delta_t   = inner_merge(base, deltas) - base
        v_t       = momentum * v_{t-1} + delta_t
        new_base  = base + outer_lr * (momentum * v_t + delta_t)   [nesterov]
                  = base + outer_lr * v_t                          [plain]
    """

    @property
    def host_list_ingest(self) -> bool:
        """Forward the inner strategy's ingestion preference (the outer
        step itself never touches the stack)."""
        return getattr(self.inner, "host_list_ingest", False)

    def lineage_weights(self, weights):
        """None: the outer velocity step makes the published base a
        NON-linear function of this round's deltas (momentum carries
        prior rounds), so the lineage record is attribution-only."""
        return None

    def __init__(self, inner, *, outer_lr: float = 0.7,
                 momentum: float = 0.9, nesterov: bool = True,
                 state_path: str | None = None):
        """``state_path``: optional msgpack file persisting the velocity
        across restarts — without it a supervised averager restart silently
        drops the momentum the merge quality depends on (several rounds of
        re-warmup at this protocol's ~20 min cadence)."""
        self.inner = inner
        self.outer_lr = outer_lr
        self.momentum = momentum
        self.nesterov = nesterov
        self.state_path = state_path
        self.velocity: Params | None = None
        self._pending_velocity: Params | None = None

        def outer_step(base, merged, velocity):
            d = delta_lib.tree_sub(merged, base)
            v = jax.tree_util.tree_map(
                lambda vp, dp: self.momentum * vp + dp, velocity, d)
            upd = jax.tree_util.tree_map(
                lambda vp, dp: self.momentum * vp + dp, v, d) \
                if self.nesterov else v
            new = jax.tree_util.tree_map(
                lambda b, u: b + self.outer_lr * u, base, upd)
            return new, v

        self._outer_step = jax.jit(outer_step)

    def merge(self, engine, base: Params, stacked: Params, miner_ids: list[str],
              *, val_batches=None, consensus: dict[str, float] | None = None
              ) -> tuple[Params, jax.Array]:
        merged, w = self.inner.merge(engine, base, stacked, miner_ids,
                                     val_batches=val_batches,
                                     consensus=consensus)
        if self.velocity is None:
            self.velocity = self._restore_velocity(base)
        # velocity is committed only when the round publishes: a failed
        # round retries against the UNCHANGED base, and double-accumulating
        # momentum for a base that never moved would overshoot the next
        # published update
        new_base, self._pending_velocity = self._outer_step(
            base, merged, self.velocity)
        return new_base, w

    def _restore_velocity(self, base: Params) -> Params:
        if self.state_path is not None and os.path.exists(self.state_path):
            try:
                host = jax.tree_util.tree_map(
                    lambda x: np.zeros(x.shape, x.dtype),
                    jax.eval_shape(lambda: base))
                v = ser.load_file(self.state_path, host)
                logger.info("outer-opt velocity restored from %s",
                            self.state_path)
                # inherit the base's shardings (a mesh averager's base is
                # sharded; an unsharded restore would park the full tree on
                # one device exactly where sharding exists to avoid that)
                return jax.tree_util.tree_map(
                    lambda b, x: jax.device_put(x, b.sharding)
                    if hasattr(b, "sharding") else jnp.asarray(x), base, v)
            except Exception:
                logger.exception("outer-opt velocity restore failed; "
                                 "starting from zero momentum")
        return delta_lib.zeros_like(base)

    def commit(self) -> None:
        """Called by the loop after the merged base is published."""
        if self._pending_velocity is not None:
            self.velocity = self._pending_velocity
            if self.state_path is not None:
                try:
                    # cross-process-sharded leaves can't be fetched on one
                    # host; pod averagers skip persistence (restart re-warms)
                    if all(getattr(l, "is_fully_addressable", True)
                           for l in jax.tree_util.tree_leaves(self.velocity)):
                        ser.save_file(self.velocity, self.state_path)
                except Exception:
                    logger.exception("outer-opt velocity save failed")
            self._pending_velocity = None


class ParameterizedMerge:
    """Meta-learned mixing weights (the production merge,
    neurons/averager.py:102 -> averaging_logic.py:335-583).

    loss(w) = eval-set loss of (base + sum_i w_i * delta_i); w is optimized by
    ``meta_epochs`` passes of SGD at ``meta_lr`` (ref defaults 7 and 0.01,
    neurons/averager.py:106). ``per_tensor=True`` learns one weight per miner
    per parameter tensor (the reference's (num_models, num_params) weight
    matrix); False learns one scalar per miner.
    """

    def __init__(self, model, *, meta_epochs: int = 7, meta_lr: float = 0.01,
                 per_tensor: bool = True, softmax_weights: bool = True,
                 meta_optimizer: str = "adam"):
        self.model = model
        self.meta_epochs = meta_epochs
        self.meta_lr = meta_lr
        self.per_tensor = per_tensor
        # the reference keeps raw weights; softmax parameterization keeps the
        # mixture normalized and is the default here (documented deviation)
        self.softmax_weights = softmax_weights
        # "adam" (default) vs "sgd" (the reference's manual-gradient
        # spelling, averaging_logic.py:513-528). The mixture-loss surface
        # is nearly flat in the softmax logits, so SGD at the reference's
        # lr 0.01 moves them ~1e-3/epoch and the learned weights stay
        # within ~1% of uniform no matter how unequal the miners are
        # (round-4 verdict weak #3). Adam's per-coordinate normalization
        # marches logits at ~meta_lr per step regardless of that
        # flatness, so a mediocre delta's weight lands measurably below a
        # good one's within the same 7-epoch budget.
        if meta_optimizer not in ("adam", "sgd"):
            raise ValueError(f"meta_optimizer must be 'adam' or 'sgd', "
                             f"got {meta_optimizer!r}")
        self.meta_optimizer = meta_optimizer
        # (mixture, meta_step, tx) per m_pad: the jitted functions take
        # base/stacked as ARGUMENTS, so they are reusable round after
        # round — rebuilding them per merge() would hand jax a fresh
        # function identity and retrace+recompile the full model fwd+bwd
        # every averaging round
        self._step_cache: dict[int, tuple] = {}

    def lineage_weights(self, weights):
        """Scalar-per-miner mode mixes linearly in softmax(w) (or w
        itself when softmax is off), so the record is replayable;
        per-tensor mode learns one weight per PARAMETER TENSOR — not a
        scalar mix — and resolves to attribution-only."""
        if self.per_tensor:
            return None
        if self.softmax_weights:
            return jax.nn.softmax(jnp.asarray(weights))
        return weights

    def _build_step(self, m_pad: int):
        """``base``/``stacked`` flow through every jitted function as
        ARGUMENTS, never closures: a closed-over concrete array is embedded
        into the program as a constant, and an ingest-sharded stack loses
        its sharding that way — the merge then silently replicates the full
        M x params stack per device instead of compiling to local partial
        sums + an ICI all-reduce (checked at the HLO level by
        tests/test_parallel.py::test_parameterized_mesh_merge_lowers_to_allreduce).
        Cached per m_pad so repeated rounds reuse the compiled programs."""
        cached = self._step_cache.get(m_pad)
        if cached is not None:
            return cached
        model = self.model

        # the stack may be zero-padded for even mesh sharding; weights are
        # normalized over the REAL miner count, then zero-padded to match
        # (padding a softmax input instead would leak mass onto zero deltas)
        def mixture(w, base, stacked):
            if self.softmax_weights:
                norm = (jax.tree_util.tree_map(
                            lambda x: jax.nn.softmax(x), w)
                        if self.per_tensor else jax.nn.softmax(w))
            else:
                norm = w
            if self.per_tensor:
                norm = jax.tree_util.tree_map(
                    lambda x: delta_lib.pad_merge_weights(x, m_pad), norm)
                return delta_lib.per_tensor_weighted_merge(base, stacked, norm)
            return delta_lib.weighted_merge(
                base, stacked, delta_lib.pad_merge_weights(norm, m_pad))

        def loss_fn(w, base, stacked, batch):
            params = mixture(w, base, stacked)
            logits = model.apply(
                {"params": params}, batch["input_ids"],
                attention_mask=batch.get("attention_mask"),
                segment_ids=batch.get("segment_ids"),
                position_ids=batch.get("position_ids"))
            loss, _ = causal_lm_loss(logits, batch["input_ids"],
                                     batch.get("loss_mask"))
            return loss

        tx = (optax.adam(self.meta_lr) if self.meta_optimizer == "adam"
              else optax.sgd(self.meta_lr))

        @jax.jit
        def meta_step(w, opt_state, base, stacked, batch):
            loss, g = jax.value_and_grad(loss_fn)(w, base, stacked, batch)
            updates, opt_state = tx.update(g, opt_state)
            w = optax.apply_updates(w, updates)
            return w, opt_state, loss

        self._step_cache[m_pad] = (jax.jit(mixture), meta_step, tx)
        return self._step_cache[m_pad]

    def merge(self, engine, base: Params, stacked: Params, miner_ids: list[str],
              *, val_batches: Callable[[], Iterable[dict]],
              consensus=None) -> tuple[Params, Any]:
        m = len(miner_ids)
        if self.softmax_weights:
            init = jnp.zeros((m,), jnp.float32)  # softmax(0) = uniform
            w = (jax.tree_util.tree_map(lambda _: init, base)
                 if self.per_tensor else init)
        else:
            w = delta_lib.init_merge_weights(base, m, per_tensor=self.per_tensor)
        mixture, meta_step, tx = self._build_step(
            delta_lib.miner_axis_size(stacked))
        opt_state = tx.init(w)
        last = None
        # traced under the engine's mesh, like the engine's own loss: the
        # in-model Pallas attention reads the ambient mesh to shard_map
        # itself (GSPMD refuses to partition a Mosaic call)
        mesh = getattr(engine, "mesh", None)
        mesh_ctx = mesh if mesh is not None else contextlib.nullcontext()
        with mesh_ctx:
            for epoch in range(self.meta_epochs):
                for batch in val_batches():
                    batch = engine.place_batch(batch)
                    # `last` stays a device array inside the batch loop so
                    # the host never blocks on an individual meta-step; one
                    # float() per epoch (the log line) is the only sync
                    # point.
                    w, opt_state, last = meta_step(w, opt_state, base,
                                                   stacked, batch)
                logger.info("meta-learning epoch %d/%d loss=%.4f",
                            epoch + 1, self.meta_epochs,
                            float("nan") if last is None else float(last))
        merged = mixture(w, base, stacked)   # pre-jitted (_build_step cache)
        return merged, w


class GeneticMerge:
    """Evolutionary weight search (GeneticAverager, averaging_logic.py:830-970):
    population of mixing-weight vectors, Gaussian mutation, elite selection by
    eval loss. Slower than gradient meta-learning but derivative-free.

    Cost shape: the reference evaluates every candidate on the FULL val
    set every generation — up to population x generations eval passes per
    round (~100 at the defaults). Here selection runs as successive
    halving: candidates are RANKED on the first ``screen_batches`` val
    batches (rank is all selection needs — crossing losses between
    near-identical mixtures rarely reorders past the elite boundary with
    a shared batch subset), and only the winning elites pay a full-set
    eval. Per-generation cost drops from P full passes to P short passes
    + elite full passes; ``screen_batches=None`` restores the reference's
    exact full-set behavior."""

    def __init__(self, *, population: int = 10, generations: int = 10,
                 sigma: float = 0.1, elite: int = 2, seed: int = 0,
                 screen_batches: int | None = 2, batched: bool = True):
        self.population = population
        self.generations = generations
        self.sigma = sigma
        self.elite = elite
        self.seed = seed
        if screen_batches is not None and screen_batches < 1:
            # 0 would islice an empty iterator -> NaN losses -> arbitrary
            # selection with no error; fail eagerly like delta_density
            raise ValueError("screen_batches must be >= 1 or None "
                             f"(full-set fitness), got {screen_batches}")
        self.screen_batches = screen_batches
        # ``batched``: score each tier's UNCACHED candidates through the
        # batched cohort evaluator (engine/batched_eval.py) — the whole
        # population rides one stacked program per val batch instead of
        # population sequential eval passes per generation. Single-device
        # stacks only: the [P, M] x [M, params] candidate expansion
        # materializes P x params, which the chunked/mesh ingest paths
        # exist to avoid (they keep the sequential tiers).
        self.batched = batched
        self._pop_evaluator: tuple | None = None  # (engine, evaluator)

    def lineage_weights(self, weights):
        """The winning vector IS the linear mix applied by merge_fn
        (``base + sum_i w_i d_i``), so the record is replayable."""
        return weights

    def merge(self, engine, base: Params, stacked: Params, miner_ids: list[str],
              *, val_batches: Callable[[], Iterable[dict]],
              consensus=None) -> tuple[Params, jax.Array]:
        import itertools

        m = len(miner_ids)
        m_pad = delta_lib.miner_axis_size(stacked)
        rng = jax.random.PRNGKey(self.seed)

        def merge_fn(base, stacked, w):
            # w is normalized over the real M; zero-pad to a padded stack.
            # The module-level jitted merge is reused so repeated rounds
            # (and the many per-generation fitness evals) never retrace
            return delta_lib.weighted_merge_jit(
                base, stacked, delta_lib.pad_merge_weights(w, m_pad))

        # elites recur across generations: memoize both tiers by
        # weight-vector bytes
        cache: dict[tuple[bytes, bool], float] = {}

        evaluator = None
        if (self.batched and not isinstance(stacked, list)
                and getattr(engine, "mesh", None) is None):
            from .batched_eval import BatchedCohortEvaluator
            if (self._pop_evaluator is None
                    or self._pop_evaluator[0] is not engine):
                self._pop_evaluator = (engine,
                                       BatchedCohortEvaluator(engine))
            evaluator = self._pop_evaluator[1]

        def _eval(w, *, full: bool) -> float:
            key = (np.asarray(w).tobytes(), full)
            if key not in cache:
                batches = val_batches()
                if not full and self.screen_batches is not None:
                    batches = itertools.islice(batches, self.screen_batches)
                loss, _ = engine.evaluate(merge_fn(base, stacked, w),
                                          batches)
                cache[key] = loss
            return cache[key]

        def _eval_many(ws, *, full: bool) -> None:
            """Fill the cache for every uncached vector in ``ws`` — as ONE
            candidate cohort per val batch when the batched evaluator is
            available (each candidate's delta is its weighted mixture of
            the miner stack, delta.combine_candidate_deltas), else by the
            per-candidate sequential spelling."""
            uniq, seen = [], set()
            for w in ws:
                k = np.asarray(w).tobytes()
                if (k, full) not in cache and k not in seen:
                    seen.add(k)
                    uniq.append(w)
            if not uniq:
                return
            if evaluator is None or len(uniq) == 1:
                for w in uniq:
                    _eval(w, full=full)
                return
            W = jnp.stack([delta_lib.pad_merge_weights(jnp.asarray(w), m_pad)
                           for w in uniq])
            cands = delta_lib.combine_candidate_deltas(stacked, W)
            batches = val_batches()
            if not full and self.screen_batches is not None:
                batches = itertools.islice(batches, self.screen_batches)
            scored = evaluator.evaluate_stacked(base, cands, len(uniq),
                                                batches)
            for w, (loss, _) in zip(uniq, scored):
                cache[(np.asarray(w).tobytes(), full)] = loss

        def screen(w) -> float:   # cheap ranking tier
            return _eval(w, full=self.screen_batches is None)

        def fitness(w) -> float:  # full-set tier (elites, final winner)
            return _eval(w, full=True)

        pop = [jnp.full((m,), 1.0 / m)]
        for i in range(self.population - 1):
            rng, k = jax.random.split(rng)
            pop.append(jax.nn.softmax(jax.random.normal(k, (m,))))
        elites: list = []  # --genetic-generations 0 = pick best of the
        for gen in range(self.generations):  # initial population below
            _eval_many(pop, full=self.screen_batches is None)
            scored = sorted(pop, key=screen)
            _eval_many(scored[: self.elite * 2], full=True)
            elites = sorted(scored[: self.elite * 2],
                            key=fitness)[: self.elite]
            children = list(elites)
            while len(children) < self.population:
                rng, k1, k2 = jax.random.split(rng, 3)
                parent = elites[int(jax.random.randint(k1, (), 0, self.elite))]
                child = parent + self.sigma * jax.random.normal(k2, (m,))
                children.append(jax.nn.softmax(child))
            pop = children
            logger.info("genetic gen %d best loss=%.4f", gen + 1,
                        fitness(elites[0]))
        # final selection: the screen-ranked survivors PLUS the last
        # generation's elites — their full-set losses are already cached,
        # so including them costs nothing and guarantees a noisy final
        # screening batch can never discard the known full-eval best
        _eval_many(pop, full=self.screen_batches is None)
        finalists = sorted(pop, key=screen)[: max(self.elite, 2)] + elites
        _eval_many(finalists, full=True)
        best = min(finalists, key=fitness)
        return merge_fn(base, stacked, best), best


# ---------------------------------------------------------------------------
# The loop
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AveragerReport:
    rounds: int = 0
    last_accepted: int = 0
    last_rejected: int = 0
    last_loss: float = float("nan")
    skipped_publishes: int = 0


class AveragerLoop:
    """run_periodic_averaging parity (averaging_logic.py:544-583): pull base,
    gather+screen every miner delta, merge via strategy, publish new base.

    With ``hierarchy`` set (a list of sub-averager node ids), this loop
    is the ROOT of a tree aggregation (engine/hier_average.py): it stages
    the reserved ``__agg__.<node>`` partial-aggregate artifacts instead
    of chain hotkeys, and its consensus weights are the per-subtree
    weight sums the sub-averagers declared on their meta riders — so
    each strategy's mixing weights become per-subtree, and a missing or
    stale aggregate simply drops that subtree from the round (the root
    degrades to the surviving subtrees)."""

    def __init__(self, engine, transport, chain, strategy, *,
                 val_batches: Callable[[], Iterable[dict]],
                 address_store=None,
                 clock: Clock | None = None,
                 max_delta_abs: float | None = 1e3,
                 metrics=None,
                 lora_cfg=None,
                 accept_quant: bool = True,
                 accept_wire_v2: bool = True,
                 stale_deltas: str = "skip",
                 publish_policy: str = "improved",
                 ingest_workers: int = 4,
                 ingest_cache_mb: int = 2048,
                 fleet=None,
                 remediation=None,
                 lease=None,
                 hierarchy: Sequence[str] | None = None,
                 lineage=None,
                 base_dist=None):
        self.engine = engine
        # fleet health plane (engine/health.py FleetMonitor): polled at
        # the round cadence, fed the EXACT staging outcomes each gather
        # acted on (the contribution ledger matches the merge decisions
        # by construction), SLO-evaluated and ledger-flushed per round
        self.fleet = fleet
        # remediation layer (engine/remediate.py RemediationEngine): its
        # quarantine set is the staging exclude hook, and each round's
        # SLO breaches drive its state machine at _fleet_round_end
        self.remediation = remediation
        # publication lease (engine/remediate.py LeaseManager): when set,
        # ownership is re-confirmed immediately before every base publish
        # and the publish stamps the held epoch — the failover arbitration
        # that keeps base publication single-writer across a standby
        # takeover. None = no failover configured (single-averager fleet).
        self.lease = lease
        # tree aggregation (engine/hier_average.py): the configured sub
        # node ids this root gathers aggregates from; None = flat mode
        self.hierarchy = list(hierarchy) if hierarchy else None
        # provenance plane (engine/lineage.py LineagePlane): every base
        # publish freezes a content-addressed lineage record — parent
        # revision, the exact (hotkey, cid, weight, bytes, verdict,
        # score) set that entered the merge — and feeds the merged
        # held-out loss to the quality-drift detector. None = no
        # provenance (the reference posture).
        self.lineage = lineage
        # base distribution plane (engine/basedist.BasePublisher): each
        # monolithic publish_base is followed by the hash-addressed
        # shard set + per-revision manifest, so sharded fetchers
        # delta-pull only changed layers while legacy fetchers keep the
        # monolithic artifact. None = monolithic-only (the reference
        # posture, --no-base-wire-v2). Single-host only: on a pod the
        # coordinator-gated monolithic publish stays the whole story.
        self.base_dist = base_dist
        # agg artifact id -> declared weight sum (meta rider), per round
        self._round_agg_weights: dict[str, float] = {}
        self.transport = transport
        self.chain = chain
        self.strategy = strategy
        self.val_batches = val_batches
        self.address_store = address_store
        self.clock = clock or RealClock()
        self.max_delta_abs = max_delta_abs
        self.metrics = metrics
        # False = all-float fleet: reject int8-wire submissions and skip
        # the quant-template alloc on garbage (see Validator.accept_quant)
        self.accept_quant = accept_quant
        # wire-v2 shard-manifest submissions (engine/ingest.py fetches
        # only changed shards); False = v1-only receiver posture
        self.accept_wire_v2 = accept_wire_v2
        # "skip": a delta whose rider names a DIFFERENT base than the
        # current one is not merged — applying it would re-add the part
        # of the last merge the miner had already incorporated (stale
        # double-apply; the reference silently does this,
        # training_manager.py:417-422 vs averaging_logic.py:422-448).
        # "accept" restores reference behavior; riderless deltas are
        # always accepted either way.
        if stale_deltas not in ("skip", "accept"):
            raise ValueError(f"stale_deltas must be 'skip' or 'accept', "
                             f"got {stale_deltas!r}")
        self.stale_deltas = stale_deltas
        # "improved": publish the merged base only when its eval loss
        # does not exceed the CURRENT base's on the same fixed batches —
        # the 2-hour soak showed that always-publishing (the reference's
        # behavior, averaging_logic.py:544-583) lets val-negative deltas
        # (short training windows, train/val noise) compound the shared
        # base upward round over round (docs/soak_r04_before_stale_fix
        # .jsonl: 1.99 -> 2.71 over 62 rounds). One extra eval pass per
        # round buys a monotone non-increasing base. "always" restores
        # reference behavior.
        if publish_policy not in ("improved", "always"):
            raise ValueError(f"publish_policy must be 'improved' or "
                             f"'always', got {publish_policy!r}")
        self.publish_policy = publish_policy
        # accept adapter-tree submissions alongside full-param deltas
        # (the ingestor builds + caches the adapter wire template)
        self.lora_cfg = lora_cfg
        # concurrent revision-aware ingest (engine/ingest.py): fetch pool
        # width and host-cache byte budget (0 disables the cache; 1
        # worker restores serial fetch order)
        self.ingest_workers = ingest_workers
        self.ingest_cache_mb = ingest_cache_mb
        self._ingestor = None
        # hotkey -> delta_revision probed by THIS round's ingest — the
        # declined-merge fingerprint reuses these instead of issuing a
        # second delta_revision read per miner per round
        self._round_revisions: dict[str, str | None] = {}
        self.report = AveragerReport()
        self.base_params: Params | None = None
        self._base_revision = None
        self._base_loss = None   # cached eval of base_params (publish guard)
        self._declined_fp = None  # delta-revision set of the last declined
        #                           merge (skip identical re-merges)
        self._host_template_cache = None
        self._quant_template_cache = None
        # hotkey -> correlation id (delta_id from the meta rider) of the
        # submissions gathered THIS round — the merge span records exactly
        # which artifacts entered each merge (utils/obs.py)
        self._round_cids: dict[str, str] = {}
        # hotkey -> full StagedDelta of the submissions ACCEPTED this
        # round (revision/wire_bytes/verdict) — what the lineage record
        # freezes; matches the merge inputs by construction
        self._round_staged: dict = {}

    # -- multi-host (the averager can span a pod too) -----------------------
    def _multi(self) -> bool:
        from .train import mesh_spans
        return mesh_spans(self.engine)

    def _host_template(self):
        """Cached WIRE-layout template for every transport read (see the
        wire helpers in train.py — artifacts travel unrolled; wire_in
        converts to this engine's internal layout)."""
        if self._host_template_cache is None:
            from .train import host_wire_template
            self._host_template_cache = host_wire_template(self.engine)
        return self._host_template_cache

    def bootstrap(self, rng=None, params=None) -> None:
        """``params`` (value or zero-arg callable, e.g. a pretrained loader)
        seeds the genesis base; an already-published base always wins."""
        from .train import wire_in, wire_out
        if self._multi():
            # coordinator-read + broadcast, like every pod transport read
            from .train import broadcast_base_fetch
            fetched = broadcast_base_fetch(self.transport,
                                           self._host_template(), None)
        elif self.transport.base_revision() is not None:
            fetched = self.transport.fetch_base(self._host_template())
        else:
            fetched = None
        if fetched is not None:
            self.base_params = wire_in(self.engine, fetched[0])
            self._base_revision = fetched[1]
        else:
            given = None if callable(params) else params
            if given is None and callable(params):
                given = params()
            # genesis: identical on every process (deterministic from the
            # same rng / the same loaded weights)
            template = given if given is not None else \
                self.engine.model.init_params(
                    rng if rng is not None else jax.random.PRNGKey(0))
            self.base_params = template
            # the averager owns the shared repo and publishes the first base
            # (averaging_logic.py:549-568); coordinator-gated on a pod
            wire_tree = wire_out(self.engine, template)
            self._base_revision = self.transport.publish_base(wire_tree)
            self._publish_base_dist(wire_tree)
            if self.lineage is not None and self._base_revision:
                # the DAG root: a genesis record with no parent and no
                # contributions, so every later revision's chain
                # terminates at the seed checkpoint instead of dangling
                self.lineage.on_publish(
                    kind="base", revision=self._base_revision,
                    parent=None, round_no=self.report.rounds,
                    contributions=[], strategy="genesis",
                    replayable=False, weights_kind="none")
        self.base_params = self.engine.place_params(self.base_params)
        self._base_loss = None   # new base: guard re-evaluates lazily

    def _quant_template(self):
        """Lazy+cached int8 wire template supplier (see Validator's)."""
        if self._quant_template_cache is None:
            self._quant_template_cache = delta_lib.quantized_template(
                self._host_template())
        return self._quant_template_cache

    def _ingest(self):
        """Lazy shared ingest front-end (engine/ingest.py): concurrent
        fetch pool + content-addressed host cache + fused cohort screen.
        Screening runs in WIRE layout against the wire template — the
        same leaves screen_delta checked post-wire_in, so verdicts are
        identical whatever this averager's scan setting."""
        if self._ingestor is None:
            from .ingest import DeltaIngestor
            from .train import _scan_wire_adapters
            # packed submissions stay PACKED end-to-end when the merge
            # strategy folds a host list by scatter-add
            # (WeightedAverage's aggregate_deltas path) and the engine
            # layout IS the wire layout (no mesh stack, no scan-blocks
            # restack) — the densify_packed_v2 round-trip (full-tensor
            # writes per contribution) then never runs on this role;
            # regressions are visible as ``delta.densify_fallbacks``
            self._packed_ingest = (
                getattr(self.strategy, "host_list_ingest", False)
                and getattr(self.engine, "mesh", None) is None
                and _scan_wire_adapters(self.engine.model) is None)
            self._ingestor = DeltaIngestor(
                self.transport, self._host_template,
                lora_cfg=self.lora_cfg,
                quant_template=self._quant_template,
                accept_quant=self.accept_quant,
                accept_wire_v2=self.accept_wire_v2,
                max_delta_abs=self.max_delta_abs,
                stale_deltas=self.stale_deltas,
                workers=self.ingest_workers,
                cache_bytes=self.ingest_cache_mb * (1 << 20),
                span_prefix="avg",
                densify=not self._packed_ingest,
                observer=(self.fleet.record_staging
                          if self.fleet is not None else None))
        return self._ingestor

    def close(self) -> None:
        """Drop the ingest pool's worker threads (idempotent)."""
        if self._ingestor is not None:
            self._ingestor.close()
        if self.fleet is not None:
            self.fleet.close()

    def gather_deltas(self) -> tuple[list[str], list[Params]]:
        from .train import wire_in
        self._round_cids.clear()
        self._round_revisions.clear()
        self._round_agg_weights.clear()
        self._round_staged.clear()
        if self.hierarchy is not None:
            # root of a tree aggregation: the cohort is the CONFIGURED
            # sub-averager node list (never the metagraph — __agg__.* is
            # a reserved namespace chain hotkeys can't collide with)
            from ..transport.base import agg_id
            hotkeys = [agg_id(n) for n in self.hierarchy]
        else:
            if self._multi():
                from .train import broadcast_metagraph
                meta = broadcast_metagraph(self.chain)
            else:
                meta = self.chain.sync()
            hotkeys = [h for h in meta.hotkeys
                       if h != getattr(self.chain, "my_hotkey", None)]
        if self.fleet is not None and not self._multi():
            # one observation round BEFORE staging: the staging observer
            # then folds outcomes into the freshly-advanced round. Pods
            # skip (the monitor is coordinator-only; the role entry point
            # wires fleet=None off-coordinator anyway).
            try:
                self.fleet.poll(hotkeys)
            except Exception:
                logger.exception("averager: fleet heartbeat poll failed")
        staged = self._ingest().stage(hotkeys,
                                      base_revision=self._base_revision,
                                      multi=self._multi(),
                                      exclude=(self.remediation.is_excluded
                                               if self.remediation is not None
                                               else None))
        ids, deltas = [], []
        rejected = 0
        for s in staged:
            self._round_revisions[s.hotkey] = s.revision
            if s.cid is not None:
                self._round_cids[s.hotkey] = s.cid
            if s.agg_weight is not None:
                self._round_agg_weights[s.hotkey] = s.agg_weight
            if s.delta is None:
                if s.reason == "stale_base":
                    logger.info("averager: skipping %s (delta vs a "
                                "superseded base)", s.hotkey)
                    rejected += 1
                elif s.reason == "quarantined":
                    logger.info("averager: skipping %s (quarantined)",
                                s.hotkey)
                    rejected += 1
                elif s.reason != "no_delta":
                    # shape/NaN/magnitude screens (averaging_logic.py:
                    # 121-127,404-410) and isolated per-miner fetch errors
                    logger.warning("averager: rejecting %s (%s)",
                                   s.hotkey, s.reason)
                    rejected += 1
                continue
            ids.append(s.hotkey)
            self._round_staged[s.hotkey] = s
            # packed v2 trees are ALREADY wire layout by definition (and
            # only staged packed when the engine layout matches it —
            # _ingest's densify gate); wire_in's restack would mangle
            # their {"idx","q","scale"} entries
            deltas.append(s.delta if delta_lib.is_packed_v2(s.delta)
                          else wire_in(self.engine, s.delta))
        # only the cids of ACCEPTED deltas annotate the merge records
        self._round_cids = {h: c for h, c in self._round_cids.items()
                            if h in set(ids)}
        self.report.last_accepted = len(ids)
        self.report.last_rejected = rejected
        return ids, deltas

    def _delta_fingerprint(self, ids: list[str]):
        """(hotkey, delta_revision) set — identifies an exact submission
        set so a declined merge is not recomputed until something
        changes. Single-host only (per-process revision reads would
        diverge on a pod; pods just re-merge). Revisions come from THIS
        round's ingest probes — no second transport read per miner; the
        rare fallback read is guarded against transport I/O errors only
        (a coding bug must surface, not read as 'no fingerprint')."""
        out = []
        for h in ids:
            rev = self._round_revisions.get(h)
            if rev is None:
                try:
                    rev = self.transport.delta_revision(h)
                except OSError:
                    return None
            out.append((h, rev))
        return frozenset(out)

    def _publish_base_dist(self, wire_tree: Params) -> None:
        """Shard-plane publication for the revision that just landed
        monolithically (engine/basedist.py): changed shards, then the
        per-revision manifest, then the announce rider. Isolated AND
        single-host only — a shard-plane failure degrades fetchers to
        the monolithic base they already have, never the round; a pod's
        publish is coordinator-gated at the monolithic layer and stays
        monolithic-only."""
        if self.base_dist is None or self._base_revision is None \
                or self._multi():
            return
        try:
            self.base_dist.publish_revision(wire_tree, self._base_revision)
        except Exception:
            logger.exception("averager: sharded base publish failed; "
                             "fetchers stay on the monolithic base")

    def _record_lineage(self, ids: list[str], weights, consensus,
                        parent: str | None, loss: float) -> None:
        """Freeze the just-published revision's provenance record
        (engine/lineage.py). Isolated: lineage failures degrade
        provenance, never the round."""
        try:
            from . import lineage as lineage_lib
            w, wkind = lineage_lib.resolve_weights(self.strategy, weights,
                                                   len(ids))
            contribs = lineage_lib.contributions_from_staging(
                ids, w, self._round_staged, consensus=consensus,
                cids=self._round_cids)
            self.lineage.on_publish(
                kind="base", revision=self._base_revision, parent=parent,
                round_no=self.report.rounds, contributions=contribs,
                strategy=type(self.strategy).__name__,
                replayable=w is not None, weights_kind=wkind,
                loss=loss, parent_loss=self._base_loss)
        except Exception:
            logger.exception("averager: lineage record failed")

    def _fleet_round_end(self) -> None:
        """SLO evaluation + remediation + ledger flush at the round
        cadence — called on EVERY run_round exit (merged, declined, or
        empty), so staleness advances and breaches fire even when nothing
        merges (a dead fleet is exactly when the SLOs matter). Isolated:
        health-plane failures never fail a round."""
        if self.fleet is None:
            return
        try:
            breaches = self.fleet.evaluate_slos()
            if self.remediation is not None:
                # breaches become actions: quarantine, probation ticks,
                # re-admission (engine/remediate.py) — BEFORE the flush so
                # the ledger snapshot this round records the new state
                self.remediation.observe_round(breaches)
            self.fleet.flush(self.metrics, step=self.report.rounds)
        except Exception:
            logger.exception("averager: fleet round-end failed")

    def run_round(self) -> bool:
        """One averaging cycle; returns True when deltas were gathered and
        merged (whether or not the publish guard let the result replace
        the base — see ``publish_policy``), False when there was nothing
        to merge."""
        if self.base_params is None:
            self.bootstrap()
        ids, deltas = self.gather_deltas()
        if not ids:
            logger.info("averager: no valid deltas this round")
            self._fleet_round_end()
            return False
        if (self._declined_fp is not None and not self._multi()
                and self._delta_fingerprint(ids) == self._declined_fp):
            # the exact submission set we already merged and declined:
            # re-running the (possibly meta-learning) merge would burn
            # the same eval passes for the same verdict
            logger.info("averager: submissions unchanged since the "
                        "declined merge; skipping recompute")
            self._fleet_round_end()
            self.report.rounds += 1
            return True
        if getattr(self.engine, "mesh", None) is not None:
            # ingest-shard the miner axis: the full M x params stack never
            # materializes on one device, and every merge strategy's sum
            # over that axis runs as partial sums + ICI all-reduce. The
            # stack pads to the merge-bucket ladder, so an elastic fleet
            # reuses compiled merge programs instead of compiling per M
            from ..parallel.collectives import (merge_axis, merge_bucket,
                                                stack_deltas_sharded)
            axis = merge_axis(self.engine.mesh)
            stacked = stack_deltas_sharded(
                deltas, self.engine.mesh, axis=axis,
                target=merge_bucket(len(deltas), self.engine.mesh, axis))
        elif getattr(self.strategy, "host_list_ingest", False):
            # the strategy bounds its own device memory (chunked merge /
            # packed scatter-add) — handing it a full device stack would
            # defeat that
            stacked = deltas
        else:
            # bucket-pad the single-device stack too: the stacked
            # strategies key their jitted programs (the full model
            # fwd+bwd for ParameterizedMerge) on the padded M, so a
            # wobbling accepted count must land on a ladder rung, not a
            # fresh multi-second compile per distinct M
            from ..parallel.collectives import mark_merge_bucket, merge_bucket
            m_pad = merge_bucket(len(deltas))
            mark_merge_bucket(m_pad)
            stacked = delta_lib.pad_stack(
                delta_lib.stack_deltas(deltas), m_pad)
        if self.hierarchy is not None:
            # per-subtree mixing: each aggregate's weight is the weight
            # sum its sub-averager declared (missing rider = 1.0 — one
            # anonymous subtree must not zero out, matching the
            # riderless-delta accept rule)
            consensus = {h: self._round_agg_weights.get(h, 1.0)
                         for h in ids}
        elif self._multi():
            # small chain read, same lockstep rule as everything else
            from .train import broadcast_json
            from ..parallel import multihost
            consensus = broadcast_json(
                getattr(self.chain, "consensus_scores", lambda: {})()
                if multihost.is_coordinator() else None) or {}
        else:
            consensus = getattr(self.chain, "consensus_scores", lambda: {})()
        # the merge span records exactly WHICH artifacts entered this
        # merge: with the per-push delta_id riders, one artifact's whole
        # life (snapshot -> upload -> fetch -> eval -> merge) joins on cid
        # in scripts/obs_report.py
        cids = [c for c in (self._round_cids.get(h) for h in ids) if c]
        with obs.span("avg.merge", miners=len(ids), cids=cids):
            merged, weights = self.strategy.merge(
                self.engine, self.base_params, stacked, ids,
                val_batches=self.val_batches, consensus=consensus)
        with obs.span("avg.eval"):
            loss, ppl = self.engine.evaluate(merged, self.val_batches())
        if self.publish_policy == "improved":
            if self._base_loss is None:
                # once per base: the batch factory is fixed, so the
                # comparison is exact; after a publish the new base's
                # loss IS the merged loss just computed (no re-eval)
                self._base_loss, _ = self.engine.evaluate(
                    self.base_params, self.val_batches())
            # NOT-improved spelling, deliberately: a NaN merged loss must
            # fail this test (``nan > x`` is False — the `>` spelling
            # would publish the NaN base and then disable every future
            # comparison), making the guard the NaN backstop BEHIND the
            # per-delta screens too
            if not (loss <= self._base_loss + 1e-6):
                logger.info(
                    "averager: merged loss %.4f would worsen the base "
                    "(%.4f); keeping the current base", loss,
                    self._base_loss)
                # last_loss reports the PUBLISHED base's loss — the
                # rejected candidate's would read as a regression the
                # guard just prevented
                self.report.last_loss = self._base_loss
                self.report.skipped_publishes += 1
                if self.metrics:
                    self.metrics.log(
                        {"merged_loss": loss, "merged_ppl": ppl,
                         "base_loss": self._base_loss,
                         "accepted": len(ids), "published": 0,
                         "merge_delta_ids": dict(self._round_cids)},
                        step=self.report.rounds)
                    obs.flush(self.metrics, step=self.report.rounds)
                self._fleet_round_end()
                self.report.rounds += 1
                self._declined_fp = self._delta_fingerprint(ids)
                self.transport.gc()   # storage bounding must not stall
                # the round DID meaningful work (gathered + merged +
                # evaluated); only the publish was declined
                return True
        if self.lease is not None:
            held = False
            try:
                held = self.lease.renew()
            except Exception:
                logger.exception("averager: lease renewal failed")
            if not held:
                # a higher epoch exists (a standby took over while this
                # averager was wedged/partitioned): publishing now would
                # put TWO writers on the shared base. Stand down — keep
                # merging locally so a later re-acquisition resumes warm,
                # but the round publishes nothing.
                logger.warning("averager: publication lease not held; "
                               "standing down (merged but not published)")
                obs.count("avg.lease_standdowns")
                self.report.last_loss = loss
                self.report.skipped_publishes += 1
                if self.metrics:
                    self.metrics.log(
                        {"merged_loss": loss, "merged_ppl": ppl,
                         "accepted": len(ids), "published": 0,
                         "lease_lost": 1,
                         "merge_delta_ids": dict(self._round_cids)},
                        step=self.report.rounds)
                    obs.flush(self.metrics, step=self.report.rounds)
                self._fleet_round_end()
                self.report.rounds += 1
                return True
        self.report.last_loss = loss
        parent_revision = self._base_revision
        from .train import wire_out
        with obs.span("avg.publish", cids=cids):
            wire_tree = wire_out(self.engine, merged)
            self._base_revision = self.transport.publish_base(wire_tree)
            self._publish_base_dist(wire_tree)
        if self.metrics:
            self.metrics.log({"merged_loss": loss, "merged_ppl": ppl,
                              "accepted": len(ids), "published": 1,
                              "base_revision": self._base_revision,
                              "lease_epoch": (self.lease.epoch
                                              if self.lease else None),
                              "merge_delta_ids": dict(self._round_cids)},
                             step=self.report.rounds)
        if self.lease is not None:
            # the publication carries the epoch: the token now names the
            # revision just published under the held epoch
            self.lease.stamp(self._base_revision)
            obs.gauge("avg.lease_epoch", float(self.lease.epoch))
        if self.lineage is not None:
            # provenance record for the revision that just landed —
            # AFTER the lease stamp (single-writer confirmed), BEFORE
            # the strategy commit; at this point self._base_loss still
            # holds the PARENT base's eval (None under publish "always")
            self._record_lineage(ids, weights, consensus,
                                 parent_revision, loss)
        # round-spanning strategy state (e.g. OuterOptMerge velocity) commits
        # only once the new base is actually out
        commit = getattr(self.strategy, "commit", None)
        if commit is not None:
            commit()
        self.base_params = merged
        self._base_loss = loss
        self._declined_fp = None
        self.transport.gc()
        if self.metrics:
            # registry flush at the round cadence (fetch/merge/publish
            # span histograms, retry counters)
            obs.flush(self.metrics, step=self.report.rounds)
        self._fleet_round_end()
        self.report.rounds += 1
        return True

    def run_periodic(self, *, interval: float = 1200.0,   # neurons/averager.py:106
                     rounds: int | None = None) -> int:
        """Run rounds forever (or ``rounds`` times); returns how many rounds
        actually merged (no exception and at least one accepted delta)."""
        done = merged = 0
        while rounds is None or done < rounds:
            try:
                if self.run_round():
                    merged += 1
            except Exception:
                logger.exception("averaging round failed; continuing")
            done += 1
            if rounds is None or done < rounds:
                self.clock.sleep(interval)
        return merged
