"""Hierarchical tree aggregation: sub-averagers fold fanout-sized slices
of the fleet into partial aggregates; a root averager merges aggregates.

The reference averager is ONE trusted node that pulls every miner delta
and merges on one host (PAPER.md §0, averaging_logic.py) — round cost
O(miners) on one machine, the scaling wall left in ROADMAP item 2 now
that the wire (PR 7) and ingest (PR 4) are off the critical path. This
module splits the merge into a tree:

- a :class:`SubAverager` owns a SLICE of the fleet (``plan_fanout``):
  each round it stages its assigned miners through the shared ingest
  front-end (engine/ingest.py — same pool, same content-addressed
  cache, same fused screens, ``densify=False`` so wire-v2 submissions
  stay PACKED and fold in by scatter-add, delta.accumulate_delta),
  computes the consensus-weighted average of the accepted deltas with
  O(params) device memory, and publishes it as an ORDINARY delta
  artifact under the reserved ``__agg__.<node>`` id
  (transport/base.agg_id) — so every transport, wrapper (signed /
  chaos / coordinator-gated), retry policy, and cache carries
  aggregates with zero new backend code;
- the ROOT is just :class:`~.average.AveragerLoop` with
  ``hierarchy=[node ids]``: it stages the ``__agg__.*`` ids instead of
  chain hotkeys, reads each subtree's weight mass off the aggregate's
  ``"agg"`` meta rider, and merges aggregates through whatever strategy
  it runs — ParameterizedMerge/GeneticMerge mixing weights become
  per-subtree for free.

Round cost per node drops O(miners) → O(miners / fanout) (each sub
stages+merges its fanout; the root stages+merges miners/fanout
aggregates), and the layers compose: a sub-averager is just another
lease-holding single-writer role, so the PR-6 standby machinery covers
it via ``LeaseManager(role="subavg.<node>")``.

Exactness: a sub publishes ``a_j = sum_{i in j} (c_i / C_j) d_i`` and
declares ``C_j`` (its clamped consensus mass; miner count when the
subtree has no scores — the uniform spelling). The root mixes with
``C_j / sum_j C_j``, so the tree telescopes to the flat merge
``sum_i (c_i / C) d_i`` exactly in real arithmetic and to fp tolerance
on hardware (pinned in tests/test_hier_average.py). A dead or torn
sub-averager stages as
absent/stale at the root, which degrades to the surviving subtrees —
the same per-miner isolation the flat gather already had.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable, Sequence

import numpy as np

from .. import delta as delta_lib
from ..transport.base import agg_id
from ..utils import obs
from .scheduler import Clock, RealClock

logger = logging.getLogger(__name__)

Params = Any


def plan_fanout(hotkeys: Sequence[str], *,
                nodes: Sequence[str] | None = None,
                fanout: int | None = None) -> dict[str, list[str]]:
    """Deterministic miner→sub-averager assignment.

    ``nodes`` names the sub-averagers explicitly (the stable production
    spelling — every role derives the identical plan from the same
    metagraph view and node list); ``fanout`` alone auto-names
    ``ceil(M / fanout)`` nodes ``sub0..subN-1`` (tests, benches, and
    fleets whose sub count tracks fleet size). Assignment is round-robin
    over the SORTED hotkeys, so it is stable under metagraph enumeration
    order and balanced to within one miner per node. Every node appears
    in the result (possibly with an empty slice) — a sub-averager must
    be able to look itself up even on a round where the fleet shrank.
    """
    keys = sorted(dict.fromkeys(hotkeys))
    if nodes:
        node_list = list(dict.fromkeys(nodes))
    else:
        if not fanout or fanout < 1:
            raise ValueError("plan_fanout: pass nodes=[...] or fanout >= 1")
        n = max(1, -(-len(keys) // fanout)) if keys else 1
        node_list = [f"sub{i}" for i in range(n)]
    plan: dict[str, list[str]] = {n: [] for n in node_list}
    for i, h in enumerate(keys):
        plan[node_list[i % len(node_list)]].append(h)
    return plan


def subtree_weights(ids: Sequence[str],
                    consensus: dict[str, float] | None
                    ) -> tuple[jax.Array, float]:
    """(normalized (m,) mixing vector, declared weight mass) for one
    subtree. The vector is :func:`delta.normalized_merge_weights`
    (normalized over the REAL m — padding never leaks in); the mass is
    the subtree's clamped consensus total, or the miner COUNT when the
    subtree carries no score mass — the spelling under which the root's
    ``C_j / sum C_j`` mixing telescopes to the flat uniform 1/M."""
    w = delta_lib.normalized_merge_weights(ids, consensus)
    if consensus:
        mass = float(sum(max(float(consensus.get(h, 0.0)), 0.0)
                         for h in ids))
        if np.isfinite(mass) and mass > 0:
            return w, mass
    return w, float(len(ids))


@dataclasses.dataclass
class SubAveragerReport:
    rounds: int = 0
    last_accepted: int = 0
    last_rejected: int = 0
    pushes: int = 0                 # DeltaPublisher's counter fields
    pushes_failed: int = 0
    pushes_superseded: int = 0
    skipped_publishes: int = 0      # lease stand-downs
    last_weight_sum: float = float("nan")


class SubAverager:
    """One node of the aggregation tree: gather an assigned slice,
    publish the partial aggregate.

    No engine, no eval set: a sub-averager is pure delta arithmetic in
    WIRE layout against ``template`` (the host wire template,
    engine/train.host_wire_template — or any structurally identical
    zeros tree). ``assigned`` is the node's miner slice: a list, or a
    zero-arg callable re-evaluated each round (the ``plan_fanout`` hook
    for elastic fleets). ``consensus`` supplies validator scores the
    same way. ``wire_spec`` opts the aggregate itself into the v2 shard
    wire (density 1.0 + quant "none" by default when enabled — LOSSLESS,
    so tree parity survives, while unchanged layers still dedupe at the
    shard level round over round); None publishes the dense v1 artifact.
    ``lease`` (LeaseManager, role ``subavg.<node>``) makes the node a
    single-writer role under the PR-6 failover machinery: renewal is
    re-confirmed immediately before every publish, and a lost lease
    stands the round down exactly like the root averager's."""

    def __init__(self, transport, node_id: str, template, assigned, *,
                 consensus: Callable[[], dict] | dict | None = None,
                 max_delta_abs: float | None = 1e3,
                 stale_deltas: str = "skip",
                 accept_quant: bool = True,
                 accept_wire_v2: bool = True,
                 lora_cfg=None, quant_template=None,
                 ingest_workers: int = 4,
                 ingest_cache_mb: int = 2048,
                 wire_spec: dict | None = None,
                 lease=None, metrics=None, fleet=None,
                 retry_policy=None, publish_retry=None, meta_retry=None,
                 lineage=None,
                 mirror=None,
                 clock: Clock | None = None):
        self.transport = transport
        self.node_id = node_id
        self.artifact_id = agg_id(node_id)
        self._template_in = template
        self._template_cache = None
        self._assigned = assigned
        self._consensus = consensus
        self.max_delta_abs = max_delta_abs
        self.stale_deltas = stale_deltas
        self.accept_quant = accept_quant
        self.accept_wire_v2 = accept_wire_v2
        self.lora_cfg = lora_cfg
        self.quant_template = quant_template
        self.ingest_workers = ingest_workers
        self.ingest_cache_mb = ingest_cache_mb
        if wire_spec is True:
            wire_spec = {"format": 2, "density": 1.0, "quant": "none"}
        self.wire_spec = wire_spec
        self.lease = lease
        self.metrics = metrics
        self.fleet = fleet
        self.retry_policy = retry_policy       # ingest probes/fetches
        self.publish_retry = publish_retry     # aggregate publishes
        self.meta_retry = meta_retry
        # provenance plane (engine/lineage.py): each published aggregate
        # freezes an "agg" lineage record — the (hotkey, rev, weight)
        # slice that entered this fold — so the root's "base" record and
        # the subs' "agg" records together form the full DAG level
        self.lineage = lineage
        # regional shard-mirror duty (engine/basedist.MirrorDuty): this
        # __agg__ node re-publishes the base shards it already pulled
        # under its __mirror__.<node> slots, so fetchers near it race a
        # replica instead of joining the origin incast. One sync per
        # round, isolated — a failed mirror pass is a non-event (the
        # whole design premise: any replica may die).
        self.mirror = mirror
        self.clock = clock or RealClock()
        self.report = SubAveragerReport()
        self._ingestor = None
        self._publisher = None

    # -- lazy plumbing -------------------------------------------------------
    def _template(self):
        if self._template_cache is None:
            t = self._template_in
            self._template_cache = t() if callable(t) else t
        return self._template_cache

    def _ingest(self):
        if self._ingestor is None:
            from .ingest import DeltaIngestor
            self._ingestor = DeltaIngestor(
                self.transport, self._template,
                lora_cfg=self.lora_cfg,
                quant_template=self.quant_template,
                accept_quant=self.accept_quant,
                accept_wire_v2=self.accept_wire_v2,
                max_delta_abs=self.max_delta_abs,
                stale_deltas=self.stale_deltas,
                workers=self.ingest_workers,
                cache_bytes=self.ingest_cache_mb * (1 << 20),
                span_prefix="subavg",
                densify=False,   # packed submissions fold in packed form
                retry_policy=self.retry_policy,
                observer=(self.fleet.record_staging
                          if self.fleet is not None else None))
        return self._ingestor

    def _pub(self):
        if self._publisher is None:
            from .publish import DeltaPublisher
            self._publisher = DeltaPublisher(
                self.transport, self.artifact_id, report=self.report,
                nan_guard=False,   # inputs are already screened finite
                publish_retry=self.publish_retry,
                meta_retry=self.meta_retry,
                wire_spec=self.wire_spec)
        return self._publisher

    def assigned(self) -> list[str]:
        a = self._assigned() if callable(self._assigned) else self._assigned
        return list(a)

    def consensus(self) -> dict[str, float]:
        c = self._consensus() if callable(self._consensus) \
            else self._consensus
        return dict(c) if c else {}

    def close(self) -> None:
        if self._ingestor is not None:
            self._ingestor.close()
        if self._publisher is not None:
            self._publisher.close()
        if self.fleet is not None:
            self.fleet.close()

    # -- one round -----------------------------------------------------------
    def run_round(self) -> bool:
        """Gather the slice, fold, publish. Returns True when an
        aggregate was computed (whether or not the lease let it publish),
        False on an empty round (nothing accepted — the node publishes
        nothing, so the root's stale skip retires its previous aggregate
        instead of double-applying it against a moved base)."""
        try:
            base_revision = self.transport.base_revision()
        except Exception:
            logger.warning("subavg %s: base revision probe failed; staging "
                           "without staleness context", self.node_id,
                           exc_info=True)
            base_revision = None
        assigned = self.assigned()
        if self.mirror is not None:
            # mirror BEFORE the fold: the shards this node replicates
            # are the base its miners are about to pull, so the replica
            # is warm when the fan-out tree needs it. Runs on EVERY
            # round (empty folds included) — mirror freshness must not
            # depend on this subtree having submissions.
            try:
                with obs.span("subavg.mirror", node=self.node_id):
                    self.mirror.sync()
            except Exception:
                logger.exception("subavg %s: mirror sync failed",
                                 self.node_id)
        if self.fleet is not None:
            try:
                self.fleet.poll(assigned)
            except Exception:
                logger.exception("subavg %s: fleet poll failed",
                                 self.node_id)
        staged = self._ingest().stage(assigned,
                                      base_revision=base_revision) \
            if assigned else []
        ids, deltas = [], []
        staged_by_hotkey = {}
        rejected = 0
        for s in staged:
            if s.delta is None:
                if s.reason not in ("no_delta",):
                    rejected += 1
                continue
            ids.append(s.hotkey)
            staged_by_hotkey[s.hotkey] = s
            deltas.append(s.delta)
        self.report.last_accepted = len(ids)
        self.report.last_rejected = rejected
        if not ids:
            logger.info("subavg %s: no valid deltas this round",
                        self.node_id)
            obs.count("hier.empty_sub_rounds")
            self.report.rounds += 1
            return False
        w, mass = subtree_weights(ids, self.consensus())
        self.report.last_weight_sum = mass
        with obs.span("subavg.merge", node=self.node_id, miners=len(ids)):
            # one accumulator, one contribution at a time — packed
            # (scatter-add) and dense (fused add) alike; the M x params
            # stack never exists on this node
            agg = delta_lib.aggregate_deltas(self._template(), deltas, w)
        # the PR-5 peak-bytes gauge is the production assert that the
        # packed merge stayed O(params): a fold that secretly stacked
        # M x params would jump this high-water mark by the stack size
        # (empty on stat-less backends — CPU; the structural test in
        # tests/test_hier_average.py pins it there)
        from ..utils.metrics import device_memory_watermarks
        for k, v in device_memory_watermarks().items():
            obs.gauge(f"subavg.{k}", v)
        if self.lease is not None:
            held = False
            try:
                held = self.lease.renew()
            except Exception:
                logger.exception("subavg %s: lease renewal failed",
                                 self.node_id)
            if not held:
                logger.warning("subavg %s: publication lease not held; "
                               "standing down (merged but not published)",
                               self.node_id)
                obs.count("hier.lease_standdowns")
                self.report.skipped_publishes += 1
                self.report.rounds += 1
                return True
        payload = agg
        if self.wire_spec:
            packed, _ = delta_lib.pack_delta_v2(
                agg, density=float(self.wire_spec.get("density", 1.0)),
                quant=self.wire_spec.get("quant", "none"))
            payload = packed
        with obs.span("subavg.publish", node=self.node_id):
            ok = self._pub().publish_now(
                payload, None, base_revision,
                extra_meta={"agg": {"weight": mass, "miners": len(ids),
                                    "node": self.node_id}})
        if ok:
            obs.count("hier.agg_publishes")
            if self.lease is not None:
                self.lease.stamp(base_revision)
            if self.lineage is not None:
                self._record_lineage(ids, w, staged_by_hotkey,
                                     base_revision)
        if self.metrics:
            try:
                self.metrics.log({"subavg_node": self.node_id,
                                  "accepted": len(ids),
                                  "rejected": rejected,
                                  "weight_sum": mass,
                                  "published": int(ok)},
                                 step=self.report.rounds)
                obs.flush(self.metrics, step=self.report.rounds)
            except Exception:
                logger.exception("subavg %s: metrics emit failed",
                                 self.node_id)
        self.report.rounds += 1
        return True

    def _record_lineage(self, ids: list[str], w, staged: dict,
                        base_revision: str | None) -> None:
        """Freeze the just-published aggregate's "agg" lineage record.
        The record's revision is the AGGREGATE artifact's revision
        (probed after the publish — the content address the root will
        stage), its parent is the base the fold ran against, and its
        weights are the exact normalized subtree vector, so any
        validator can re-derive the aggregate (lineage_report --replay).
        Isolated: lineage failures never fail the round."""
        try:
            from . import lineage as lineage_lib
            try:
                rev = self.transport.delta_revision(self.artifact_id)
            except Exception:
                logger.warning("subavg %s: aggregate revision probe "
                               "failed; lineage record skipped",
                               self.node_id, exc_info=True)
                return
            if rev is None:
                return
            weights = [float(x) for x in np.asarray(w).reshape(-1)]
            contribs = lineage_lib.contributions_from_staging(
                ids, weights, staged, consensus=self.consensus())
            self.lineage.on_publish(
                kind="agg", revision=rev, parent=base_revision,
                round_no=self.report.rounds, contributions=contribs,
                strategy="weighted", replayable=not self.wire_spec
                or self.wire_spec.get("quant", "none") == "none",
                weights_kind="merge", artifact=self.artifact_id)
        except Exception:
            logger.exception("subavg %s: lineage record failed",
                             self.node_id)

    def run_periodic(self, *, interval: float = 1200.0,
                     rounds: int | None = None) -> int:
        """Run rounds forever (or ``rounds`` times); returns how many
        rounds aggregated at least one delta."""
        done = merged = 0
        while rounds is None or done < rounds:
            try:
                if self.run_round():
                    merged += 1
            except Exception:
                logger.exception("subavg %s: round failed; continuing",
                                 self.node_id)
            done += 1
            if rounds is None or done < rounds:
                self.clock.sleep(interval)
        return merged
