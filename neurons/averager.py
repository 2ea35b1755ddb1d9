"""Averager entry point: merge miner deltas into the next base model.

Rebuild of the reference averager (neurons/averager.py:39-106 →
ParameterizedAverager, hivetrain/averaging_logic.py:335-583). Run offline:

    python neurons/averager.py --backend local --work-dir /tmp/run \
        --model tiny --dataset synthetic --strategy parameterized --rounds 1
"""

from __future__ import annotations

import logging
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from distributedtraining_tpu.config import RunConfig           # noqa: E402
from distributedtraining_tpu.engine import (                   # noqa: E402
    AveragerLoop, GeneticMerge, OuterOptMerge, ParameterizedMerge,
    WeightedAverage)
from neurons.common import build, build_health_plane           # noqa: E402


def make_strategy(cfg: RunConfig, model):
    if cfg.strategy == "weighted":
        strategy = WeightedAverage(chunk_size=cfg.merge_chunk)
    elif cfg.strategy == "genetic":
        strategy = GeneticMerge(
            population=cfg.genetic_population,
            generations=cfg.genetic_generations,
            sigma=cfg.genetic_sigma,
            screen_batches=cfg.genetic_screen_batches or None)
    else:
        strategy = ParameterizedMerge(model, meta_epochs=cfg.meta_epochs,
                                      meta_lr=cfg.meta_lr,
                                      meta_optimizer=cfg.meta_optimizer)
    if cfg.outer_momentum > 0:
        strategy = OuterOptMerge(
            strategy, outer_lr=cfg.outer_lr, momentum=cfg.outer_momentum,
            # persist the DiLoCo velocity across supervised restarts
            state_path=os.path.join(cfg.work_dir, "averager_state",
                                    f"velocity_{cfg.hotkey}.msgpack"))
    return strategy


def _hier_nodes(cfg: RunConfig) -> list[str]:
    return [n.strip() for n in (cfg.hier_nodes or "").split(",")
            if n.strip()]


def _run_sub_averager(cfg: RunConfig, c, plane) -> int:
    """--hier sub: this process is one node of the aggregation tree
    (engine/hier_average.py) — gather the plan_fanout slice, publish the
    partial aggregate under __agg__.<node>. No eval set, no strategy, no
    base publication; failover rides a per-node subavg.<node> lease."""
    from distributedtraining_tpu.engine.hier_average import (SubAverager,
                                                             plan_fanout)
    from distributedtraining_tpu.engine.train import host_wire_template

    nodes = _hier_nodes(cfg)
    node = cfg.hier_node or cfg.hotkey
    if not nodes and cfg.hier_fanout <= 0:
        raise SystemExit("--hier sub needs --hier-nodes or --hier-fanout "
                         "to derive this node's miner slice")
    if nodes and node not in nodes:
        raise SystemExit(f"--hier-node {node!r} is not in --hier-nodes "
                         f"{nodes} — the slice plan would never assign "
                         "it a miner")

    def assigned():
        meta = c.chain.sync()
        hotkeys = [h for h in meta.hotkeys if h != cfg.hotkey]
        plan = plan_fanout(hotkeys, nodes=nodes or None,
                           fanout=cfg.hier_fanout or None)
        return plan.get(node, [])

    lease = None
    if cfg.remediate or cfg.standby:
        from distributedtraining_tpu.engine.remediate import LeaseManager
        lease = LeaseManager(c.transport, cfg.hotkey,
                             role=f"subavg.{node}")
    lineage = None
    if cfg.lineage:
        from distributedtraining_tpu.engine.lineage import LineagePlane
        lineage = LineagePlane(c.transport, node=f"subavg.{node}")
    mirror = None
    if cfg.base_wire_v2 and cfg.base_mirror:
        # regional mirror duty (engine/basedist.py): this __agg__ node
        # re-publishes the base shards it pulls under __mirror__.<node>
        # so nearby fetchers race a replica instead of the origin
        from distributedtraining_tpu.engine.basedist import MirrorDuty
        mirror = MirrorDuty(c.transport, node)
    sub = SubAverager(
        c.transport, node, lambda: host_wire_template(c.engine), assigned,
        consensus=lambda: getattr(c.chain, "consensus_scores",
                                  lambda: {})(),
        max_delta_abs=cfg.max_delta_abs,
        stale_deltas=cfg.stale_deltas or "skip",
        accept_quant=cfg.accept_quant,
        accept_wire_v2=cfg.accept_wire_v2,
        lora_cfg=c.lora_cfg,
        ingest_workers=cfg.ingest_workers,
        ingest_cache_mb=cfg.ingest_cache_mb,
        wire_spec=True if cfg.hier_wire_v2 else None,
        lease=lease, metrics=c.metrics, fleet=plane.fleet,
        lineage=lineage, mirror=mirror)
    try:
        merged = sub.run_periodic(interval=cfg.averaging_interval,
                                  rounds=cfg.rounds)
    except KeyboardInterrupt:
        merged = sub.report.rounds
    finally:
        plane.close()
        sub.close()
        from distributedtraining_tpu.utils import devprof, flight, obs
        flight.shutdown()
        obs.reset()
        devprof.reset()
    logging.info("sub-averager %s done: rounds=%d accepted=%d pushes=%d",
                 node, sub.report.rounds, sub.report.last_accepted,
                 sub.report.pushes)
    return 0 if merged else 1


def main(argv=None) -> int:
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    cfg = RunConfig.from_args("averager", argv)
    c = build(cfg)
    # crash-forensics triggers (utils/flight.py, see neurons/miner.py)
    from distributedtraining_tpu.utils import flight
    flight.install_crash_hooks()
    # fleet health plane: the averager both heartbeats AND monitors —
    # its FleetMonitor folds every gather's staging outcomes into the
    # contribution ledger and evaluates the SLO rules each round; a
    # breach arms the AnomalyMonitor one-shot (detection + counters —
    # no train loop here to tick a profiler capture).
    from distributedtraining_tpu.engine.health import report_vitals
    from distributedtraining_tpu.utils.obs import AnomalyMonitor
    anomaly = AnomalyMonitor()
    plane = build_health_plane(cfg, c, monitor=True,
                               anomaly=anomaly,
                               start_heartbeat=False)
    if cfg.hier == "sub":
        # the sub-averager role shares the build + health plane but runs
        # a different loop entirely (no base publication)
        if plane.heartbeat is not None:
            plane.heartbeat.start()
        return _run_sub_averager(cfg, c, plane)
    hierarchy = None
    if cfg.hier == "root":
        hierarchy = _hier_nodes(cfg)
        if not hierarchy and cfg.hier_fanout > 0:
            # fanout-only fleets: derive the auto-named node list from
            # the boot-time metagraph (the subs derive the same names);
            # --hier-nodes is the stable spelling when the fleet wobbles
            from distributedtraining_tpu.engine.hier_average import \
                plan_fanout
            meta = c.chain.sync()
            hierarchy = list(plan_fanout(
                [h for h in meta.hotkeys if h != cfg.hotkey],
                fanout=cfg.hier_fanout))
        if not hierarchy:
            raise SystemExit("--hier root needs --hier-nodes (or "
                             "--hier-fanout) to know which __agg__ "
                             "artifacts to gather")
    # publication lease (engine/remediate.py): held and renewed whenever
    # a remediating or standby-backed fleet runs, so base publication
    # stays single-writer across an averager failover
    lease = None
    if cfg.remediate or cfg.standby:
        from distributedtraining_tpu.engine.remediate import LeaseManager
        lease = LeaseManager(c.transport, cfg.hotkey)
    # provenance plane (engine/lineage.py): a content-addressed
    # __lineage__ record per landed merge + the merged-quality
    # EWMA/CUSUM drift detector, sharing the fleet's AnomalyMonitor
    # one-shot so a quality drift arms the same forensics a breach does
    lineage = None
    if cfg.lineage:
        from distributedtraining_tpu.engine.lineage import LineagePlane
        lineage = LineagePlane(c.transport, node=cfg.hotkey,
                               anomaly=anomaly)
    # content-addressed base distribution (engine/basedist.py): each
    # monolithic publish is followed by the changed-shard set + signed
    # per-revision manifest; the announce rider advertises the fleet's
    # __agg__ nodes (plus any --base-mirrors) as shard mirrors.
    # Single-host only — a pod's coordinator-gated monolithic publish
    # stays the whole story (the loop also gates on _multi()).
    base_dist = None
    if cfg.base_wire_v2:
        import jax as _jax
        if _jax.process_count() <= 1:
            from distributedtraining_tpu.engine.basedist import BasePublisher
            mirror_nodes = list(hierarchy or [])
            mirror_nodes += [m.strip() for m in
                             (cfg.base_mirrors or "").split(",")
                             if m.strip() and m.strip() not in mirror_nodes]
            base_dist = BasePublisher(c.transport, mirrors=mirror_nodes)
    loop = AveragerLoop(c.engine, c.transport, c.chain,
                        make_strategy(cfg, c.model),
                        val_batches=c.eval_batches(),
                        address_store=c.address_store,
                        max_delta_abs=cfg.max_delta_abs,
                        metrics=c.metrics, lora_cfg=c.lora_cfg,
                        accept_quant=cfg.accept_quant,
                        accept_wire_v2=cfg.accept_wire_v2,
                        stale_deltas=cfg.stale_deltas or "skip",
                        publish_policy=cfg.publish_policy,
                        ingest_workers=cfg.ingest_workers,
                        ingest_cache_mb=cfg.ingest_cache_mb,
                        fleet=plane.fleet,
                        remediation=plane.remediation,
                        lease=lease,
                        hierarchy=hierarchy,
                        lineage=lineage,
                        base_dist=base_dist)
    if plane.heartbeat is not None:
        plane.heartbeat.vitals = report_vitals(
            loop.report, base_revision=lambda: loop._base_revision)
        plane.heartbeat.start()
    try:
        if cfg.standby:
            # passive failover replica: NO bootstrap (a standby must
            # never publish a genesis base or steal the lease at boot) —
            # it follows the primary and bootstraps at takeover
            from distributedtraining_tpu.engine.remediate import (
                StandbyAverager)
            standby = StandbyAverager(
                loop, lease,
                deadline_s=(cfg.failover_deadline
                            or 3 * cfg.averaging_interval),
                poll_s=max(1.0, min(cfg.averaging_interval / 4, 30.0)))
            merged = standby.run(interval=cfg.averaging_interval,
                                 rounds=cfg.rounds)
        else:
            if lease is not None:
                try:
                    if not lease.acquire():
                        logging.warning(
                            "averager: lease held elsewhere at boot; "
                            "rounds will merge but stand down at publish "
                            "until the lease is reclaimed")
                except Exception:
                    logging.warning("averager: lease acquisition failed "
                                    "at boot; will retry lazily",
                                    exc_info=True)
            loop.bootstrap(params=c.initial_params)
            merged = loop.run_periodic(interval=cfg.averaging_interval,
                                       rounds=cfg.rounds)
    except KeyboardInterrupt:
        merged = loop.report.rounds > 0
    finally:
        plane.close()  # exporter socket + heartbeat timer + fleet pool
        loop.close()   # drain the ingest pool's worker threads
        # see neurons/miner.py: crash bundle, then global obs state reset
        flight.shutdown()
        from distributedtraining_tpu.utils import devprof, obs
        obs.reset()
        devprof.reset()
    logging.info("averager done: rounds=%d accepted=%d rejected=%d loss=%.4f",
                 loop.report.rounds, loop.report.last_accepted,
                 loop.report.last_rejected, loop.report.last_loss)
    return 0 if merged else 1


if __name__ == "__main__":
    raise SystemExit(main())
