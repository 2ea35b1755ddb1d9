"""Miner entry point: train on the current base, publish weight deltas.

Rebuild of the reference miner (neurons/miner.py:30-129 → DeltaLoop,
hivetrain/training_manager.py:345-433). Run offline end-to-end with:

    python neurons/miner.py --backend local --work-dir /tmp/run \
        --model tiny --dataset synthetic --max-steps 50
"""

from __future__ import annotations

import logging
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from distributedtraining_tpu.config import RunConfig   # noqa: E402
from distributedtraining_tpu.engine import MinerLoop   # noqa: E402
from neurons.common import (build, build_base_fetcher,  # noqa: E402
                            build_health_plane)


def _guard_kwargs(cfg, c) -> dict:
    """Self-validation-guard wiring, shared by the full-param and LoRA
    branches. 0 disables; negative follows --send-interval (and disables
    when that is non-positive — push-every-step runs would eval every
    step and revert on per-step noise).

    The guard evals run on the miner's OWN disjoint slice of the test
    split (Components.miner_val_batches), never the validator's shard:
    keeping best-seen state by the exact data it is scored on would bias
    published scores upward by selection (round-5 advisor)."""
    if cfg.self_eval_interval == 0:
        return {}
    interval = (cfg.self_eval_interval if cfg.self_eval_interval > 0
                else cfg.send_interval)
    if interval <= 0:
        return {}
    return dict(val_batches=c.miner_val_batches(),
                val_guard_interval=interval,
                val_guard_patience=cfg.self_eval_patience,
                val_guard_margin=cfg.self_eval_margin)


def main(argv=None) -> int:
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    cfg = RunConfig.from_args("miner", argv)
    c = build(cfg)
    # crash-forensics triggers (utils/flight.py): an unhandled exception
    # (main or worker thread) or interpreter exit freezes the flight
    # ring into a transport-published postmortem bundle
    from distributedtraining_tpu.utils import flight
    flight.install_crash_hooks()

    trace = None
    if cfg.profile_dir:
        from distributedtraining_tpu.utils.metrics import TraceCapture
        trace = TraceCapture(cfg.profile_dir, steps=cfg.profile_steps)
    anomaly = None
    if cfg.anomaly_trace:
        # disarmed capture + monitor: a loss spike, push-failure streak,
        # or step-time p99 blowout arms ONE bounded profiler window
        # automatically (utils/obs.AnomalyMonitor); until then every
        # tick is a no-op
        from distributedtraining_tpu.utils.metrics import TraceCapture
        from distributedtraining_tpu.utils.obs import AnomalyMonitor
        anomaly = AnomalyMonitor(TraceCapture(
            cfg.anomaly_dir or os.path.join(cfg.work_dir, "anomaly_traces",
                                            cfg.hotkey),
            steps=cfg.profile_steps, arm=False))
    # content-addressed base pulls (engine/basedist.py): changed-hash
    # layers only, mirror racing, monolithic fallback; None when
    # --no-base-wire-v2 (or on a pod, where the coordinator broadcast
    # stays monolithic)
    base_fetcher = build_base_fetcher(cfg, c)
    store = None
    if cfg.checkpoint_interval > 0:
        from distributedtraining_tpu.checkpoint import CheckpointStore
        ckpt_dir = cfg.checkpoint_dir or os.path.join(
            cfg.work_dir, "checkpoints", cfg.hotkey)
        store = CheckpointStore(ckpt_dir)
    if c.lora_cfg is not None:
        # config-4 mode: adapter-only training, adapter-tree artifacts.
        # Reuse the composed engine's optimizer so --learning-rate and
        # --grad-clip apply to adapters too; the mesh shards the frozen
        # base (fsdp/tp) while adapters replicate.
        from distributedtraining_tpu.engine import LoRAEngine, LoRAMinerLoop
        if cfg.keep_optimizer_on_pull:
            # adapters are re-initialized on every base change (they are
            # defined RELATIVE to the base), so there is no state to
            # carry — refuse silently doing nothing
            logging.warning(
                "--keep-optimizer-on-pull has no effect for LoRA miners "
                "(adapters and their optimizer reset with the base); "
                "ignoring")
        engine = LoRAEngine(c.model, c.lora_cfg, optimizer=c.engine.tx,
                            mesh=c.engine.mesh, seq_len=cfg.seq_len,
                            accum_steps=cfg.accum_steps,
                            fused_loss=cfg.fused_loss)
        loop = LoRAMinerLoop(engine, c.transport, cfg.hotkey,
                             send_interval=cfg.send_interval,
                             check_update_interval=cfg.check_update_interval,
                             metrics=c.metrics, log_every=cfg.log_every,
                             checkpoint_store=store,
                             checkpoint_interval=cfg.checkpoint_interval,
                             push_async=cfg.push_async,
                             push_queue_depth=cfg.push_queue_depth,
                             trace=trace, anomaly=anomaly,
                             base_fetcher=base_fetcher,
                             **_guard_kwargs(cfg, c))
    else:
        loop = MinerLoop(c.engine, c.transport, cfg.hotkey,
                         send_interval=cfg.send_interval,
                         check_update_interval=cfg.check_update_interval,
                         metrics=c.metrics, log_every=cfg.log_every,
                         delta_dtype=(None if cfg.delta_dtype == "float32"
                                      else cfg.delta_dtype),
                         delta_density=cfg.delta_density,
                         wire_v2=cfg.wire_v2,
                         wire_density=cfg.wire_density,
                         wire_quant=cfg.wire_quant,
                         keep_optimizer_on_pull=cfg.keep_optimizer_on_pull,
                         checkpoint_store=store,
                         checkpoint_interval=cfg.checkpoint_interval,
                         push_async=cfg.push_async,
                         push_queue_depth=cfg.push_queue_depth,
                         trace=trace, anomaly=anomaly,
                         base_fetcher=base_fetcher,
                         **_guard_kwargs(cfg, c))
    # fleet health plane: heartbeat publisher (loop-managed: starts with
    # training, final beat + close in flush()) and the --obs-port
    # exporter. Vitals read the loop's live report.
    from distributedtraining_tpu.engine.health import report_vitals
    plane = build_health_plane(
        cfg, c, start_heartbeat=False,
        vitals=report_vitals(loop.report,
                             base_revision=lambda: loop._base_revision),
        # base-distribution extras (base_fetch_bytes / mirror hit rate)
        # ride the heartbeat so fleet_report's base_b/mirror_hit columns
        # show the delta-pull economy per node
        collect=(base_fetcher.heartbeat_fields
                 if base_fetcher is not None else None))
    loop.heartbeat = plane.heartbeat

    def _bootstrap():
        # bounded retry on TRANSPORT errors only: a preemption restart is
        # exactly when the backend may still be partitioned (the outage
        # that killed us), and an instant crash here burns supervise.sh's
        # crash-loop budget against a fault a short backoff rides out.
        # Programming errors re-raise immediately. bootstrap is
        # idempotent (restore + fetch, no partial publishes), so a retry
        # re-runs it whole.
        import time as _time
        for attempt in range(3):
            try:
                return loop.bootstrap(params=c.initial_params)
            except OSError:
                if attempt == 2:
                    raise
                delay = 2.0 * (attempt + 1)
                logging.warning("miner bootstrap: transport unreachable "
                                "(attempt %d/3); retrying in %.0fs",
                                attempt + 1, delay, exc_info=True)
                _time.sleep(delay)

    try:
        _bootstrap()
        report = loop.run(c.train_batches(), max_steps=cfg.max_steps)
        loop.flush()  # final delta + checkpoint so short runs still publish
    except KeyboardInterrupt:
        report = loop.report
        loop.flush()
    finally:
        if store is not None:
            store.close()
        plane.close()   # exporter socket + heartbeat timer (idempotent)
        # crash bundle first (an exceptional exit freezes the ring here,
        # while the transport is still wired), then drop the process-wide
        # observability state: sequential in-process role runs
        # (scripts/e2e_round.py, tests) must not bleed this role's
        # recorder/registry/sink into the next
        flight.shutdown()
        from distributedtraining_tpu.utils import devprof, obs
        obs.reset()
        devprof.reset()
    logging.info("miner done: steps=%d pushes=%d (failed=%d superseded=%d) "
                 "base_pulls=%d loss=%.4f",
                 report.steps, report.pushes, report.pushes_failed,
                 report.pushes_superseded, report.base_pulls,
                 report.last_loss)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
