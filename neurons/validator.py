"""Validator entry point: score every miner's delta, emit chain weights.

Rebuild of the reference validator (neurons/validator.py:26-115 →
ModelValidator/DeltaValidator, hivetrain/validation_logic.py). Run offline:

    python neurons/validator.py --backend local --work-dir /tmp/run \
        --model tiny --dataset synthetic --hotkey hotkey_91 --rounds 1
"""

from __future__ import annotations

import logging
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from distributedtraining_tpu.config import RunConfig   # noqa: E402
from distributedtraining_tpu.engine import Validator   # noqa: E402
from neurons.common import (build, build_base_fetcher,  # noqa: E402
                            build_health_plane)


def main(argv=None) -> int:
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    cfg = RunConfig.from_args("validator", argv)
    c = build(cfg)
    # crash-forensics triggers (utils/flight.py, see neurons/miner.py)
    from distributedtraining_tpu.utils import flight
    flight.install_crash_hooks()
    base_fetcher = build_base_fetcher(cfg, c)
    validator = Validator(c.engine, c.transport, c.chain,
                          eval_batches=c.eval_batches(),
                          metric=cfg.score_metric,
                          max_delta_abs=cfg.max_delta_abs,
                          metrics=c.metrics, lora_cfg=c.lora_cfg,
                          accept_quant=cfg.accept_quant,
                          accept_wire_v2=cfg.accept_wire_v2,
                          stale_deltas=cfg.stale_deltas or "accept",
                          cohort_size=cfg.val_cohort,
                          pipeline_depth=cfg.val_pipeline_depth,
                          ingest_workers=cfg.ingest_workers,
                          ingest_cache_mb=cfg.ingest_cache_mb,
                          base_fetcher=base_fetcher)
    # the reference gates weight-setting to staked validators
    # (btt_connector.py:358-385); refuse up front instead of silently
    # burning eval compute on scores no one will ever see. On a pod the
    # COORDINATOR's verdict is broadcast: per-process chain syncs could
    # disagree at a stake boundary, and one process exiting while the rest
    # proceed would strand them at their first collective.
    import jax
    permitted = validator.has_vpermit() if jax.process_count() <= 1 else None
    if permitted is None:
        import numpy as np
        from jax.experimental import multihost_utils as mhu

        from distributedtraining_tpu.parallel import multihost
        local = validator.has_vpermit() if multihost.is_coordinator() else False
        permitted = bool(mhu.broadcast_one_to_all(
            np.asarray(local, np.int32)))
    if not permitted:
        if not cfg.allow_no_vpermit:
            raise SystemExit(
                f"hotkey {c.chain.my_hotkey} holds no validator permit "
                f"(stake < {cfg.vpermit_stake_limit}); pass "
                f"--allow-no-vpermit to run anyway without emitting weights")
        logging.warning("running WITHOUT a validator permit: weights will "
                        "not be emitted")
    # fleet health plane (after the permit gate, so a refused boot never
    # leaves an exporter socket or heartbeat timer behind): the validator
    # heartbeats AND monitors — its ledger carries the per-miner score
    # history alongside the staging outcomes; SLO breaches arm the
    # AnomalyMonitor one-shot (detection + counters).
    from distributedtraining_tpu.engine.health import Vitals
    from distributedtraining_tpu.utils.obs import AnomalyMonitor
    plane = build_health_plane(cfg, c, monitor=True,
                               anomaly=AnomalyMonitor(),
                               start_heartbeat=False,
                               collect=(base_fetcher.heartbeat_fields
                                        if base_fetcher is not None
                                        else None))
    validator.fleet = plane.fleet   # before the first round's lazy _ingest
    validator.remediation = plane.remediation  # and the lazy evaluator
    if plane.heartbeat is not None:
        plane.heartbeat.vitals = Vitals(
            steps=lambda: validator._round,
            loss=lambda: validator.base_loss,
            counters=lambda: {"rounds": validator._round},
            base_revision=lambda: validator._base_revision)
        plane.heartbeat.start()
    validator.bootstrap(params=c.initial_params)
    try:
        ok = validator.run_periodic(interval=cfg.validation_interval,
                                    rounds=cfg.rounds)
    except KeyboardInterrupt:
        logging.info("validator interrupted; exiting")
        return 0
    finally:
        plane.close()       # exporter socket + heartbeat timer + pool
        validator.close()   # drain the ingest pool's worker threads
        # see neurons/miner.py: crash bundle, then global obs state reset
        flight.shutdown()
        from distributedtraining_tpu.utils import devprof, obs
        obs.reset()
        devprof.reset()
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
