"""Server entry point: serve generation over the live base model.

The fourth role of the fleet (ROADMAP item 3): a continuous-batching
generation engine (engine/serve.py) that subscribes to the averager's
base-model revisions through the transport and hot-swaps weights between
decode steps — the federated loop's output, deployed continuously. Run
offline against a local round:

    python neurons/server.py --backend local --work-dir /tmp/run \
        --model tiny --dataset synthetic --serve-port 8900

POST token ids at it:

    curl -d '{"tokens": [1, 2, 3], "max_new_tokens": 16}' \
        http://127.0.0.1:8900/generate

Heartbeats carry the served base revision and tokens/sec, so
scripts/fleet_report.py shows train -> merge -> serve lag end to end;
``--obs-port`` exports the ``serve.*`` registry as ``dt_serve_*``.
"""

from __future__ import annotations

import logging
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from distributedtraining_tpu.config import RunConfig           # noqa: E402
from distributedtraining_tpu.engine.serve import (             # noqa: E402
    BaseRevisionWatcher, GenerationEngine, ServeHTTPFrontend, ServeLoop,
    host_param_template)
from neurons.common import (build, build_base_fetcher,         # noqa: E402
                            build_health_plane)

logger = logging.getLogger(__name__)


def _await_base(cfg: RunConfig, c, watcher: BaseRevisionWatcher):
    """Boot weights: the published base when one exists (polling until
    it does), else ``--init-from`` pretrained weights (serving can come
    up before the averager's first publish)."""
    deadline = (time.monotonic() + cfg.rounds * cfg.swap_poll
                if cfg.rounds else None)
    while True:
        if watcher.poll_once():
            staged = watcher.take_pending()
            if staged is not None:
                return staged[1], staged[0]
        params = c.initial_params()
        if params is not None:
            logger.info("no published base yet; serving --init-from "
                        "weights until one lands")
            return params, None
        if deadline is not None and time.monotonic() > deadline:
            raise SystemExit(
                "no base model appeared within the bounded wait "
                "(--rounds x --swap-poll); is the averager running?")
        logger.info("waiting for a published base model "
                    "(poll every %.1fs)...", cfg.swap_poll)
        time.sleep(cfg.swap_poll)


def _build_drafter(cfg: RunConfig, c):
    """Speculative drafter (``--speculative``): a :class:`DraftEngine`
    around the small fleet-trained base named by ``--draft-repo``
    ("preset@work_dir" — a second transport watches that deployment's
    averaged revisions and feeds the drafter's hot-swap lane). Empty
    ``--draft-repo`` self-drafts from the serving transport (smoke
    only: a draft the target's own size saves nothing). Every failure
    degrades to plain decode — a misconfigured drafter must never keep
    the server from serving."""
    if not cfg.serve_speculative:
        return None
    from distributedtraining_tpu.engine import speculative as _spec
    from distributedtraining_tpu.models import family_of
    try:
        if cfg.serve_draft_repo:
            preset, _, work_dir = cfg.serve_draft_repo.partition("@")
            family = family_of(preset)
            if preset not in family.PRESETS:
                raise ValueError(f"unknown draft preset {preset!r}")
            dmodel, _ = family.make_model(preset)
            from distributedtraining_tpu.transport import LocalFSTransport
            tr = LocalFSTransport(os.path.join(work_dir, "artifacts"))
        else:
            dmodel, tr = c.model, c.transport
        reason = _spec.compat_reason(dmodel, c.model_cfg)
        if reason:
            logger.warning("drafter incompatible (%s); serving plain",
                           reason)
            return None
        dwatcher = BaseRevisionWatcher(
            tr, lambda: host_param_template(dmodel),
            poll_s=max(cfg.swap_poll, 0.1))
        draft = _spec.DraftEngine(
            dmodel, max_slots=cfg.serve_slots,
            page_size=cfg.serve_page_size, watcher=dwatcher)
        # synchronous first pull so a draft base that is already
        # published speculates from step one; otherwise the watcher
        # thread installs it whenever it lands (plain decode until then)
        if dwatcher.poll_once():
            staged = dwatcher.take_pending()
            if staged is not None:
                draft.install_params(staged[1], revision=staged[0])
        dwatcher.start()
        logger.info("speculative decoding on: draft=%s k=%d ready=%s",
                    cfg.serve_draft_repo or "<self>", cfg.serve_draft_k,
                    draft.ready)
        return draft
    except Exception:
        logger.exception("drafter construction failed; serving plain")
        return None


def main(argv=None) -> int:
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    cfg = RunConfig.from_args("server", argv)
    c = build(cfg)
    # crash-forensics triggers (utils/flight.py, see neurons/miner.py)
    from distributedtraining_tpu.utils import flight
    flight.install_crash_hooks()

    # content-addressed base pulls (engine/basedist.py): hot-swap
    # fetches become delta-pulls of only the layers the merge moved
    base_fetcher = build_base_fetcher(cfg, c)
    watcher = BaseRevisionWatcher(
        c.transport, lambda: host_param_template(c.model),
        poll_s=max(cfg.swap_poll, 0.1), fetcher=base_fetcher)
    # SLO burn-rate alerting over the request-trace stream
    # (engine/health.py): every finished/shed request the TraceBook
    # records feeds the monitor; multi-window rules fire the standard
    # breach escalation and export as dt_slo_burn{slo,window}
    from distributedtraining_tpu.engine import health as _health
    burn = (_health.BurnRateMonitor(metrics=c.metrics)
            if cfg.serve_trace else None)
    _health.attach_burn(burn)
    # disaggregated worker classes (engine/kv_transfer.py): a prefill
    # worker exports KV pages over the SERVING transport (the same
    # store base revisions ride), a decode worker adopts them; unified
    # touches neither
    from distributedtraining_tpu.engine import kv_transfer as _kvt
    kv_exporter = (_kvt.KVExporter(c.transport)
                   if cfg.serve_phase == "prefill" else None)
    kv_adopter = (_kvt.KVAdopter(c.transport)
                  if cfg.serve_phase == "decode" else None)
    engine = GenerationEngine(
        c.model, max_slots=cfg.serve_slots, page_size=cfg.serve_page_size,
        pool_pages=cfg.serve_kv_pages, max_seq_len=cfg.serve_max_seq,
        max_new_tokens=cfg.serve_max_new,
        eos_id=getattr(c.tokenizer, "eos_id", None),
        swap_policy=cfg.swap_policy, watcher=watcher,
        max_queue=cfg.serve_max_queue,
        prefix_cache=cfg.serve_prefix_cache,
        draft=(None if cfg.serve_phase == "prefill"
               else _build_drafter(cfg, c)),
        draft_k=cfg.serve_draft_k,
        trace=cfg.serve_trace,
        trace_exemplars=cfg.serve_trace_exemplars,
        trace_window_s=cfg.serve_trace_window or 30.0,
        burn=burn, phase=cfg.serve_phase,
        kv_exporter=kv_exporter, kv_adopter=kv_adopter)
    # the engine holds the watcher now: what the boot poll stages is the
    # serving tree already (engine/serve_weights.py), made leaf by leaf
    # from the host base, and the float32 base never lies on the device
    try:
        params, revision = _await_base(cfg, c, watcher)
    except BaseException:
        engine.close()
        raise
    if base_fetcher is not None and revision is None:
        # --init-from boot: seed the shard store from the weights we
        # serve (the host's float32 base, as published bases are), so
        # the FIRST published base pulls only what differs
        base_fetcher.seed(params)
    engine.install_params(params, revision=revision)
    del params   # the engine's to drop at the first swap
    watcher.start()

    # health plane: the server heartbeats its SERVED revision (the
    # "base_revision" field every fleet consumer already reads) plus
    # tokens/sec and queue depth as numeric extras — fleet_report's
    # served_rev/tok_s columns come from here
    from distributedtraining_tpu.engine.health import Vitals
    from distributedtraining_tpu.utils import obs as _obs

    def _serve_counters():
        out = {"tokens_per_sec": engine.tokens_per_sec,
               "queue_depth": float(engine.queue_depth),
               "tokens": float(engine.tokens_emitted),
               "shed": float(engine.shed_count)}
        # prefix-cache effectiveness rides the heartbeat only once the
        # cache has seen traffic — fleet_report renders "-" otherwise
        if engine.prefix_hits + engine.prefix_misses > 0:
            out["prefix_hit_rate"] = engine.prefix_hit_rate
        # speculative acceptance rides the heartbeat once drafting has
        # actually verified tokens — fleet_report's acc_rate column
        if engine.speculative and engine.spec_rounds > 0:
            out["spec_accept_rate"] = engine.spec_accept_rate
        # request-level latency percentiles (engine/serve.py observes
        # serve.ttft_ms / serve.tpot_ms per token): ride the heartbeat
        # as numeric extras so fleet_report's ttft95/tpot95 columns show
        # caller-experienced latency next to tokens/sec. names() guards
        # the read — histogram() would CREATE an empty series and skew
        # the registry digest on idle servers.
        names = _obs.registry().names()
        for metric, field in (("serve.ttft_ms", "ttft_ms_p95"),
                              ("serve.tpot_ms", "tpot_ms_p95"),
                              ("serve.queue_age_ms", "q_age_ms_p95")):
            if metric in names:
                h = _obs.registry().histogram(metric)
                if h.count:
                    out[field] = h.percentiles((95.0,))["p95"]
        # worst fast-window burn rate across the serving SLOs —
        # fleet_report's slo_burn column (0.0 = comfortably on budget)
        if burn is not None:
            out["slo_burn"] = burn.max_burn()
        # disaggregated transfer volume — fleet_report's phase column
        # reads the string field; the kv counters ride only on workers
        # that actually export/adopt so unified heartbeats stay lean
        if engine.phase != "unified":
            out["phase"] = engine.phase
            out["kv_exported"] = float(engine.kv_exported)
            out["kv_adopted"] = float(engine.kv_adopted)
        return out

    vitals = Vitals(
        steps=lambda: engine.steps,
        counters=_serve_counters,
        base_revision=lambda: engine.revision)
    plane = build_health_plane(
        cfg, c, vitals=vitals,
        collect=(base_fetcher.heartbeat_fields
                 if base_fetcher is not None else None))

    frontend = None
    if cfg.serve_port:
        frontend = ServeHTTPFrontend(engine, cfg.serve_port,
                                     tokenizer=c.tokenizer)
        frontend.start()
    loop = ServeLoop(engine).start()
    from distributedtraining_tpu.utils import devprof, obs
    try:
        idle_since = None
        last_flush = time.monotonic()
        while True:
            time.sleep(0.25)
            if c.metrics is not None and \
                    time.monotonic() - last_flush >= 15.0:
                # registry snapshots (serve.* timings) at a steady
                # cadence, so fleet_report's registry[server] line and
                # offline joins see the serving numbers
                obs.flush(step=engine.steps)
                if burn is not None:
                    # burn-rate rules re-check on the same cadence; any
                    # firing walks the standard breach escalation
                    burn.evaluate()
                last_flush = time.monotonic()
            if cfg.max_steps is None:
                continue   # unbounded: serve until interrupted
            if engine.steps >= cfg.max_steps:
                logger.info("reached --max-steps %d decode steps",
                            cfg.max_steps)
                break
            # bounded runs (tests, smoke) must terminate without traffic
            # too: a drained queue that stays idle ends the run
            if engine.idle:
                idle_since = idle_since or time.monotonic()
                if time.monotonic() - idle_since > 2 * max(cfg.swap_poll,
                                                           1.0):
                    logger.info("bounded run idle; exiting at %d steps",
                                engine.steps)
                    break
            else:
                idle_since = None
    except KeyboardInterrupt:
        pass
    finally:
        if frontend is not None:
            frontend.close()
        loop.close()
        plane.close()
        engine.close()
        _health.attach_burn(None)
        if c.metrics is not None:
            obs.flush(step=engine.steps)
        # crash bundle (exceptional exits), then global obs state reset
        flight.shutdown()
        obs.reset()
        devprof.reset()
    logger.info("server done: steps=%d tokens=%d revision=%s",
                engine.steps, engine.tokens_emitted, engine.revision)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
