"""bench.py's helpers at tiny scale.

The driver runs bench.py unattended at round end on hardware this CI
never sees; a broken helper means a silently lost measurement round, so
the burst/A-B/merge plumbing is pinned here on the CPU backend with a
tiny model (the numbers are meaningless off-TPU — only the mechanics and
contracts are under test).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
from distributedtraining_tpu.models import gpt2


@pytest.fixture()
def tiny(monkeypatch):
    monkeypatch.setattr(bench, "BATCH", 2)
    monkeypatch.setattr(bench, "SEQ", 32)
    monkeypatch.setattr(bench, "WARMUP", 1)
    monkeypatch.setattr(bench, "MERGE_M", 3)
    monkeypatch.setattr(bench, "MERGE_ITERS", 2)
    model, cfg = gpt2.make_model(gpt2.GPT2Config(
        n_layer=2, n_embd=64, n_head=2, vocab_size=256, n_positions=32))
    return model, cfg


def test_step_burst_contract(tiny):
    model, cfg = tiny
    burst = bench._step_burst(model, cfg)
    a = burst(2)
    b = burst(2)
    assert a > 0 and b > 0
    # state persists across bursts (the warm burst really warms)
    burst16 = bench._step_burst(model, cfg, batch_size=4)
    assert burst16(1) > 0


def test_ab_speedup_and_pair_stats(tiny):
    model, cfg = tiny
    base = bench._step_burst(model, cfg)
    base(1)
    tps, ratio = bench._ab_speedup(base, model, cfg, fused_b="scan")
    assert tps > 0 and ratio > 0
    assert bench._pair_stats([(100.0, 50.0), (200.0, 100.0)]) == (75.0, 0.5)


def test_loop_vs_engine_reports_both_keys(tiny):
    model, cfg = tiny
    base = bench._step_burst(model, cfg)
    base(1)
    out = bench._time_loop_vs_engine(model, cfg, base, trials=1, iters=2)
    assert set(out) == {"loop_tokens_per_sec", "loop_vs_engine"}
    assert out["loop_tokens_per_sec"] > 0


def test_time_merge_reports_all_spellings(tiny):
    model, cfg = tiny
    out = bench._time_merge(model)
    for key in ("merge_wallclock_s", "merge_gbps", "merge_flat_wallclock_s",
                "merge_bf16_wallclock_s", "merge_bf16_speedup",
                "sparse8_encode_s", "sparse8_decode_s",
                "sparse8_artifact_bytes", "sparse8_vs_f32_bytes"):
        assert key in out, out
    assert out["merge_m"] == 3
    assert out["sparse8_vs_f32_bytes"] > 4  # beats even dense int8's 4x


def test_time_validator_round_ab(tiny):
    """The cohort-vs-sequential validator A/B (ISSUE 1 acceptance): the
    dispatch-count reduction is exact and >= 2x at K=4, the cohort path's
    wall-clock beats the sequential spelling even on CPU (the contrast is
    dispatch/placement overhead, present on every backend), and the two
    paths agree numerically."""
    model, cfg = tiny
    out = bench._time_validator_round(model, cfg, k=4, n_batches=3,
                                      trials=2)
    for key in ("validator_round_sec", "validator_seq_round_sec",
                "candidates_per_sec", "validator_round_speedup"):
        assert key in out and out[key] > 0, out
    assert out["validator_seq_dispatches"] == 12
    assert out["validator_cohort_dispatches"] == 3
    assert out["validator_dispatch_ratio"] >= 2.0
    assert out["validator_round_speedup"] > 1.0, out
    assert out["validator_parity_max_abs_err"] < 1e-4


def test_time_push_overlap_ab():
    """The async-vs-sync miner publish A/B (ISSUE 2 acceptance): with a
    simulated-latency transport the pipeline hides the training-thread
    stall (>= 80% at the bench's default 150 ms; the floor here is looser
    because CI boxes run loaded) and the published artifacts are
    byte-identical. Cheap spelling: fewer steps, still latency-bound."""
    out = bench._time_push_overlap(latency_s=0.1, steps=10)
    for key in ("push_stall_ms", "push_stall_async_ms",
                "push_overlap_speedup", "push_stall_removed"):
        assert key in out and out[key] is not None, out
    assert out["push_parity"] is True, out
    assert out["push_overlap_speedup"] > 1.2, out
    assert out["push_stall_removed"] >= 0.5, out
    # the stall the sync path pays per push is at least the injected
    # transport latency (upload + rider)
    assert out["push_stall_ms"] >= 80.0, out


def test_time_gather_deltas_ab():
    """The pooled+cached averager ingest A/B (ISSUE 4 acceptance): on a
    cold round with >= 4 miners the concurrent pool has its fetches in
    flight TOGETHER where the serial gather has one at a time, a warm
    round with unchanged revisions downloads ZERO artifact bytes, and
    accepted deltas are byte-identical in both modes. The overlap is read
    from the start and end stamps every fetch records, not from a race of
    the two wall clocks: under six loaded test workers the pooled round's
    wall time has lost a 0.5x race it wins on an idle box (the recorded
    ratio stays in the output; `python bench.py` reports it)."""
    out = bench._time_gather_deltas(n_miners=4, latency_s=0.03, trials=2)
    for key in ("averager_ingest_ms", "averager_ingest_serial_ms",
                "averager_ingest_warm_ms", "ingest_speedup_cold",
                "ingest_speedup_warm"):
        assert key in out and out[key] > 0, out
    assert out["ingest_parity"] is True, out
    assert out["ingest_warm_downloads"] == 0, out
    assert out["ingest_inflight_serial"] == 1, out
    assert out["ingest_inflight_cold"] >= 2, out


def test_time_heartbeat_overhead_ab():
    """The fleet-health-plane A/B (ISSUE 5 acceptance): the production
    MinerLoop with a HeartbeatPublisher at an aggressive cadence vs
    without. The plane must actually run (beats sent) and its measured
    cost must stay under the 2% acceptance floor — loosened to 10% here
    because short CI bursts on loaded boxes are noise-dominated. Host contention
    only ever INFLATES the measured fraction, so on a miss the burst is
    re-measured (min-of-attempts is the tighter estimator on a shared
    rig — a single in-suite burst has measured 0.02–0.13 either way)."""
    for attempt in range(3):
        out = bench._time_heartbeat_overhead(steps=30, trials=1)
        for key in ("heartbeat_off_s", "heartbeat_on_s",
                    "heartbeat_overhead_frac"):
            assert key in out and out[key] is not None, out
        assert out["heartbeat_beats_sent"] >= 2, out
        if out["heartbeat_overhead_frac"] < 0.10:
            break
    assert out["heartbeat_overhead_frac"] < 0.10, out


def test_time_remediation_overhead_ab():
    """The remediation-layer A/B (ISSUE 6 acceptance): validator rounds
    with the fleet plane vs fleet plane + RemediationEngine. The layer
    must actually run both sides' rounds and its measured cost must stay
    small — loosened to 15% here because short CI bursts on loaded boxes
    are noise-dominated (the acceptance floor is < 2%). The rounds are ~20 ms, so
    scheduler jitter alone can blow the cap; noise only inflates the
    fraction, so a miss re-measures (min-of-attempts)."""
    for attempt in range(3):
        out = bench._time_remediation_overhead(miners=4, rounds=2, trials=1)
        for key in ("remediation_off_s", "remediation_on_s",
                    "remediation_overhead_frac"):
            assert key in out and out[key] is not None, out
        assert out["remediation_off_s"] > 0 and out["remediation_on_s"] > 0
        if out["remediation_overhead_frac"] < 0.15:
            break
    assert out["remediation_overhead_frac"] < 0.15, out


def test_time_flight_overhead_ab():
    """The flight-recorder A/B (ISSUE 10 tentpole): the production
    MinerLoop with the obs layer on both sides, contrast = the
    postmortem event ring (utils/flight.py). The ring must actually
    record (span closes, publish outcomes, registry snapshots) and
    freeze, and its measured cost must stay small — loosened to 25%
    here because short CI bursts on loaded boxes are noise-dominated
    (the same 30-step burst has measured 3%–18% across runs on the
    shared 1-core rig; the acceptance floor is < 2%). Noise only inflates
    the fraction; a miss re-measures (min-of-attempts)."""
    for attempt in range(3):
        out = bench._time_flight_overhead(steps=30, trials=1)
        for key in ("flight_off_s", "flight_on_s", "flight_overhead_frac"):
            assert key in out and out[key] is not None, out
        assert out["flight_events_recorded"] > 0, out
        assert out["flight_bundle_events"] > 0, out
        if out["flight_overhead_frac"] < 0.25:
            break
    assert out["flight_overhead_frac"] < 0.25, out


def test_time_lineage_overhead_ab():
    """The lineage-plane A/B (ISSUE 13 tentpole): production averager
    rounds with the provenance record + drift detector per publish vs
    without (engine/lineage.py). The plane must freeze a record for every
    merged round of its side (the warm round and the two timed ones) and
    both sides must have run. What the plane costs is
    `lineage_overhead_frac` of a `python bench.py` record (acceptance
    floor < 2%): at 2 rounds x ~70 ms under six loaded test workers the
    ratio is the box's load, so no threshold on it is asserted here."""
    out = bench._time_lineage_overhead(miners=3, rounds=2, trials=1)
    for key in ("lineage_off_s", "lineage_on_s", "lineage_overhead_frac"):
        assert key in out and out[key] is not None, out
    assert out["lineage_records_published"] >= 3, out
    assert out["lineage_off_s"] > 0 and out["lineage_on_s"] > 0
    assert 0.0 <= out["lineage_overhead_frac"] < float("inf"), out


def test_time_devprof_overhead_ab():
    """The device-observatory A/B (ISSUE 12 tentpole): the production
    MinerLoop with the obs layer on both sides, contrast =
    utils/devprof.py (per-program cost probes, blocking exec timing on
    CPU, flush-time snapshot mirror). The observatory must attribute
    EVERY dispatch of the train step (the two warm steps and the thirty
    timed ones) and its FLOPs where the backend has a cost model, and
    both sides must have run. What it costs is `devprof_overhead_frac`
    of a `python bench.py` record (acceptance floor < 2%); a 30-step CPU
    burst under six loaded test workers measures the load, so no
    threshold on the ratio is asserted here."""
    from distributedtraining_tpu.utils import devprof

    out = bench._time_devprof_overhead(steps=30, trials=1)
    for key in ("devprof_off_s", "devprof_on_s", "devprof_overhead_frac"):
        assert key in out and out[key] is not None, out
    assert out["devprof_off_s"] > 0 and out["devprof_on_s"] > 0, out
    assert 0.0 <= out["devprof_overhead_frac"] < float("inf"), out
    assert out["devprof_programs"] >= 1, out
    assert out["devprof_train_step_calls"] == 32, out
    assert "prog_achieved" in out  # empty on CPU (unknown roofline)
    if devprof.cost_analysis_available():
        assert out["devprof_train_step_flops"] > 0, out


def test_bench_env_forensics():
    """Every bench record embeds device kind/counts, platform and
    jax/jaxlib versions — a number cannot be compared with the next one
    without the device that produced it."""
    env = bench._bench_env()
    for key in ("jax_version", "jaxlib_version", "platform",
                "device_kind", "device_count", "host_count"):
        assert key in env, env
    assert env["platform"] == "cpu"
    assert env["device_count"] >= 1 and env["host_count"] >= 1
    assert env["jax_version"] == jax.__version__


def test_gate_baseline_utilization(tmp_path):
    """--baseline gating: the per-program achieved-fraction regresses ->
    flagged even when the headline holds."""
    base = {"value": 100.0, "prog_achieved": {"train.step": 0.40,
                                              "serve.decode": 0.20}}
    bp = tmp_path / "base.json"
    bp.write_text(json.dumps(base))
    # headline holds, one program's utilization collapses
    rec = {"value": 101.0, "prog_achieved": {"train.step": 0.10,
                                             "serve.decode": 0.19}}
    regs = bench._gate_baseline(rec, str(bp))
    assert len(regs) == 1 and "train.step" in regs[0]
    # headline regression gates too
    regs = bench._gate_baseline(
        {"value": 50.0, "prog_achieved": base["prog_achieved"]}, str(bp))
    assert any("headline" in r for r in regs)
    # within-tolerance run passes; missing program is flagged
    assert bench._gate_baseline(dict(base), str(bp)) == []
    regs = bench._gate_baseline({"value": 100.0, "prog_achieved": {}},
                                str(bp))
    assert len(regs) == 2
    # unreadable baseline degrades to no gate
    assert bench._gate_baseline(dict(base), str(tmp_path / "nope")) == []


def test_peak_flops_ladder():
    """Peaks are keyed by the device kind JAX reports, nothing else."""
    assert bench._peak_flops("TPU v5 lite") == 197e12
    assert bench._peak_flops("TPU v6 lite") == 918e12
    assert bench._peak_flops("TPU v4") == 275e12
    assert bench._peak_flops("cpu") is None   # mfu omitted


def test_bench_refuses_without_tpu(capsys):
    """No chip, no number: the first backend touch exits non-zero with
    the device named, and nothing is measured on another platform."""
    with pytest.raises(SystemExit) as exc:
        bench._require_backend()
    assert exc.value.code == 3
    err = capsys.readouterr().err
    assert "needs a TPU" in err and "'cpu'" in err
    with pytest.raises(SystemExit) as exc:
        bench.main([])
    assert exc.value.code == 3
    assert capsys.readouterr().out == ""      # no record line at all


def test_bench_exits_nonzero_on_sub_bench_error(monkeypatch, capsys):
    """A run in which a sub-bench raised prints its record and fails."""
    monkeypatch.setattr(bench, "_require_backend", lambda: None)
    monkeypatch.setattr(bench, "_step_burst",
                        lambda *a, **kw: (lambda iters: 1000.0))

    def boom(*a, **kw):
        raise RuntimeError("sub-bench blew up")

    for name in dir(bench):
        if name.startswith("_time_") or name in ("_ab_speedup",
                                                 "_param_count"):
            monkeypatch.setattr(bench, name, boom)
    with pytest.raises(SystemExit) as exc:
        bench.main([])
    assert exc.value.code == 1
    out = capsys.readouterr()
    rec = json.loads(out.out.strip().splitlines()[-1])
    assert rec["value"] == 1000.0 and rec["platform"] == "cpu"
    assert "sub-bench blew up" in rec["merge_error"]
    assert "FAILED merge_error" in out.err
