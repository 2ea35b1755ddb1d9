"""Flight recorder & postmortem plane (utils/flight.py).

Covers: the bounded event ring + producer/consumer schema lint, content-
addressed bundle freeze/publish/fetch over the reserved ``__pm__``
transport namespace, the obs span/flush/anomaly hooks, crash hooks,
publish-outcome events (including torn wire-v2 shard sets), lease/
remediation/SLO attachment of bundle references to the contribution
ledger, the debug endpoints, JSONL retention sweep, and the acceptance
round: a ChaosTransport round that kills a miner mid-publish must leave
a Transport-fetchable ``__pm__`` bundle whose reconstructed timeline
(scripts/postmortem.py) names the torn publish and the SLO rule that
fired, joined on cid across >= 2 roles.
"""

import json
import os
import sys
import threading
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from distributedtraining_tpu import delta as dl
from distributedtraining_tpu.engine.health import (FleetMonitor, SLORule,
                                                   build_heartbeat)
from distributedtraining_tpu.engine.publish import DeltaPublisher
from distributedtraining_tpu.engine.remediate import (LeaseManager,
                                                      RemediationEngine,
                                                      RemediationPolicy)
from distributedtraining_tpu.transport import base as tbase
from distributedtraining_tpu.transport.chaos import (ChaosEvent,
                                                     ChaosTransport)
from distributedtraining_tpu.transport.localfs import LocalFSTransport
from distributedtraining_tpu.transport.memory import InMemoryTransport
from distributedtraining_tpu.transport.retry import RetryPolicy
from distributedtraining_tpu.utils import flight, obs
from distributedtraining_tpu.utils.metrics import InMemorySink, JSONLSink

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts"))
import postmortem  # noqa: E402

FAST_RETRY = RetryPolicy(attempts=2, base_delay=0.0, max_delay=0.0,
                         jitter=0.0)


@pytest.fixture(autouse=True)
def _clean_state():
    obs.reset()
    flight.reset()
    yield
    flight.reset()
    obs.reset()


class _Report:
    pushes = 0
    pushes_failed = 0
    pushes_superseded = 0


def _tree(seed=0, big=(300, 40), small=(32,)):
    rs = np.random.RandomState(seed)
    return {"wte": (rs.randn(*big) * 0.01).astype(np.float32),
            "ln": {"g": (rs.randn(*small) * 0.01).astype(np.float32)}}


# ---------------------------------------------------------------------------
# Ring + schema lint
# ---------------------------------------------------------------------------

def test_ring_is_bounded_and_thread_safe():
    rec = flight.FlightRecorder("miner", "m0", capacity=16)
    threads = [threading.Thread(
        target=lambda: [rec.record("note", i=i) for i in range(100)])
        for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = rec.events()
    assert len(evs) == 16                  # ring keeps only the tail
    assert rec.recorded >= 400             # lifetime counter keeps all
    assert all(e["kind"] in ("note", "config") for e in evs)


def test_record_rejects_unknown_kind_at_producer():
    rec = flight.FlightRecorder("miner", "m0")
    with pytest.raises(ValueError, match="unknown flight event kind"):
        rec.record("not_a_kind", x=1)
    # module helper is a no-op when unconfigured, lints when configured
    flight.record("not_a_kind")            # no recorder: silent no-op
    flight.configure("miner", "m0")
    with pytest.raises(ValueError):
        flight.record("not_a_kind")


def test_parse_bundle_rejects_junk_and_unknown_event_kinds():
    assert flight.parse_bundle(b"\x00garbage") is None
    assert flight.parse_bundle(b'{"pm": "no"}') is None
    assert flight.parse_bundle(
        json.dumps({"pm": 1, "role": "miner"}).encode()) is None
    assert flight.parse_bundle(
        b"x" * (flight.PM_MAX_BYTES + 1)) is None
    good = {"pm": 1, "role": "miner", "hotkey": "m0", "t": 1.0,
            "reason": "slo_stale_node",
            "events": [{"t": 1.0, "kind": "publish", "outcome": "ok"},
                       {"t": 2.0, "kind": "EVIL", "x": 1},
                       {"kind": "publish"},          # no timestamp
                       "not-a-dict"]}
    parsed = flight.parse_bundle(json.dumps(good).encode())
    assert parsed is not None
    assert [e["kind"] for e in parsed["events"]] == ["publish"]
    assert parsed["events_rejected"] == 3


def test_sanitize_config_redacts_secret_keys():
    out = flight.sanitize_config({
        "learning_rate": 5e-4, "role": "miner", "push_async": True,
        "wallet_path": "/secrets/w.json", "wallet_hotkey": "hot",
        "hf_token": "sk-xyz", "long": "x" * 1000, "skip": None})
    assert out["learning_rate"] == pytest.approx(5e-4)
    assert out["push_async"] is True
    assert out["wallet_path"] == "<redacted>"
    assert out["wallet_hotkey"] == "<redacted>"
    assert out["hf_token"] == "<redacted>"
    assert len(out["long"]) <= 400
    assert "skip" not in out


def test_bundle_is_content_addressed():
    rec = flight.FlightRecorder("miner", "m0", clock=lambda: 123.0)
    rec.record("note", what="x")
    b1 = rec.freeze("r")
    b2 = rec.freeze("r")
    # identical content except seq -> different address; same dict ->
    # digest is a pure function of the body
    assert b1["bundle_id"] != b2["bundle_id"]
    assert flight.bundle_digest(b1) == b1["bundle_id"]
    assert flight.bundle_digest(dict(b1)) == b1["bundle_id"]


# ---------------------------------------------------------------------------
# Publish / fetch over the reserved __pm__ namespace
# ---------------------------------------------------------------------------

def test_pm_id_is_reserved():
    pid = tbase.pm_id("miner", "m0")
    assert pid == "__pm__.miner.m0"
    assert tbase.is_pm_id(pid)
    assert tbase.is_reserved_id(pid)
    assert not tbase.is_pm_id("m0")


@pytest.mark.parametrize("make", [InMemoryTransport,
                                  "localfs"])
def test_freeze_publish_fetch_roundtrip(make, tmp_path):
    transport = (LocalFSTransport(str(tmp_path / "art"))
                 if make == "localfs" else make())
    rec = flight.configure("averager", "a0", transport=transport)
    rec.record("slo", rule="stale_node", hotkey="m0", round=3)
    ref = flight.freeze_and_publish("slo_stale_node")
    assert ref is not None
    fetched = flight.fetch_bundle(transport, "averager", "a0")
    assert fetched is not None
    assert fetched["bundle_id"] == ref
    assert fetched["reason"] == "slo_stale_node"
    assert any(e["kind"] == "slo" and e.get("rule") == "stale_node"
               for e in fetched["events"])
    # registry snapshot + digest ride the bundle
    assert fetched["role"] == "averager"
    assert flight.fetch_bundle(transport, "miner", "nobody") is None


def test_publish_truncates_oversized_bundles():
    transport = InMemoryTransport()
    rec = flight.FlightRecorder("miner", "m0", capacity=4096,
                                transport=transport)
    blob = "y" * 390
    for i in range(4000):
        rec.record("note", payload=blob, i=i)
    bundle = rec.freeze("big")
    assert rec.publish(bundle)
    data = transport.fetch_delta_bytes(tbase.pm_id("miner", "m0"))
    assert data is not None and len(data) <= flight.PM_MAX_BYTES
    parsed = flight.parse_bundle(data)
    assert parsed is not None and parsed["events"]
    # newest evidence survives the truncation
    assert parsed["events"][-1]["i"] == 3999


def test_publish_failure_is_survivable_and_mirrored_to_sink():
    class Broken(InMemoryTransport):
        def publish_raw(self, miner_id, data):
            raise OSError("dark")

    sink = InMemorySink()
    obs.configure(sink, role="miner")
    rec = flight.configure("miner", "m0", transport=Broken())
    rec.record("note", what="evidence")
    ref = flight.freeze_and_publish("crash")
    assert ref is not None                  # the reference still exists
    assert rec.publish_failures == 1
    mirrored = [r for r in sink.records if "postmortem" in r]
    assert mirrored and mirrored[0]["postmortem"]["bundle_id"] == ref


# ---------------------------------------------------------------------------
# obs hooks
# ---------------------------------------------------------------------------

def test_span_hook_records_spans_and_metrics_snapshots():
    sink = InMemorySink()
    obs.configure(sink, role="miner")
    rec = flight.configure("miner", "m0")
    with obs.span("push.upload", cid="m0-000007"):
        pass
    kinds = [e["kind"] for e in rec.events()]
    assert "span" in kinds
    span_ev = next(e for e in rec.events() if e["kind"] == "span")
    assert span_ev["name"] == "push.upload"
    assert span_ev["cid"] == "m0-000007"
    # the span registered span.push.upload_ms -> vocabulary changed ->
    # a metrics snapshot event landed with the digest
    metrics_ev = [e for e in rec.events() if e["kind"] == "metrics"]
    assert metrics_ev and metrics_ev[-1]["digest"] == obs.registry_digest()
    n = len(rec.events())
    with obs.span("push.upload"):
        pass                                # same vocabulary: span only
    kinds2 = [e["kind"] for e in rec.events()[n:]]
    assert kinds2 == ["span"]


def test_span_error_flag_and_anomaly_hook():
    sink = InMemorySink()
    obs.configure(sink, role="miner")
    rec = flight.configure("miner", "m0")
    with pytest.raises(RuntimeError):
        with obs.span("val.eval"):
            raise RuntimeError("boom")
    ev = next(e for e in rec.events()
              if e["kind"] == "span" and e["name"] == "val.eval")
    assert ev["error"] is True
    mon = obs.AnomalyMonitor()
    mon.observe_loss(float("nan"))
    anomalies = [e for e in rec.events() if e["kind"] == "anomaly"]
    assert anomalies and anomalies[0]["reason"] == "loss_nonfinite"


# ---------------------------------------------------------------------------
# Crash hooks
# ---------------------------------------------------------------------------

def test_crash_hooks_install_uninstall_and_freeze():
    transport = InMemoryTransport()
    flight.configure("miner", "m0", transport=transport)
    prev_hook = sys.excepthook
    flight.install_crash_hooks()
    assert flight.hooks_installed()
    assert sys.excepthook is not prev_hook
    try:
        raise RuntimeError("synthetic crash")
    except RuntimeError:
        et, ev, tb = sys.exc_info()
    # drive the installed hook directly (raising uncaught in pytest is
    # not an option); the default chain prints to stderr, which is fine
    sys.excepthook(et, ev, tb)
    fetched = flight.fetch_bundle(transport, "miner", "m0")
    assert fetched is not None and fetched["reason"] == "crash"
    assert fetched["crash"]["type"] == "RuntimeError"
    assert "synthetic crash" in fetched["crash"]["message"]
    assert any(e["kind"] == "crash" for e in fetched["events"])
    flight.uninstall_crash_hooks()
    assert sys.excepthook is prev_hook
    assert not flight.hooks_installed()


def test_shutdown_freezes_on_exceptional_exit_only():
    transport = InMemoryTransport()
    flight.configure("server", "s0", transport=transport)
    flight.shutdown()                      # clean exit: no crash bundle
    assert flight.fetch_bundle(transport, "server", "s0") is None
    assert not flight.dirty()
    flight.configure("server", "s0", transport=transport)
    try:
        raise ValueError("died mid-round")
    except ValueError:
        flight.shutdown()                  # role-main finally semantics
    fetched = flight.fetch_bundle(transport, "server", "s0")
    assert fetched is not None and fetched["reason"] == "crash"
    assert not flight.dirty()              # shutdown also resets


# ---------------------------------------------------------------------------
# Publish-outcome events (engine/publish.py)
# ---------------------------------------------------------------------------

def test_publisher_records_ok_and_failed_outcomes():
    rec = flight.configure("miner", "m1")
    transport = InMemoryTransport()
    pub = DeltaPublisher(transport, "m1", report=_Report(),
                         publish_retry=FAST_RETRY, meta_retry=FAST_RETRY)
    assert pub.publish_now(_tree(1), None, "rev0", "m1-000001")

    class Dark(InMemoryTransport):
        def publish_delta(self, miner_id, payload):
            raise OSError("dark")

    pub2 = DeltaPublisher(Dark(), "m1", report=_Report(),
                          publish_retry=FAST_RETRY, meta_retry=FAST_RETRY)
    assert pub2.publish_now(_tree(2), None, "rev0", "m1-000002") is False
    evs = [e for e in rec.events() if e["kind"] == "publish"]
    assert [(e["outcome"], e["cid"]) for e in evs] == \
        [("ok", "m1-000001"), ("failed", "m1-000002")]


def test_torn_v2_publish_names_shard_progress():
    """A wire-v2 publish that dies between shards records a ``torn``
    event naming how far it got — the forensic needle of a mid-publish
    kill."""
    rec = flight.configure("miner", "m2")

    class DiesOnSecondShard(InMemoryTransport):
        def __init__(self):
            super().__init__()
            self.shards = 0

        def publish_shard(self, hotkey, layer_key, data):
            self.shards += 1
            if self.shards >= 2:
                raise OSError("killed mid-publish")
            self.publish_raw(tbase.shard_id(hotkey, layer_key), data)

    pub = DeltaPublisher(DiesOnSecondShard(), "m2", report=_Report(),
                         publish_retry=FAST_RETRY, meta_retry=FAST_RETRY,
                         wire_spec={"format": 2, "density": 1 / 64,
                                    "quant": "int8"})
    packed = jax.device_get(dl.pack_delta_v2(_tree(3), density=1 / 64)[0])
    assert pub.publish_now(packed, None, "rev0", "m2-000001") is False
    torn = [e for e in rec.events()
            if e["kind"] == "publish" and e["outcome"] == "torn"]
    assert len(torn) == 1
    assert torn[0]["shards_done"] == 1
    assert torn[0]["shards_total"] == 2
    assert torn[0]["manifest"] is False
    assert torn[0]["cid"] == "m2-000001"


# ---------------------------------------------------------------------------
# Lease / SLO / remediation attachment
# ---------------------------------------------------------------------------

def test_lease_transitions_recorded_and_lost_freezes():
    transport = InMemoryTransport()
    rec = flight.configure("averager", "a1", transport=transport)
    primary = LeaseManager(transport, "a1")
    assert primary.acquire()
    usurper = LeaseManager(transport, "a2")
    assert usurper.acquire()
    assert primary.renew() is False        # superseded -> lost + freeze
    actions = [(e["action"], e.get("holder"))
               for e in rec.events() if e["kind"] == "lease"]
    assert ("acquired", "a1") in actions
    assert ("lost", "a2") in actions
    fetched = flight.fetch_bundle(transport, "averager", "a1")
    assert fetched is not None and fetched["reason"] == "lease_lost"


def test_slo_breach_freezes_bundle_and_stamps_ledger():
    transport = InMemoryTransport()
    sink = InMemorySink()
    obs.configure(sink, role="averager")
    flight.configure("averager", "a0", transport=transport)
    fm = FleetMonitor(transport, metrics=sink,
                      rules=[SLORule("stale_node", "stale", threshold=1)])
    try:
        transport.publish_delta_meta(
            tbase.heartbeat_id("miner", "m0"),
            build_heartbeat("miner", "m0", 1, now=1.0, steps=1.0))
        assert fm.poll(["m0"]) == 1
        for _ in range(3):                 # rounds advance, m0 silent
            fm.poll(["m0"])
        breaches = fm.evaluate_slos()
        assert len(breaches) == 1
        ref = breaches[0]["pm_ref"]
        assert ref
        assert fm.ledger()["miner/m0"]["pm_ref"] == ref
        fetched = flight.fetch_bundle(transport, "averager", "a0")
        assert fetched is not None
        assert fetched["bundle_id"] == ref
        assert fetched["reason"] == "slo_stale_node"
        slo_evs = [e for e in fetched["events"] if e["kind"] == "slo"]
        assert slo_evs and slo_evs[-1]["hotkey"] == "m0"
        # breach record mirrored to the sink with the reference
        logged = [r for r in sink.records if "slo_breach" in r]
        assert logged and logged[0]["pm_ref"] == ref
    finally:
        fm.close()


def test_remediation_attaches_breach_bundle_to_ledger():
    transport = InMemoryTransport()
    sink = InMemorySink()
    obs.configure(sink, role="validator")
    flight.configure("validator", "v0", transport=transport)
    fm = FleetMonitor(transport, metrics=sink,
                      rules=[SLORule("stale_node", "stale", threshold=1)])
    rem = RemediationEngine(
        fm, metrics=sink,
        policy=RemediationPolicy(quarantine_rules=("stale_node",)))
    try:
        transport.publish_delta_meta(
            tbase.heartbeat_id("miner", "m0"),
            build_heartbeat("miner", "m0", 1, now=1.0, steps=1.0))
        assert fm.poll(["m0"]) == 1
        for _ in range(3):
            fm.poll(["m0"])
        breaches = fm.evaluate_slos()
        actions = rem.observe_round(breaches)
        quar = [a for a in actions if a["remediation"] == "quarantined"]
        assert quar and quar[0]["pm_ref"] == breaches[0]["pm_ref"]
        assert fm.ledger()["miner/m0"]["pm_ref"] == quar[0]["pm_ref"]
        assert fm.ledger()["miner/m0"]["quarantined"] == 1
        rem_evs = [e for e in flight.recorder().events()
                   if e["kind"] == "remediation"]
        assert rem_evs and rem_evs[0]["action"] == "quarantined"
    finally:
        fm.close()


# ---------------------------------------------------------------------------
# Debug endpoints
# ---------------------------------------------------------------------------

def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}") as resp:
        return resp.status, resp.read()


def test_debug_endpoints(tmp_path):
    from distributedtraining_tpu.utils.obs_http import ObsHTTPExporter
    sink = InMemorySink()
    obs.configure(sink, role="miner")
    transport = InMemoryTransport()
    rec = flight.configure("miner", "m0", transport=transport)
    rec.record("note", what="live")
    exp = ObsHTTPExporter(0, role="miner",
                          profile_dir=str(tmp_path / "prof"))
    port = exp.start()
    try:
        status, body = _get(port, "/debug/stacks")
        assert status == 200
        text = body.decode()
        assert "MainThread" in text or "obs-http" in text
        status, body = _get(port, "/debug/dump")
        assert status == 200
        bundle = json.loads(body)
        assert bundle["reason"] == "debug_dump"
        assert any(e["kind"] == "note" for e in bundle["events"])
        # ?publish=1 ships it through the transport too
        status, body = _get(port, "/debug/dump?publish=1")
        assert status == 200
        assert flight.fetch_bundle(transport, "miner", "m0") is not None
        status, body = _get(port, "/debug/profile?ms=40")
        assert status == 200
        info = json.loads(body)
        assert info["ms"] == pytest.approx(40.0)
        assert os.path.isdir(info["trace_dir"])
        assert flight.live_profile_sessions() == []
    finally:
        exp.close()


def test_debug_dump_without_recorder_is_503():
    from distributedtraining_tpu.utils.obs_http import ObsHTTPExporter
    exp = ObsHTTPExporter(0, role="miner")
    port = exp.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(port, "/debug/dump")
        assert e.value.code == 503
    finally:
        exp.close()


def test_capture_profile_rejects_concurrent_sessions(tmp_path):
    import distributedtraining_tpu.utils.flight as fl

    start = threading.Event()
    release = threading.Event()

    def slow_sleep(_s):
        start.set()
        release.wait(5.0)

    result = {}

    def runner():
        result["info"] = fl.capture_profile(str(tmp_path / "p1"), 5,
                                            sleep=slow_sleep)

    t = threading.Thread(target=runner)
    t.start()
    assert start.wait(5.0)
    assert len(fl.live_profile_sessions()) == 1
    with pytest.raises(RuntimeError, match="already running"):
        fl.capture_profile(str(tmp_path / "p2"), 5)
    release.set()
    t.join(5.0)
    assert result["info"]["trace_dir"].endswith("p1")
    assert fl.live_profile_sessions() == []


# ---------------------------------------------------------------------------
# JSONL retention sweep (satellite)
# ---------------------------------------------------------------------------

def test_jsonl_retention_sweep_on_open(tmp_path):
    path = str(tmp_path / "m.jsonl")
    for n in range(1, 7):                  # stale segments of an old run
        with open(f"{path}.{n}", "w") as f:
            f.write("{}\n")
    sink_obs = InMemorySink()
    obs.configure(sink_obs, role="miner")
    sink = JSONLSink(path, max_bytes=1 << 20, keep_segments=2)
    try:
        assert os.path.exists(f"{path}.6")  # lazy: nothing swept yet
        sink.log({"a": 1})                  # first record opens + sweeps
        assert sink.segments_pruned == 4
        assert os.path.exists(f"{path}.1") and os.path.exists(f"{path}.2")
        for n in range(3, 7):
            assert not os.path.exists(f"{path}.{n}")
        assert obs.registry().counter("obs.segments_pruned").value == 4
    finally:
        sink.close()


def test_jsonl_retention_override_and_validation(tmp_path):
    path = str(tmp_path / "m.jsonl")
    for n in range(1, 5):
        with open(f"{path}.{n}", "w") as f:
            f.write("{}\n")
    sink = JSONLSink(path, keep_segments=1, retention_segments=3)
    try:
        sink.log({"a": 1})
        assert sink.segments_pruned == 1    # only .4 fell outside 3
        assert os.path.exists(f"{path}.3")
        assert not os.path.exists(f"{path}.4")
    finally:
        sink.close()
    with pytest.raises(ValueError):
        JSONLSink(path, retention_segments=0)


# ---------------------------------------------------------------------------
# The acceptance round: chaos kill mid-publish -> fetchable forensics
# ---------------------------------------------------------------------------

def test_chaos_forensics_round_end_to_end(tmp_path):
    """A miner is chaos-killed mid-(wire-v2)-publish; its crash handler
    ships a postmortem bundle once the transport briefly heals (the
    supervisor's last gasp). The averager's SLO engine then breaches
    stale_node and freezes ITS bundle. scripts/postmortem.py must
    reconstruct one causal timeline from the two bundles + two JSONL
    streams: the torn publish is named with its shard progress and cid,
    the SLO rule that fired is named against the dead miner, and at
    least one cid joins events from both roles."""
    art = str(tmp_path / "artifacts")
    miner_jsonl = str(tmp_path / "miner.jsonl")
    avg_jsonl = str(tmp_path / "averager.jsonl")
    plain = LocalFSTransport(art)

    # ---- phase 1: the miner publishes a healthy v2 delta + heartbeat
    miner_sink = JSONLSink(miner_jsonl)
    obs.configure(miner_sink, role="miner")
    rec_m = flight.configure("miner", "m0", transport=plain)
    pub1 = DeltaPublisher(plain, "m0", report=_Report(),
                          publish_retry=FAST_RETRY, meta_retry=FAST_RETRY,
                          wire_spec={"format": 2, "density": 1 / 64,
                                     "quant": "int8"})
    packed1 = jax.device_get(dl.pack_delta_v2(_tree(1), density=1 / 64)[0])
    assert pub1.publish_now(packed1, None, None, "m0-000001")
    plain.publish_delta_meta(
        tbase.heartbeat_id("miner", "m0"),
        build_heartbeat("miner", "m0", 1, now=1.0, steps=10.0))

    # ---- phase 2: the next publish is killed between shard 1 and
    # shard 2 (each shard publish is one chaos op; the op schedule kills
    # the role at op 2 and revives it at op 4 — the window in which the
    # crash handler's bundle publish slips out)
    chaos_m = ChaosTransport(
        LocalFSTransport(art), role="miner",
        schedule=[ChaosEvent(2, "kill_role", "miner"),
                  ChaosEvent(4, "revive_role", "miner")])
    rec_m.transport = chaos_m
    pub2 = DeltaPublisher(chaos_m, "m0", report=_Report(),
                          publish_retry=FAST_RETRY, meta_retry=FAST_RETRY,
                          wire_spec={"format": 2, "density": 1 / 64,
                                     "quant": "int8"})
    packed2 = jax.device_get(dl.pack_delta_v2(_tree(2), density=1 / 64)[0])
    assert pub2.publish_now(packed2, None, None, "m0-000002") is False
    torn = [e for e in rec_m.events()
            if e["kind"] == "publish" and e["outcome"] == "torn"]
    assert torn and torn[0]["shards_done"] == 1 \
        and torn[0]["cid"] == "m0-000002"
    # the "process dies": role-main finally freezes the crash bundle,
    # whose publish rides op 4 — the revive — onto the shared store
    try:
        raise RuntimeError("miner chaos-killed mid-publish")
    except RuntimeError:
        flight.shutdown()
    obs.reset()
    miner_sink.close()
    miner_bundle = flight.fetch_bundle(plain, "miner", "m0")
    assert miner_bundle is not None, \
        "chaos-killed miner left no Transport-fetchable postmortem"
    assert miner_bundle["reason"] == "crash"
    assert any(e["kind"] == "publish" and e.get("outcome") == "torn"
               for e in miner_bundle["events"])

    # ---- phase 3: the averager's rounds observe the death
    avg_sink = JSONLSink(avg_jsonl)
    obs.configure(avg_sink, role="averager")
    chaos_a = ChaosTransport(LocalFSTransport(art), role="averager")
    flight.configure("averager", "a0", transport=chaos_a)
    fm = FleetMonitor(chaos_a, metrics=avg_sink,
                      rules=[SLORule("stale_node", "stale", threshold=1)])
    rem = RemediationEngine(
        fm, metrics=avg_sink,
        policy=RemediationPolicy(quarantine_rules=("stale_node",)))
    try:
        # round 1 sees the last heartbeat; the later rounds see silence.
        # stage_one-style fetches tag avg spans with the rider's cid
        # (still m0-000001: the torn publish never committed a manifest
        # or rider — manifest-last kept readers consistent)
        assert fm.poll(["m0"]) == 1
        cid = obs.rider_delta_id(chaos_a.fetch_delta_meta("m0"))
        with obs.span("avg.fetch", cid=cid, miner="m0"):
            assert chaos_a.fetch_delta_bytes("m0") is not None
        for _ in range(3):
            fm.poll(["m0"])
        breaches = fm.evaluate_slos()
        assert [b["slo_breach"] for b in breaches] == ["stale_node"]
        actions = rem.observe_round(breaches)
        assert actions and actions[0]["remediation"] == "quarantined"
        assert fm.ledger()["miner/m0"]["pm_ref"] == breaches[0]["pm_ref"]
        fm.flush(avg_sink)
        obs.flush(avg_sink)
    finally:
        fm.close()
        flight.reset()
        obs.reset()
        avg_sink.close()
    avg_bundle = flight.fetch_bundle(plain, "averager", "a0")
    assert avg_bundle is not None
    assert avg_bundle["reason"] == "slo_stale_node"

    # ---- phase 4: scripts/postmortem.py reconstructs the timeline
    rep = postmortem.report(
        [miner_jsonl, avg_jsonl]
        + sorted(__import__("glob").glob(
            os.path.join(art, "deltas", "__pm__*"))))
    assert {"miner", "averager"} <= set(rep["roles"])
    assert len(rep["bundles"]) == 2
    # the torn publish is named, with its cid and shard progress
    torn = [e for e in rep["torn_publishes"] if e.get("outcome") == "torn"]
    assert torn, rep["torn_publishes"]
    assert torn[0]["cid"] == "m0-000002"
    assert torn[0]["shards_done"] == 1 and torn[0]["shards_total"] == 2
    assert torn[0]["source"] == "miner/m0"
    # the SLO rule that fired is named against the dead miner
    slo = [e for e in rep["slo_fired"] if e.get("rule") == "stale_node"
           or e.get("hotkey") == "m0"]
    assert slo, rep["slo_fired"]
    # >= 2 roles join on one cid: the miner's healthy publish and the
    # averager's fetch of that same artifact share m0-000001
    assert "m0-000001" in rep["joined_cids"], rep["joined_cids"]
    sources = rep["joined_cids"]["m0-000001"]
    assert any(s.startswith("miner/") for s in sources)
    assert any(s.startswith("averager/") for s in sources)
    # the timeline is time-ordered and spans both roles
    ts = [e["t"] for e in rep["timeline"]]
    assert ts == sorted(ts)
    # --json CLI spelling works end to end
    out = str(tmp_path / "pm.json")
    assert postmortem.main(["--work-dir", str(tmp_path), "--json",
                            "--out", out]) == 0
    with open(out) as f:
        rep2 = json.load(f)
    assert rep2["torn_publishes"] and rep2["slo_fired"]
