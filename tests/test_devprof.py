"""Device performance observatory (utils/devprof.py).

The tentpole contracts of ISSUE 12: per-program XLA cost attribution
(skip-not-fail where the backend has no cost model), the roofline
table's unknown-chip fallback, the closed program vocabulary as a
producer-side lint (plus the source-level lint that every jax.jit in
the five hot-path modules is wrapped or explicitly exempted), the
cardinality cap, the obs.flush mirror, the step-time anatomy join,
perf_report's where-the-time-goes/coverage table, and the
postmortem/perf_report Chrome-trace export round trip.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.utils import devprof, obs
from distributedtraining_tpu.utils.metrics import InMemorySink, JSONLSink

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts"))
import obs_report   # noqa: E402
import perf_report  # noqa: E402
import postmortem   # noqa: E402


@pytest.fixture(autouse=True)
def _clean():
    obs.reset()
    devprof.reset()
    yield
    devprof.reset()
    obs.reset()


# ---------------------------------------------------------------------------
# Roofline
# ---------------------------------------------------------------------------

def test_roofline_known_chips():
    v5e = devprof.roofline_for("TPU v5 lite")
    assert v5e.known and v5e.peak_flops == 197e12
    assert devprof.roofline_for("TPU v6 lite").peak_flops == 918e12
    assert devprof.roofline_for("TPU v5p").peak_flops == 459e12
    v4 = devprof.roofline_for("TPU v4")
    assert v4.hbm_bytes_per_s == 1228e9
    # ridge point = peak flops / peak bandwidth
    assert v4.ridge_intensity == pytest.approx(275e12 / 1228e9)


def test_roofline_unknown_chip_fallback():
    for kind in ("cpu", "Graphcore IPU", "", None):
        rl = devprof.roofline_for(kind)
        assert rl.known is False
        assert rl.peak_flops is None and rl.hbm_bytes_per_s is None
        assert rl.ridge_intensity is None
    # achieved fractions are omitted, never fabricated, on unknown chips
    stats = devprof.ProgramStats("train.step", "-")
    stats.flops = 1e9
    stats.exec_ms.observe(10.0)
    assert stats.achieved(devprof.roofline_for("cpu")) == (None, None)


def test_achieved_fractions_on_known_roofline():
    rl = devprof.roofline_for("TPU v5 lite")
    stats = devprof.ProgramStats("train.step", "8x1024")
    stats.flops = 197e12 * 0.005      # 0.5% of one peak-second
    stats.bytes_accessed = 819e9 * 0.01
    stats.exec_ms.observe(10.0)       # p50 = 10ms
    ff, bf = stats.achieved(rl)
    assert ff == pytest.approx(0.5)   # 0.005 peak-s of work in 0.01 s
    assert bf == pytest.approx(1.0)
    rec = stats.as_record(rl)
    assert rec["achieved_flops_frac"] == pytest.approx(0.5)
    assert rec["achieved_bw_frac"] == pytest.approx(1.0)
    assert rec["arith_intensity"] == pytest.approx(
        stats.flops / stats.bytes_accessed, rel=1e-3)
    # the same fractions in the registry's export, which the JSONL record,
    # perf_report and /metrics read
    devprof.enable()
    devprof._STATE.roofline = rl
    devprof._STATE.records[(stats.prog, stats.bucket)] = stats
    snap = devprof.snapshot()
    assert snap["roofline"]["peak_flops"] == 197e12
    assert snap["programs"] == [rec]
    assert any(line.startswith("dt_prog_achieved_flops_frac{")
               and line.endswith(" 0.5") for line in devprof.prom_lines())


# ---------------------------------------------------------------------------
# wrap / track
# ---------------------------------------------------------------------------

def test_wrap_rejects_unknown_program_name():
    # the producer-side lint (the flight.EVENT_KINDS discipline): a hot
    # path cannot ship observed under a name outside the vocabulary
    with pytest.raises(ValueError, match="unknown devprof program"):
        devprof.wrap("my.new.thing", lambda x: x)
    with pytest.raises(ValueError, match="unknown devprof program"):
        with devprof.track("my.new.thing"):
            pass


def test_wrap_disabled_is_passthrough():
    calls = []
    w = devprof.wrap("delta.finite", lambda x: calls.append(x) or x * 2)
    assert w(3) == 6
    assert calls == [3]
    assert devprof.records() == []
    assert not devprof.dirty()


def test_wrap_records_calls_compile_and_exec():
    f = jax.jit(lambda x: (x @ x).sum())
    w = devprof.wrap("delta.merge", f,
                     bucket=lambda a, kw: a[0].shape[0])
    devprof.enable()
    x = jnp.ones((16, 16), jnp.float32)
    for _ in range(4):
        w(x)
    recs = devprof.records()
    assert len(recs) == 1
    r = recs[0]
    assert (r.prog, r.bucket) == ("delta.merge", "16")
    assert r.calls == 4
    assert r.compile_ms is not None and r.compile_ms > 0
    # first call is compile, the other three land in the exec histogram
    assert r.exec_ms.count == 3
    # cost attribution: skip-not-fail when the backend has no cost model
    if devprof.cost_analysis_available():
        assert r.flops and r.flops >= 2 * 16 ** 3 * 0.9
        assert r.bytes_accessed and r.bytes_accessed > 0
    else:  # pragma: no cover — exotic backend
        assert r.flops is None
    # a second bucket is a second record
    w(jnp.ones((8, 8), jnp.float32))
    assert {rec.bucket for rec in devprof.records()} == {"16", "8"}


def test_wrap_preserves_lower_and_wrapped():
    f = jax.jit(lambda x: x + 1)
    w = devprof.wrap("delta.finite", f)
    assert w.__wrapped__ is f
    assert w._devprof_name == "delta.finite"
    # AOT/HLO introspection keeps working through the wrapper
    assert "add" in w.lower(jnp.ones((2,))).as_text()


def test_track_host_phase():
    devprof.enable()
    with devprof.track("delta.densify"):
        pass
    (r,) = devprof.records()
    assert r.prog == "delta.densify" and r.host is True
    assert r.calls == 1 and r.exec_ms.count == 1
    assert r.flops is None  # host phases get no cost probe
    rec = r.as_record(devprof.roofline_for("cpu"))
    assert rec["host"] is True


def test_cardinality_cap_drops_and_counts():
    devprof.enable(max_programs=1)
    w1 = devprof.wrap("delta.finite", jax.jit(lambda x: x + 1))
    w2 = devprof.wrap("delta.merge", jax.jit(lambda x: x * 2))
    x = jnp.ones((4,))
    w1(x)
    w2(x)  # past the cap: dropped-and-counted, still executes
    assert [r.prog for r in devprof.records()] == ["delta.finite"]
    snap = devprof.snapshot()
    assert snap["dropped_programs"] >= 1
    assert any("dt_prog_dropped" in ln for ln in devprof.prom_lines())


# ---------------------------------------------------------------------------
# Exposure: prom lines, obs.flush mirror, anatomy
# ---------------------------------------------------------------------------

def test_prom_lines_labeled_series():
    devprof.enable()
    w = devprof.wrap("serve.decode", jax.jit(lambda x: x * 2), bucket="8x16")
    x = jnp.ones((4,))
    w(x)
    w(x)
    lines = devprof.prom_lines()
    text = "\n".join(lines)
    assert 'dt_prog_calls{prog="serve.decode",bucket="8x16"} 2.0' in text
    # the labeled per-program compile series (satellite: next to the
    # unlabeled compile.ms aggregate, which keeps rendering separately)
    assert 'dt_compile_ms{prog="serve.decode",bucket="8x16"}' in text
    assert 'dt_prog_exec_ms{prog="serve.decode",bucket="8x16",q="0.5"}' \
        in text
    # disabled -> nothing rendered
    devprof.disable()
    assert devprof.prom_lines() == []


def test_obs_http_render_includes_devprof():
    from distributedtraining_tpu.utils import obs_http
    obs.configure(InMemorySink(), role="t")
    devprof.enable()
    w = devprof.wrap("delta.finite", jax.jit(lambda x: x + 1))
    w(jnp.ones((4,)))
    body = obs_http.render()
    assert 'dt_prog_calls{prog="delta.finite",bucket="-"}' in body


def test_obs_flush_mirrors_devprof_record():
    sink = InMemorySink()
    obs.configure(sink, role="miner")
    devprof.enable()
    w = devprof.wrap("delta.finite", jax.jit(lambda x: x + 1))
    w(jnp.ones((4,)))
    obs.count("x")  # a nonempty registry so flush emits
    obs.flush()
    recs = [r for r in sink.records if "devprof" in r]
    assert recs, "flush did not mirror the devprof snapshot"
    dp = recs[-1]
    assert dp["role"] == "miner"
    progs = dp["devprof"]["programs"]
    assert progs and progs[0]["prog"] == "delta.finite"
    assert dp["devprof"]["roofline"]["device_kind"]
    # disabling detaches: no further mirror records
    devprof.disable()
    n = len([r for r in sink.records if "devprof" in r])
    obs.flush()
    assert len([r for r in sink.records if "devprof" in r]) == n


def test_anatomy_fields_join_step_and_device():
    sink = InMemorySink()
    obs.configure(sink, role="miner")
    devprof.enable()
    # 10 steps of 10ms wall, 4ms attributed device time, 1ms data wait
    for _ in range(10):
        obs.observe("miner.step_ms", 10.0)
        obs.observe("miner.data_wait_ms", 1.0)
    rec = devprof._get_record("train.step", "2x32")
    rec.calls = 10
    for _ in range(10):
        rec.exec_ms.observe(4.0)
    an = devprof.anatomy()
    assert an["anat.step_ms"] == pytest.approx(10.0)
    assert an["anat.device_ms"] == pytest.approx(4.0)
    assert an["anat.host_ms"] == pytest.approx(6.0)
    assert an["anat.data_wait_ms"] == pytest.approx(1.0)
    assert an["anat.device_frac"] == pytest.approx(0.4)
    # heartbeat vitals carry the anatomy as numeric linted extras
    from distributedtraining_tpu.engine.health import (Vitals,
                                                       build_heartbeat,
                                                       parse_heartbeat)
    body = Vitals().collect()
    assert body["anat.step_ms"] == pytest.approx(10.0)
    hb = build_heartbeat("miner", "m0", 1, now=0.0, **body)
    parsed = parse_heartbeat(hb)
    assert parsed["anat.device_frac"] == pytest.approx(0.4)
    devprof.disable()
    assert devprof.anatomy() == {}


# ---------------------------------------------------------------------------
# The tier-1 registration lint (flight.EVENT_KINDS discipline, source level)
# ---------------------------------------------------------------------------

# the hot-path modules the observatory must cover (the round-20 kernel
# modules included: a Pallas hot path must not ship unobserved either)
_HOT_MODULES = (
    "distributedtraining_tpu/engine/train.py",
    "distributedtraining_tpu/engine/batched_eval.py",
    "distributedtraining_tpu/parallel/collectives.py",
    "distributedtraining_tpu/delta.py",
    "distributedtraining_tpu/engine/serve.py",
    "distributedtraining_tpu/engine/speculative.py",
    "distributedtraining_tpu/engine/kv_transfer.py",
    "distributedtraining_tpu/ops/paged_attention.py",
    "distributedtraining_tpu/ops/dequant_scatter.py",
)


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_every_jit_in_hot_modules_is_registered_or_exempt():
    """Every ``jax.jit(...)`` AND ``pl.pallas_call(...)`` call in the
    hot-path modules must be wrapped in ``devprof.wrap(...)`` (so it
    reports cost/exec under a closed-vocabulary name) or carry a
    ``# devprof: exempt(<reason>)`` comment on the call line — a new
    hot path (XLA or Pallas) cannot ship unobserved."""
    import ast

    for rel in _HOT_MODULES:
        path = os.path.join(_repo_root(), rel)
        src = open(path).read()
        lines = src.splitlines()
        tree = ast.parse(src)
        parents: dict[ast.AST, ast.AST] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node
        offenders = []
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and ((node.func.attr == "jit"
                          and node.func.value.id == "jax")
                         or (node.func.attr == "pallas_call"
                             and node.func.value.id == "pl"))):
                continue
            # wrapped: some ancestor is a devprof.wrap(...) call
            wrapped = False
            cur = node
            while cur in parents:
                cur = parents[cur]
                if (isinstance(cur, ast.Call)
                        and isinstance(cur.func, ast.Attribute)
                        and cur.func.attr == "wrap"
                        and isinstance(cur.func.value, ast.Name)
                        and cur.func.value.id == "devprof"):
                    wrapped = True
                    break
            if wrapped:
                continue
            if "# devprof: exempt" in lines[node.lineno - 1]:
                continue
            offenders.append(f"{rel}:{node.lineno}")
        assert not offenders, (
            f"jax.jit/pl.pallas_call sites neither devprof.wrap()-"
            f"registered nor '# devprof: exempt'-annotated: {offenders}")


def test_every_wrap_name_in_hot_modules_is_in_vocabulary():
    import re
    names = set()
    for rel in _HOT_MODULES:
        src = open(os.path.join(_repo_root(), rel)).read()
        names |= set(re.findall(
            r"devprof\.(?:wrap|track)\(\s*[\"']([^\"']+)[\"']", src))
    assert names, "no registrations found in the hot-path modules"
    unknown = names - set(devprof.PROGRAMS)
    assert not unknown, f"names outside devprof.PROGRAMS: {unknown}"
    # and the engine hot paths the ISSUE names are all present
    assert {"train.step", "eval.cohort", "merge.sharded", "delta.screen",
            "delta.densify", "serve.prefill", "serve.decode",
            "delta.dequant_scatter"} <= names


# ---------------------------------------------------------------------------
# perf_report: where-the-time-goes + coverage + Perfetto export
# ---------------------------------------------------------------------------

def _run_tiny_miner(tmp_path, steps=6):
    from distributedtraining_tpu.engine import TrainEngine
    from distributedtraining_tpu.engine.train import MinerLoop
    from distributedtraining_tpu.models import gpt2
    from distributedtraining_tpu.transport import InMemoryTransport

    path = str(tmp_path / "miner.jsonl")
    sink = JSONLSink(path)
    obs.configure(sink, role="miner")
    devprof.enable()
    model, cfg = gpt2.make_model(gpt2.GPT2Config(
        n_layer=2, n_embd=32, n_head=2, vocab_size=128, n_positions=32))
    engine = TrainEngine(model, seq_len=16)
    loop = MinerLoop(engine, InMemoryTransport(), "m0",
                     send_interval=1e9, check_update_interval=1e9,
                     log_every=2, metrics=sink)
    loop.bootstrap(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 128, (2, 16), dtype=np.int32)}

    def batches():
        while True:
            yield batch

    loop.run(batches(), max_steps=steps)
    loop.flush()
    sink.close()
    return path


def test_perf_report_table_and_coverage(tmp_path):
    """Acceptance shape: a miner run yields a per-program table whose
    attributed device programs cover >= 90% of the measured step
    wall-clock (CPU blocking timing makes attribution exact here)."""
    path = _run_tiny_miner(tmp_path)
    rep = perf_report.build_report([path])
    assert rep["programs"], "no devprof records in the run's JSONL"
    progs = {r["prog"] for r in rep["programs"]}
    assert "train.step" in progs
    cov = rep["coverage"]["miner"]
    assert cov["step_histogram"] == "miner.step_ms"
    assert cov["coverage_frac"] >= 0.90, cov
    text = perf_report.format_table(rep)
    assert "train.step" in text and "coverage[miner]" in text
    # exit contract: 0 with records, 1 without
    assert perf_report.main([path]) == 0
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert perf_report.main([str(empty)]) == 1


def test_perf_report_trace_export(tmp_path):
    path = _run_tiny_miner(tmp_path)
    out = tmp_path / "round.trace.json"
    assert perf_report.main([path, "--trace", str(out)]) == 0
    trace = json.loads(out.read_text())
    evs = trace["traceEvents"]
    names = {e["name"] for e in evs if e.get("ph") == "M"}
    assert "process_name" in names
    spans = [e for e in evs if e.get("ph") == "X"]
    assert spans and all("dur" in e and e["dur"] >= 0 for e in spans)


# ---------------------------------------------------------------------------
# postmortem --trace: two-role localfs-style round trip
# ---------------------------------------------------------------------------

def test_postmortem_trace_round_trip(tmp_path, capsys):
    """Two roles' span streams sharing a cid -> one Chrome-trace file:
    one track per role, the shared correlation id in args on both."""
    miner = tmp_path / "miner.jsonl"
    avg = tmp_path / "averager.jsonl"
    cid = "m0-000001"
    miner.write_text("\n".join(json.dumps(r) for r in [
        {"span": "push.snapshot", "dur_ms": 3.0, "t0": 100.0,
         "depth": 0, "role": "miner", "cid": cid},
        {"span": "push.upload", "dur_ms": 8.0, "t0": 100.01,
         "depth": 0, "role": "miner", "cid": cid},
    ]) + "\n")
    avg.write_text("\n".join(json.dumps(r) for r in [
        {"span": "avg.fetch", "dur_ms": 5.0, "t0": 100.2,
         "depth": 0, "role": "averager", "cid": cid},
        {"span": "avg.merge", "dur_ms": 2.0, "t0": 100.3,
         "depth": 0, "role": "averager", "cids": [cid]},
    ]) + "\n")
    out = tmp_path / "pm.trace.json"
    rc = postmortem.main([str(miner), str(avg), "--json",
                          "--trace", str(out)])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    assert cid in rep["joined_cids"]  # the causal join still works
    trace = json.loads(out.read_text())
    evs = trace["traceEvents"]
    tracks = {e["args"]["name"] for e in evs if e["name"] == "process_name"}
    assert {"miner/-", "averager/-"} <= tracks
    spans = [e for e in evs if e.get("ph") == "X"]
    assert len(spans) == 4
    joined = [e for e in spans if e["args"].get("cid") == cid]
    assert len(joined) >= 3  # cid rides into args on both tracks
    assert {e["pid"] for e in joined} != {joined[0]["pid"]} or \
        len({e["pid"] for e in spans}) == 2
    # timestamps are relative microseconds, ordered like the input
    by_name = {e["name"]: e for e in spans}
    assert by_name["push.snapshot"]["ts"] < by_name["avg.merge"]["ts"]
