"""Device time by scope (`readers/trace_scope.py`): the scope and pass rules,
self time, whole runs only, by hand; and on a recording cut from the chip
(`data/trace_train_scopes.json`: two whole runs of `train-lfm2-t8192`'s
`jit_train_step`, every event with the `op_name` the trace held for it)."""

import json
import os
import types

import pytest

from readers import trace_scope as ts
from readers import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000
STEP = r"^jit_train_step\("
P = "jit(train_step)/"


@pytest.mark.parametrize("op_name, scope", [
    (P + "train.optimizer/sub", "train.optimizer"),
    (P + "jvp(train.loss)/reduce_sum", "train.loss"),
    (P + "jvp(GPT2)/h_3/gpt2.attn/c_attn/dot_general", "gpt2.attn"),
    # the innermost of two scopes of the vocabulary
    (P + "jvp(Lfm2Moe)/layer_2/lfm2.moe_ffn/layer_2._experts/moe.experts/"
     "jit(_take)/gather", "moe.experts"),
    (P + "jvp(Lfm2Moe)/layer_2/lfm2.moe_ffn/ffn_norm/mul", "lfm2.moe_ffn"),
    # a wrapper around the scope's own component
    (P + "transpose(jvp(Lfm2Moe))/transpose(jvp(lfm2.head))/dot_general",
     "lfm2.head"),
    # dotted, but not of the vocabulary: flax's method scope
    (P + "jvp(Lfm2Moe)/layer_1/layer_1._experts/reduce_sum", None),
    (P + "transpose(jvp(GPT2))/jvp(GPT2)/remat2", None),
    (P + "add", None),
    ("", None),
])
def test_scope_is_the_innermost_component_of_the_vocabulary(op_name, scope):
    assert ts.scope_of(op_name) == scope


@pytest.mark.parametrize("op_name, which", [
    (P + "jvp(GPT2)/h_0/gpt2.mlp/c_fc/dot_general", "forward"),
    (P + "train.optimizer/mul", "forward"),
    (P + "transpose(jvp(GPT2))/jvp(GPT2)/checkpoint/h_0/gpt2.mlp/c_fc/"
     "transpose", "backward"),
    (P + "transpose(jvp(GPT2))/jvp(GPT2)/checkpoint/rematted_computation/"
     "h_0/gpt2.mlp/c_fc/dot_general", "rerun"),
    (P + "transpose(jvp(train.loss))/mul", "backward"),
    ("", "forward"),
])
def test_pass_rule(op_name, which):
    assert ts.pass_of(op_name) == which


def _ops(*events):
    return sorted(events, key=lambda e: (e[1], -e[2]))


def test_self_time_of_a_while_and_its_body():
    """The `while` counts for its own time less its body's events, a
    `conditional` inside the body likewise: every nanosecond once."""
    ops = _ops(
        ("%while.1 = (..) while(..)", 10 * MS, 50 * MS, P + "train.loss/while"),
        ("%fusion.2 = f32[8] fusion(..)", 12 * MS, 10 * MS,
         P + "train.loss/while/body/dot_general"),
        ("%conditional.3 = (..) conditional(..)", 25 * MS, 20 * MS,
         P + "train.loss/while/body/cond"),
        ("%fusion.4 = f32[8] fusion(..)", 26 * MS, 15 * MS,
         P + "train.optimizer/mul"),
        ("%fusion.5 = f32[8] fusion(..)", 70 * MS, 5 * MS, ""))
    got = {ev[0].split(" ")[0]: ns for ev, ns in ts.self_times(ops)}
    assert got == {"%while.1": 20 * MS, "%fusion.2": 10 * MS,
                   "%conditional.3": 5 * MS, "%fusion.4": 15 * MS,
                   "%fusion.5": 5 * MS}
    runs = [(0, 100 * MS)]
    tab = ts.table(runs, ts.classify(ops, runs))
    assert tab["cells"] == {("train.loss", "forward"): 35.0,
                            ("train.optimizer", "forward"): 15.0,
                            (None, "forward"): 5.0}
    assert tab["run_ms"] == 100.0 and tab["gap_ms"] == pytest.approx(45.0)


def test_a_child_that_outlasts_its_parent_is_clipped_to_it():
    ops = _ops(("%call.1", 0, 10 * MS, "x"), ("%fusion.2", 8 * MS, 5 * MS, "x"))
    assert [ns for _, ns in ts.self_times(ops)] == [8 * MS, 2 * MS]


def _rec(ops, modules, window, monkeypatch):
    """A Record as `run_cell` hands one to a reader, over plain tuples."""
    trace = xplane.from_events(
        {"/device:TPU:0": [e[:3] for e in ops]},
        [(xplane.WINDOW_SPAN, window[0], window[1] - window[0])],
        {"/device:TPU:0": modules})
    monkeypatch.setattr(ts, "load",
                        lambda trace_dir: (tuple(ops), tuple(modules)))
    ts._rows.cache_clear()
    return types.SimpleNamespace(
        trace=trace, run=types.SimpleNamespace(trace_dir="recorded"))


def test_whole_runs_only(monkeypatch):
    """A run cut by an edge of the traced window is left out with all its
    events; so is another program's run."""
    modules = [("jit_train_step(1)", 0, 30 * MS),          # began before
               ("jit_train_step(1)", 30 * MS, 30 * MS),
               ("jit_snap(7)", 60 * MS, 5 * MS),
               ("jit_train_step(1)", 65 * MS, 30 * MS),
               ("jit_train_step(1)", 95 * MS, 30 * MS)]    # ends after
    name = P + "train.optimizer/mul"
    ops = _ops(("%f.1", 12 * MS, 10 * MS, name),
               ("%f.1", 32 * MS, 4 * MS, name),
               ("%f.9", 61 * MS, 3 * MS, name),
               ("%f.1", 70 * MS, 6 * MS, name),
               ("%f.1", 96 * MS, 10 * MS, name))
    rec = _rec(ops, modules, (10 * MS, 110 * MS), monkeypatch)
    assert ts.whole_runs(modules, STEP, *rec.trace.window) == [
        (30 * MS, 60 * MS), (65 * MS, 95 * MS)]
    assert ts.read(rec, module_pattern=STEP,
                   scope=r"train\.optimizer") == pytest.approx(5.0)
    assert ts.read(rec, module_pattern=STEP, unnamed=True,
                   as_pct=True) == 0.0
    # a scope no instruction of the program carries (the parent's program):
    # nothing to read, not 0 ms
    assert ts.read(rec, module_pattern=STEP, scope=r"gpt2\.attn") is None
    assert ts.read(rec, module_pattern=STEP, passes=["rerun"]) is None
    assert ts.read(rec, module_pattern=r"^jit_serve_decode\(",
                   scope=r"train\.optimizer") is None


def test_no_op_name_reads_as_nothing_not_as_zero(monkeypatch):
    """A trace whose events carry no `op_name` (the parent's programs carry
    one too; a profiler or backend that drops the stat does not)."""
    modules = [("jit_train_step(1)", 0, 30 * MS)]
    ops = _ops(("%f.1", 2 * MS, 10 * MS, ""), ("%f.2", 15 * MS, 5 * MS, ""))
    rec = _rec(ops, modules, (0, 40 * MS), monkeypatch)
    for args in ({"scope": r"train\.optimizer"}, {"passes": ["rerun"]},
                 {"unnamed": True, "as_pct": True}):
        assert ts.read(rec, module_pattern=STEP, **args) is None
    assert ts.classify(ops, [(0, 30 * MS)]) is None
    assert ts.classify(ops, []) is None
    assert ts.table_for(rec, module_pattern=STEP) is None
    assert "no event carries an op_name" in ts.format_table(None)
    # no trace at all, and a trace without a device plane
    none = types.SimpleNamespace(trace=None, run=rec.run)
    assert ts.read(none, module_pattern=STEP, unnamed=True) is None
    monkeypatch.setattr(ts, "load", lambda trace_dir: ((), ()))
    ts._rows.cache_clear()
    bare = types.SimpleNamespace(
        trace=xplane.from_events({"x": [("a", 0, 5)]}, [], {}), run=rec.run)
    assert ts.read(bare, module_pattern=STEP, unnamed=True) is None


# ---------------------------------------------------------------------------
# the first half: the metadata table in the file's bytes
# ---------------------------------------------------------------------------

def _vi(n: int) -> bytes:
    out = b""
    while True:
        n, low = n >> 7, n & 0x7F
        out += bytes([low | (0x80 if n else 0)])
        if not n:
            return out


def _ld(field: int, body: bytes) -> bytes:       # a length-delimited field
    return _vi(field << 3 | 2) + _vi(len(body)) + body


def _v(field: int, n: int) -> bytes:             # a varint field
    return _vi(field << 3) + _vi(n)


def _plane(name, stat_names, events, lines=b"") -> bytes:
    """An XPlane (xplane.proto): `stat_names` {id: name}, `events`
    [(id, name, [(stat id, str or ("ref", id))])]."""
    body = _ld(2, name.encode()) + lines
    for sid, sname in stat_names.items():
        body += _ld(5, _v(1, sid) + _ld(2, _v(1, sid) + _ld(2, sname.encode())))
    for eid, ename, stats in events:
        meta = _v(1, eid) + _ld(2, ename.encode())
        for sid, val in stats:
            meta += _ld(5, _v(1, sid) + (
                _v(7, val[1]) if isinstance(val, tuple)
                else _ld(5, val.encode())))
        body += _ld(4, _v(1, eid) + _ld(2, meta))
    return _ld(1, body)


def test_event_metadata_stat_reads_the_wire_format():
    """`tf_op` as a string and as a reference to a stat metadata's name, an
    event without it, ids past one byte, a fixed-width field and a line in
    the way, and another plane before the device's."""
    long_name = "%fusion.7 = f32[4,1023]{1,0} fusion(" + "x" * 300 + ")"
    fixed = _vi(9 << 3 | 1) + b"\x00" * 8         # a double, skipped
    space = _plane("/host:CPU", {3: "tf_op"},
                   [(1, "host_event", [(3, "not/the/device's")])])
    space += _plane(
        "/device:TPU:0", {26: "tf_op", 300: P + "gpt2.attn/by_ref", 5: "x"},
        [(1, "%gmm.3 = bf16[8] custom-call()", [(5, "other"),
                                                (26, P + "moe.experts/gmm")]),
         (70000, long_name, [(26, ("ref", 300))]),
         (2, "%copy.1 = f32[2] copy()", [(5, "no tf_op here")])],
        lines=_ld(3, _v(1, 7) + _ld(2, b"XLA Ops") + fixed + _ld(4, b"\x08\x01")))
    space += _plane("/device:TPU:1", {26: "tf_op"},
                    [(1, "%gmm.3 = bf16[8] custom-call()", [(26, "second")])])
    assert ts.event_metadata_stat(space) == {
        "%gmm.3 = bf16[8] custom-call()": P + "moe.experts/gmm",
        long_name: P + "gpt2.attn/by_ref"}
    assert ts.event_metadata_stat(space, stat="x") == {
        "%gmm.3 = bf16[8] custom-call()": "other",
        "%copy.1 = f32[2] copy()": "no tf_op here"}
    assert ts.event_metadata_stat(space, plane_prefix="/host:") == {
        "host_event": "not/the/device's"}
    assert ts.event_metadata_stat(b"") == {}
    assert ts.event_metadata_stat(_plane("/host:CPU", {}, [])) == {}


def test_load_joins_the_table_to_profiledatas_events(tmp_path):
    """A whole file: ProfileData's events of the first device plane's two
    lines, each operation with the `op_name` its metadata holds."""
    def line(lid, name, events):        # events: [(metadata id, ps, ps)]
        body = _v(1, lid) + _ld(2, name.encode()) + _v(3, 1000)
        for mid, offset_ps, dur_ps in events:
            body += _ld(4, _v(1, mid) + _v(2, offset_ps) + _v(3, dur_ps))
        return _ld(3, body)

    gmm = "%gmm.3 = bf16[8] custom-call()"
    plane = _plane(
        "/device:TPU:0", {26: "tf_op"},
        [(1, gmm, [(26, P + "moe.experts/gmm:")]),     # "<op_name>:<type>"
         (2, "%copy.1 = f32[2] copy()", []),
         (3, "jit_train_step(42)", [])],
        lines=line(1, "XLA Modules", [(3, 0, 9_000_000)])
        + line(2, "XLA Ops", [(2, 5_000_000, 1_000_000),
                              (1, 1_000_000, 3_000_000)]))
    d = tmp_path / "plugins" / "profile" / "t0"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(plane)
    ops, modules = ts.load(str(tmp_path))
    assert ops == ((gmm, 2000, 3000, P + "moe.experts/gmm"),
                   ("%copy.1 = f32[2] copy()", 6000, 1000, ""))
    assert modules == (("jit_train_step(42)", 1000, 9000),)
    runs = ts.whole_runs(modules, STEP, 0, 20_000)
    assert ts.table(runs, ts.classify(ops, runs))["cells"] == {
        ("moe.experts", "forward"): 0.003, (None, "forward"): 0.001}


# ---------------------------------------------------------------------------
# the recording from the chip
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_train_scopes.json")) as f:
        rec = json.load(f)
    ops = tuple((name, start, dur, rec["op_names"][i])
                for name, start, dur, i in rec["ops"])
    modules = [tuple(m) for m in rec["modules"]]
    runs = ts.whole_runs(modules, STEP, *rec["window"])
    return rec, ops, modules, runs, ts.classify(ops, runs)


def test_recording_partitions_each_run(recorded):
    """The classes plus unnamed plus the gaps inside a run add up to the
    run, to floating point's rounding; so do the three passes."""
    rec, _, _, runs, rows = recorded
    assert len(runs) == rec["expect"]["runs"]
    tab = ts.table(runs, rows)
    assert sum(tab["cells"].values()) + tab["gap_ms"] == pytest.approx(
        tab["run_ms"], rel=1e-12)
    named = ts.select(runs, rows)
    unnamed = ts.select(runs, rows, unnamed=True)
    assert named + unnamed + tab["gap_ms"] == pytest.approx(tab["run_ms"],
                                                            rel=1e-12)
    assert sum(ts.select(runs, rows, passes=[p]) for p in ts.PASSES) == \
        pytest.approx(named, rel=1e-12)


def test_recording_meets_the_chips_own_table(recorded):
    """Two runs without their shortest events against the traced run's own
    table over all 42 (`expect`, printed on the chip by
    `tools/train_scopes.py`)."""
    rec, _, _, runs, rows = recorded
    want, tab = rec["expect"], ts.table(runs, rows)
    assert tab["run_ms"] == pytest.approx(want["run_ms"], rel=5e-3)
    for scope, ms in want["cells"].items():
        got = sum(v for (s, _), v in tab["cells"].items() if s == scope)
        assert got == pytest.approx(ms, rel=5e-3), scope
    for which, ms in want["passes"].items():
        got = sum(v for (_, p), v in tab["cells"].items() if p == which)
        assert got == pytest.approx(ms, rel=5e-3), which
    assert set(want["cells"]) | {None} == {s for s, _ in tab["cells"]}
    assert ("train.optimizer", "backward") not in tab["cells"]
    text = ts.format_table(tab)
    assert "moe.experts" in text and "largest unnamed" in text


def test_recording_through_the_metric_files(recorded, monkeypatch):
    """`read` with the arguments of `layer_metrics/train.scope_*.json`: each
    metric is the sum of its scopes' cells, the classes of a step add up to
    `train.step_device_ms`, and the share without a name is small."""
    rec, ops, modules, runs, rows = recorded
    r = _rec(ops, modules, tuple(rec["window"]), monkeypatch)
    cells = rec["expect"]["cells"]

    def metric(name):
        with open(os.path.join(HERE, "..", "layer_metrics",
                               f"train.{name}.json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "trace_scope"
        return ts.read(r, **spec["args"])

    got = {n: metric(f"scope_ms.{n}") for n in (
        "attn", "ffn", "head_loss", "optimizer", "conv", "rerun",
        "moe_rows")}
    want = {"attn": cells["lfm2.attn"], "conv": cells["lfm2.conv"],
            "ffn": (cells["moe.experts"] + cells["moe.route"]
                    + cells["lfm2.dense_ffn"] + cells["lfm2.moe_ffn"]),
            "head_loss": cells["lfm2.head"] + cells["train.loss"],
            "optimizer": cells["train.optimizer"],
            "rerun": rec["expect"]["passes"]["rerun"]}
    for name, ms in want.items():
        assert got[name] == pytest.approx(ms, rel=5e-3), name
    unnamed_pct = metric("scope_unnamed_pct")
    assert 0.0 < unnamed_pct < 5.0
    tab = ts.table(runs, rows)
    classes = (got["attn"] + got["ffn"] + got["conv"] + got["head_loss"]
               + got["optimizer"] + cells["lfm2.embed"])
    assert classes + unnamed_pct / 100.0 * tab["run_ms"] + tab["gap_ms"] \
        == pytest.approx(tab["run_ms"], rel=2e-3)


def test_exclude_own_leaves_the_kernels_out(recorded):
    """`train.scope_ms.moe_rows`: `moe.experts` less the grouped products'
    own events (`%gmm.N`, `%tgmm.N`)."""
    _, _, _, runs, rows = recorded
    whole = ts.select(runs, rows, scope=r"moe\.experts")
    moved = ts.select(runs, rows, scope=r"moe\.experts",
                      exclude_own=r"^%?(gmm|tgmm)")
    kernels = sum(ns for own, _, _, _, ns in rows
                  if own.startswith(("%gmm", "%tgmm"))) / len(runs) / 1e6
    assert kernels > 40.0 and moved == pytest.approx(whole - kernels,
                                                     rel=1e-9)
