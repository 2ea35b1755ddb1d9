"""Mean device time of one run of a program from the `XLA Modules` line: by
hand, and on the slice recorded from the chip."""

import types

import pytest

from readers import trace_module, xplane
from test_program_spans import _recorded

MS = 1_000_000


def _rec(modules, window=(0, 100 * MS)):
    trace = xplane.from_events(
        {"/device:TPU:0": [("a", window[0], window[1] - window[0])]},
        [(xplane.WINDOW_SPAN, window[0], window[1] - window[0])],
        {"/device:TPU:0": modules})
    return types.SimpleNamespace(trace=trace)


def test_mean_of_the_whole_runs_of_the_matching_program():
    rec = _rec([("jit_serve_decode(123)", 10 * MS, 20 * MS),
                ("jit_serve_decode(456)", 40 * MS, 30 * MS),
                ("jit_serve_decode_sample(7)", 70 * MS, 5 * MS),
                ("jit_serve_prefill(9)", 80 * MS, 10 * MS)])
    assert trace_module.read(rec, module_pattern=r"^jit_serve_decode\(") == \
        pytest.approx(25.0)
    assert trace_module.read(rec, module_pattern=r"^jit_serve_prefill\(") \
        == pytest.approx(10.0)


def test_runs_cut_by_either_edge_are_left_out():
    rec = _rec([("jit_train_step(1)", 0, 30 * MS),        # began before
                ("jit_train_step(1)", 30 * MS, 28 * MS),
                ("jit_train_step(1)", 58 * MS, 30 * MS),
                ("jit_train_step(1)", 88 * MS, 30 * MS)],  # ends after
               window=(10 * MS, 100 * MS))
    assert trace_module.read(rec, module_pattern=r"^jit_train_step\(") == \
        pytest.approx(29.0)


def test_nothing_to_read_is_none():
    rec = _rec([("jit_step(1)", 10 * MS, 20 * MS)])
    assert trace_module.read(rec, module_pattern=r"^jit_serve_decode\(") \
        is None
    assert trace_module.read(types.SimpleNamespace(trace=None),
                             module_pattern="x") is None
    # only cut runs: nothing whole to average
    cut = _rec([("jit_serve_decode(1)", 0, 200 * MS)], window=(50, 100 * MS))
    assert trace_module.read(cut, module_pattern="jit_serve_decode") is None


def test_recorded_slice():
    rec, trace, _ = _recorded()
    got = trace_module.read(types.SimpleNamespace(trace=trace),
                            module_pattern=r"^jit_serve_decode\(")
    assert got == pytest.approx(rec["expect"]["decode_device_ms"], abs=1e-6)
    names = [n for evs in trace.device_modules.values() for n, _, _ in evs]
    lo, hi = trace.window
    cut = [(n, s, d) for evs in trace.device_modules.values()
           for n, s, d in evs if s < lo or s + d > hi]
    assert cut, "the recording holds runs cut by the window's edges"
    assert any(n.startswith("jit_serve_prefill(") for n in names)
