"""`run_cell.py --trace 1` at the `tiny` preset on the CPU, for the metrics
that read the program's own spans and program names (PR 25): each comes out
as a finite number, or is left out because this trace has nothing to read it
from (a CPU trace holds no `/device:TPU:*` plane, so no `XLA Modules` run);
none raises."""

import json
import math

import pytest

from test_rehearsal import _last_json

IDLE_PARTS = ["serve.idle_pct.admit", "serve.idle_pct.decode",
              "serve.idle_pct.outside_step"]
NEEDS_A_DEVICE_PLANE = {"serve.decode_device_ms", "serve.prefill_device_ms"}


def _new_metrics(cell_of):
    """Names of the per-layer metrics PR 25 added that list `cell_of`."""
    import conftest
    with open(f"{conftest.ROOT}/BENCHMARK.json") as f:
        bench = json.load(f)
    first_new = [m["name"] for m in bench["per_layer"]].index(
        "serve.idle_pct.admit")
    return [m["name"] for m in bench["per_layer"][first_new:]
            if cell_of in m["workloads"]]


# the seed of test_rehearsal.py: the tiny cell's limits are set for it
def test_traced_run_reads_every_new_metric_or_leaves_it_out(
        tiny_checkout, capsys):
    rc = tiny_checkout.main(["--workload", "serve-tiny", "--seed", "7",
                             "--seconds", "3", "--trace", "1"])
    res, lines = _last_json(capsys)
    assert rc == 0 and res["correct"] is True, "\n".join(lines)
    got = res["metrics"]
    for name in _new_metrics("serve-large-chat"):
        if name in got:
            assert math.isfinite(got[name]["value"]), name
        else:
            assert name in NEEDS_A_DEVICE_PLANE, name
    # the program's spans were found in the trace, and they split the
    # idle share (all of the window here: no device plane) exactly
    assert set(IDLE_PARTS) <= set(got)
    assert sum(got[n]["value"] for n in IDLE_PARTS) == pytest.approx(
        got["device.idle_pct.serve"]["value"], abs=1e-9)
    assert got["serve.idle_pct.outside_step"]["value"] < 100.0
    assert got["serve.prefill_p95_ms"]["value"] > 0
    assert got["serve.ttft_inside_p95_ms"]["value"] > 0
