"""`run_cell.py --trace 1` of the tiny train cell on the CPU, for the
metrics PR 36 added: what reads the device plane (a run's time on the
device's clock, its time by scope) is LEFT OUT of the result line, not
printed as zero, because a CPU trace holds no `/device:TPU:*` plane and so
no `op_name`; what reads the program's own histogram is a finite number;
none raises."""

import json
import math

from test_rehearsal import _last_json

NEEDS_A_DEVICE_PLANE_PREFIX = ("train.step_device_ms", "train.scope_")


def _new_metrics(cell_of):
    import conftest
    with open(f"{conftest.ROOT}/BENCHMARK.json") as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    first_new = names.index("train.step_device_ms")
    return [m["name"] for m in bench["per_layer"][first_new:]
            if cell_of in m["workloads"]]


def test_traced_train_run_leaves_out_what_it_cannot_read(tiny_checkout,
                                                         capsys):
    rc = tiny_checkout.main(["--workload", "train-tiny", "--seed",
                             str(2**31 + 11), "--seconds", "2", "--trace",
                             "1"])
    res, lines = _last_json(capsys)
    assert rc == 0 and res["correct"] is True, "\n".join(lines)
    got = res["metrics"]
    new = _new_metrics("train-large-t1024")
    assert len(new) == 8 and len(_new_metrics("train-lfm2-t8192")) == 10
    for name in new:
        if name.startswith(NEEDS_A_DEVICE_PLANE_PREFIX):
            assert name not in got, (name, got[name])
        else:
            assert math.isfinite(got[name]["value"]), name
    # the loop's own wait for a batch, from `miner.data_wait_ms`
    inside = got["train.data_wait_inside_pct"]["value"]
    assert 0.0 <= inside < 100.0
    assert "train.data_wait_pct" in got     # the outside twin stays
