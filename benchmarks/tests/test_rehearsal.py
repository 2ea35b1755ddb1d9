"""`run_cell.py` end to end at the `tiny` preset on the CPU, from a
temporary copy in which a configuration, two cells, their traffic and a
per-layer metric with its reader were dropped in as NEW files plus entries
in BENCHMARK.json: nothing that was there is edited. The platform override
lives here; the command itself still refuses a CPU."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH_DIR, ROOT


def _last_json(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


@pytest.mark.parametrize("trace", [0, 1])
def test_train_cell_runs_and_is_correct(tiny_checkout, capsys, trace):
    rc = tiny_checkout.main(["--workload", "train-tiny", "--seed",
                             str(2**31 + 11), "--seconds", "2", "--trace",
                             str(trace)])
    res, lines = _last_json(capsys)
    assert rc == 0 and res["correct"] is True, "\n".join(lines)
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert res["failed"] == 0 and res["attempted"] > 0
    if trace == 0:
        assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    else:
        # the dropped-in metric came through its dropped-in reader
        assert res["metrics"]["tiny.steps"]["value"] == res["attempted"]
        assert "train.step_ms" in res["metrics"]
        assert "breakdown" in res or "busy_s" not in res["device"]
    assert any(line.startswith("bench: check first_loss_gap") for line in lines)


def test_serve_cell_runs_and_is_correct(tiny_checkout, capsys):
    rc = tiny_checkout.main(["--workload", "serve-tiny", "--seed", "7",
                             "--seconds", "3", "--trace", "0"])
    res, lines = _last_json(capsys)
    assert rc == 0 and res["correct"] is True, "\n".join(lines)
    assert set(res["metrics"]) == {"ttft_p95_ms", "itl_p95_ms",
                                   "serve_tokens_per_s", "setup_s"}
    assert res["failed"] == 0 and res["attempted"] == 24


def test_command_refuses_a_cpu():
    """The real command, in a process of its own: no TPU, so a non-zero
    exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run_cell.py"),
         "--workload", "train-large-t1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    assert not p.stdout.strip().splitlines()[-1].startswith("{")
