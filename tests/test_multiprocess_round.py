"""System test: a real multi-process federated round through the CLIs.

BASELINE.json config 3 at test scale: several miner OS processes train
concurrently against one shared LocalFS work dir, then a validator process
scores them and an averager process merges — all through the actual
``neurons/*.py`` entry points, not in-process loops. This is the test the
reference never had for its de-facto multi-node story (Local* twins,
SURVEY.md §4.1).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(role, *args):
    env = dict(os.environ)
    # concurrent role processes cannot share a chip (one process per
    # chip): this lane is CPU by construction
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)        # no virtual-device forcing needed
    return subprocess.Popen(
        [sys.executable, os.path.join(REPO, "neurons", f"{role}.py"), *args],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


COMMON = ["--backend", "local", "--model", "tiny", "--dataset", "synthetic",
          "--eval-batches", "2"]


def test_three_miners_validator_averager(tmp_path):
    work = str(tmp_path / "run")
    miners = [
        _run("miner", "--work-dir", work, *COMMON,
             "--hotkey", f"hotkey_{i}", "--max-steps", "25",
             "--send-interval", "1e9",        # flush publishes at exit
             "--heartbeat-interval", "5",     # fleet health plane on
             "--checkpoint-interval", "0")
        for i in range(3)
    ]
    for p in miners:
        out, _ = p.communicate(timeout=420)
        assert p.returncode == 0, out[-2000:]
        assert "miner done: steps=25" in out, out[-2000:]

    listing = os.listdir(os.path.join(work, "artifacts", "deltas"))
    deltas = [f for f in listing if f.endswith(".msgpack")]
    assert len(deltas) == 3, listing
    # every artifact ships its meta rider (base revision + the delta_id
    # correlation id, utils/obs.py)...
    riders = [f for f in listing if f.endswith(".meta.json")
              and not f.startswith("__hb__")]
    assert len(riders) == 3, listing
    # ...and every miner heartbeats under the reserved artifact id
    # (transport/base.heartbeat_id — the fleet health plane's channel)
    beats = [f for f in listing if f.startswith("__hb__.miner.")]
    assert len(beats) == 3, listing

    v = _run("validator", "--work-dir", work, *COMMON,
             "--hotkey", "hotkey_91", "--rounds", "1")
    out, _ = v.communicate(timeout=420)
    assert v.returncode == 0, out[-2000:]

    meta = json.load(open(os.path.join(work, "chain", "metagraph.json")))
    emitted = meta["ema_scores"]["hotkey_91"]
    positives = [h for h, s in emitted.items() if s > 0]
    assert set(positives) >= {"hotkey_0", "hotkey_1", "hotkey_2"}, positives

    avg_metrics = os.path.join(work, "averager_metrics.jsonl")
    a = _run("averager", "--work-dir", work, *COMMON,
             "--hotkey", "hotkey_95", "--rounds", "1",
             "--heartbeat-interval", "5",     # runs the FleetMonitor too
             "--metrics-path", avg_metrics,
             "--strategy", "weighted")
    out, _ = a.communicate(timeout=420)
    assert a.returncode == 0, out[-2000:]
    assert "accepted=3" in out, out[-2000:]
    assert os.path.exists(os.path.join(work, "artifacts", "base",
                                       "averaged_model.msgpack"))
    # merged loss is reported finite and below the tiny model's ~6.25 init
    line = [ln for ln in out.splitlines() if "averager done" in ln][-1]
    loss = float(line.rsplit("loss=", 1)[1])
    assert np.isfinite(loss) and loss < 6.2, line

    # the averager's FleetMonitor ledger (via scripts/fleet_report.py)
    # matches its own merge decisions exactly: 3 miners, each 1 published
    # + 1 accepted, heartbeats observed from all three
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import fleet_report
    rep = fleet_report.build_report([avg_metrics])
    for i in range(3):
        node = rep["nodes"][f"miner/hotkey_{i}"]
        assert node["published"] == 1 and node["accepted"] == 1, node
        assert node["declined"] == 0 and node["beats"] >= 1, node
        assert node["pushes"] >= 1, node     # from the heartbeat body
    assert sum(n.get("accepted", 0) for n in rep["nodes"].values()) == 3
