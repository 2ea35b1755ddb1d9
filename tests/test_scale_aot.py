"""AOT compile + HBM budget for BASELINE configs 4/5 (scripts/scale_aot.py).

Runs the real artifact generator as a subprocess (it owns its own device
count / platform setup) and asserts both target configs compile on their
pod-shaped virtual meshes AND fit the per-chip HBM budgets. This is the
round-5 upgrade of validate_7b_worker's shape-level checks: buffer
assignment catches collective layouts, GSPMD resharding, and actual
per-device argument/temp sizes that jax.eval_shape cannot."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_scale_aot_configs_fit(tmp_path):
    out = tmp_path / "scale.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # the script sets its own xla_force_host_platform_device_count
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "scale_aot.py"),
         "--out", str(out)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["all_fit"] is True
    by_name = {c["config"]: c for c in rec["configs"]}
    c4 = by_name["BASELINE config 4"]
    assert c4["devices"] == 32 and c4["per_device"]["fits"]
    assert 6.5e9 < c4["n_params"] < 7.5e9
    c5 = by_name["BASELINE config 5"]
    assert c5["devices"] == 64 and c5["per_device"]["fits"]
    assert 7.5e9 < c5["n_params"] < 8.5e9
    # the budgets are the real chips': v4 32 GiB, v5e 16 GiB
    assert c4["per_device"]["hbm_budget_gib"] == 32
    assert c5["per_device"]["hbm_budget_gib"] == 16
