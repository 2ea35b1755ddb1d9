"""examples/local_round.py must keep running (it is the README's library
quickstart and the shortest end-to-end handle on the public API)."""

import os
import subprocess
import sys


def test_local_round_example():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "examples", "local_round.py")],
        env=env, capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = proc.stdout
    assert "round complete: new base published" in out, out
    assert "validator: base loss" in out and "hotkey_0" in out, out
