"""chip_smoke.py on the CPU lane: its legs at tiny size, and its refusal.

The smoke itself runs on the chip at the full width of gpt2-124m
(``python chip_smoke.py`` through the chip tool). Here the SAME functions
drive the role mains at the ``tiny`` preset — every check except the ones
only a TPU can pass (device platform, Mosaic custom calls) — and the
unmodified command must exit non-zero because this lane has no TPU.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_flight():
    yield
    from distributedtraining_tpu.utils import flight
    flight.reset()


def test_legs_at_tiny_size(tmp_path):
    prompts = [[3, 1, 4, 1, 5], list(range(1, 21))]
    report = chip_smoke.run(
        model="tiny", seq_len=32, eval_seq_len=32, batch_size=8, steps=12,
        prompts=prompts, max_new=4, work_dir=str(tmp_path / "work"),
        expect_kernels=False)
    # eight virtual devices: the mesh legs run too (dp=8, fsdp=2 x tp=2)
    miners = report["fleet"]["miners"]
    assert miners["hotkey_0"]["mesh"]["dp"] == 8
    assert (miners["hotkey_1"]["mesh"]["fsdp"],
            miners["hotkey_1"]["mesh"]["tp"]) == (2, 2)
    for m in miners.values():
        assert m["loss_last"] < m["loss_first"]
        assert m["mosaic_calls"] == 0            # CPU: the XLA paths
        assert m["params"]["min_devices"] == 8
    assert report["fleet"]["validator"]["scores"]["hotkey_0"] > 0
    assert report["fleet"]["averager"]["base_revision"]
    assert report["serve"]["requests"] == 2
    assert report["serve"]["reference_match"]
    assert report["serve"]["revision"] == \
        report["fleet"]["averager"]["base_revision"]
    assert report["packer"] in ("native", "python")
    assert not os.path.exists(tmp_path / "work")  # removed on success


def test_unmodified_command_refuses_without_tpu():
    """``python chip_smoke.py`` where JAX finds no TPU: non-zero exit, the
    device named on stderr, and no result line on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "needs a TPU" in proc.stderr and "cpu" in proc.stderr
    assert "platform=cpu" in proc.stdout
    assert '"ok"' not in proc.stdout
