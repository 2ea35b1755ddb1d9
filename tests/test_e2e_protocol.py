"""The real-protocol round (scripts/e2e_round.py) as a test.

The committed artifact E2E_r03.json is produced by the full GPT-2-124M
run (~10 min CPU); this test exercises the identical harness — real
checkpoint format, --init-from conversion, files: corpus, word
tokenizer, all three CLIs, the three protocol assertions — at a scale CI
can afford. `-m slow` runs the full 124M spelling here too.

Reference flow being reproduced: /root/reference/neurons/miner.py:54-106.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts.e2e_round import make_hf_checkpoint, run  # noqa: E402


def test_protocol_round_tiny(tmp_path):
    """Checkpoint-boot -> train (loss must drop) -> score (must be > 0)
    -> merge (must publish) on the tiny preset; the run() helper asserts
    all three internally."""
    summary = run(str(tmp_path), steps=12, model="tiny", eval_batches=2)
    assert summary["train_loss_last"] < summary["train_loss_first"]
    assert summary["validator_score_hotkey_0"] > 0
    assert summary["merged_base_published"]


def test_protocol_round_hardened_tiny(tmp_path):
    """The full hardened stack in one round: Ed25519-signed artifacts AND
    int8 compressed wire deltas, through the same three CLIs."""
    summary = run(str(tmp_path), steps=12, model="tiny", eval_batches=2,
                  delta_dtype="int8", signed=True)
    assert summary["validator_score_hotkey_0"] > 0
    assert summary["signed_artifacts"] and summary["delta_dtype"] == "int8"
    # the signed envelope magic really is on the wire artifacts, and the
    # payload really is quantized (an ignored --delta-dtype would publish
    # ~4x these bytes: tiny's f32 delta is ~550 KB)
    from distributedtraining_tpu import signing
    delta_bytes = (tmp_path / "artifacts" / "deltas" /
                   "hotkey_0.msgpack").read_bytes()
    assert signing.is_enveloped(delta_bytes)
    assert summary["delta_artifact_bytes"] < 200_000, \
        summary["delta_artifact_bytes"]


def test_checkpoint_is_idempotent_and_bit_real(tmp_path):
    """The generated checkpoint is a real HF layout (loadable by the
    production converter) and a second call reuses it."""
    from distributedtraining_tpu.models import convert, gpt2

    path = make_hf_checkpoint(str(tmp_path / "ck"), model="tiny")
    mtime = os.path.getmtime(os.path.join(path, "model.safetensors"))
    assert make_hf_checkpoint(str(tmp_path / "ck"), model="tiny") == path
    assert os.path.getmtime(os.path.join(path, "model.safetensors")) == mtime
    params = convert.gpt2_from_hf(path, gpt2.PRESETS["tiny"])
    assert "wte" in params


@pytest.mark.slow   # full 124M protocol round, ~10 min on CPU
def test_protocol_round_gpt2_124m(tmp_path):
    summary = run(str(tmp_path), steps=30, model="gpt2-124m",
                  eval_batches=2)
    assert summary["train_loss_last"] < summary["train_loss_first"]
