"""The plain reference against `models/gpt2.py` at `tiny`, both in float32:
logits of a packed batch, and three AdamW steps through the train engine."""

import dataclasses

import jax
import numpy as np
import pytest

from drivers.program import to_program_tree
from reference import gpt2 as reference

CFG = {"n_embd": 64, "n_layer": 2, "n_head": 4, "vocab_size": 512,
       "padded_vocab": 512, "n_positions": 128, "layer_norm_epsilon": 1e-5}


@pytest.fixture(scope="module")
def model():
    from distributedtraining_tpu.models import gpt2
    return gpt2.make_model(dataclasses.replace(
        gpt2.PRESETS["tiny"], dtype="float32"))[0]


def _batch(seed):
    rng = np.random.default_rng(seed)
    seg = np.zeros((2, 48), np.int32)
    seg[:, 30:] = 1
    pos = np.concatenate([np.arange(30), np.arange(18)])[None].repeat(2, 0)
    mask = np.ones((2, 48), np.float32)
    mask[:, [29, 47]] = 0.0
    return {"input_ids": rng.integers(0, 512, (2, 48)).astype(np.int32),
            "segment_ids": seg, "position_ids": pos.astype(np.int32),
            "loss_mask": mask}


def test_logits_agree(model):
    w = reference.init_weights(CFG, 2**31 + 7)
    b = _batch(0)
    with jax.default_matmul_precision("highest"):
        prog = model.apply({"params": to_program_tree(w)}, b["input_ids"],
                           segment_ids=b["segment_ids"],
                           position_ids=b["position_ids"])
    ref = reference.Reference(CFG).logits(
        w, b["input_ids"], segment_ids=b["segment_ids"],
        position_ids=b["position_ids"])
    np.testing.assert_allclose(np.asarray(prog), np.asarray(ref), atol=2e-5)


def test_three_adamw_steps_agree(model):
    from distributedtraining_tpu.engine import TrainEngine, default_optimizer
    batches = [_batch(i) for i in range(3)]
    eng = TrainEngine(model, optimizer=default_optimizer(5e-4), seq_len=48)
    with jax.default_matmul_precision("highest"):
        st = eng.init_state(params=to_program_tree(
            reference.init_weights(CFG, 5)))
        losses = []
        for b in batches:
            st, m = eng.train_step(st, eng.place_batch(b))
            losses.append(float(m["loss"]))
    ref = reference.train_reference(CFG, 5, batches, lr=5e-4,
                                    weight_decay=0.01)
    np.testing.assert_allclose(losses, ref["losses"], rtol=2e-6)
    got = np.linalg.norm(np.asarray(st.params["h_1"]["c_fc"]["kernel"])
                         - np.asarray(reference.init_weights(CFG, 5)
                                      ["h"][1]["c_fc"]["w"]))
    assert got == pytest.approx(ref["change_norms"]["h.1.c_fc.w"], rel=1e-3)


def test_lower_precisions_move_the_logits():
    w = reference.init_weights(CFG, 3)
    ids = _batch(1)["input_ids"]
    exact = np.asarray(reference.Reference(CFG).logits(w, ids))
    err = {p: float(np.max(np.abs(np.asarray(
        reference.Reference(CFG, p).logits(w, ids)) - exact)))
        for p in ("bfloat16", "fp8")}
    assert 0 < err["bfloat16"] < err["fp8"]
