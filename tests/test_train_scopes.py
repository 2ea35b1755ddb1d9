"""The train step names its parts for a device trace, and the names change
nothing it computes.

`jax.named_scope`s (docs/observability.md, "scopes inside device programs")
reach every compiled instruction's `op_name`; the benchmark's
`readers/trace_scope.py` reads device time by them. Held here, on the CPU,
from the lowered-and-compiled tiny steps: every scope of the table is
there, the loss and the optimizer leave nothing under a bare
`jit(train_step)` path beyond a listed handful, remat's re-run is still told
from the backward proper on this JAX (the reader's pass rule), and the
default text of the lowered step, which prints no metadata, is the text it
was before the scopes.
"""

import dataclasses
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from distributedtraining_tpu.engine import TrainEngine
from distributedtraining_tpu.engine.lora_train import LoRAEngine
from distributedtraining_tpu.models import gpt2, lfm2_moe
from distributedtraining_tpu.models import lora as lora_lib

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")

B, T = 2, 32
FAMILIES = {
    "gpt2": (gpt2, "tiny"),
    "lfm2": (lfm2_moe, "tiny-lfm2"),
}
# every scope the family's train step opens (docs/observability.md)
SCOPES = {
    "gpt2": {"train.loss", "train.optimizer", "gpt2.embed", "gpt2.attn",
             "gpt2.mlp", "gpt2.head"},
    "lfm2": {"train.loss", "train.optimizer", "lfm2.embed", "lfm2.conv",
             "lfm2.attn", "lfm2.dense_ffn", "lfm2.moe_ffn", "lfm2.head",
             "moe.route", "moe.experts"},
}
# what remat re-runs: the block's scopes, never the step's ends
BLOCK_SCOPES = {
    "gpt2": {"gpt2.attn", "gpt2.mlp"},
    "lfm2": {"lfm2.conv", "lfm2.attn", "lfm2.dense_ffn", "lfm2.moe_ffn",
             "moe.route", "moe.experts"},
}
# instructions that no scope covers: `state.step + 1`, the sum over layers of
# what the routed layers counted (scalars), and the remat call's own
# plumbing between a block's backward and its re-run
BARE_HANDFUL = re.compile(
    r"^jit\(train_step\)/(add|jvp\(\w+\)/add"
    r"|transpose\(jvp\(\w+\)\)/jvp\(\w+\)/remat2)$")
# default `as_text()` of the lowered step at (B, T): the parent's, from a
# checkout of a55be7f (PR 36: scopes are metadata, which it does not print).
# LFM2's since PR 43: the step returns one counter more
# (`train.moe.rows_past_prefix`); with that entry taken out of
# `lfm2_moe.TRAIN_COUNTERS` the text is a55be7f's still, 4c8cb1fe5ca0a426 /
# a1b01fe76df41b10 (the preset holds all 8 experts: the prefix is all rows).
# And since PR 48 another (`train.moe.layers_past_prefix`); without that
# entry the text is PR 43's, f534e1cc394add95 / 42529a818e51f28d
LOWERED = {
    ("gpt2", False): "acc609dceb884f17", ("gpt2", True): "46c28f222224b48a",
    ("lfm2", False): "d0c574f63a036ac4", ("lfm2", True): "bfc0e147d9803eb4",
}


@pytest.fixture(scope="module")
def reader():
    """The benchmark's reader of device time by scope: its scope and pass
    rules are what these names are for."""
    sys.path.insert(0, _BENCH)
    try:
        from readers import trace_scope
        yield trace_scope
    finally:
        sys.path.remove(_BENCH)
        for name in [m for m in sys.modules if m.split(".")[0] == "readers"]:
            del sys.modules[name]


def _batch():
    batch = {k: jax.ShapeDtypeStruct((B, T), jnp.int32)
             for k in ("input_ids", "segment_ids", "position_ids")}
    batch["loss_mask"] = jax.ShapeDtypeStruct((B, T), jnp.float32)
    return batch


def _engine(family: str, remat: bool, **kw) -> TrainEngine:
    mod, preset = FAMILIES[family]
    model, cfg = mod.make_model(preset)
    model = type(model)(dataclasses.replace(cfg, remat=remat))
    return TrainEngine(model, **kw)


def _compiled_op_names(lowered) -> list:
    """The `op_name` of every instruction of the compiled program. The
    persistent compile cache keys a program WITHOUT its metadata, so an
    executable compiled before a scope was added would come back with the
    names it had then: for these compiles the key holds the metadata."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        text = lowered.compile().as_text()
    finally:
        jax.config.update(flag, was)
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.fixture(scope="module")
def op_names():
    """family -> the `op_name`s of the compiled step (remat on), made
    once."""
    made: dict = {}

    def get(family: str) -> list:
        if family not in made:
            eng = _engine(family, True)
            made[family] = _compiled_op_names(
                eng.train_step.lower(eng.abstract_state(), _batch()))
        return made[family]

    return get


@pytest.mark.parametrize("family", list(FAMILIES))
def test_compiled_step_carries_every_scope(reader, op_names, family):
    found = {reader.scope_of(n) for n in op_names(family)} - {None}
    assert found == SCOPES[family]
    # flax's module path stays beneath a scope
    beneath = {"gpt2": "/gpt2.attn/c_attn/", "lfm2": "/lfm2.conv/operator_norm/"}
    assert any(beneath[family] in n for n in op_names(family))


@pytest.mark.parametrize("family", list(FAMILIES))
def test_bare_step_path_holds_a_listed_handful(reader, op_names, family):
    """No instruction of the loss, of the optimizer or under the model's
    path is left without a scope: what still is, is listed."""
    unnamed = {n for n in op_names(family)
               if n.startswith("jit(") and reader.scope_of(n) is None}
    assert unnamed and all(BARE_HANDFUL.match(n) for n in unnamed), \
        sorted(unnamed)[:8]


@pytest.mark.parametrize("family", list(FAMILIES))
def test_remat_rerun_is_told_from_the_backward(reader, op_names, family):
    """`checkpoint/rematted_computation/` against `checkpoint/`, `jvp(..)`
    against `transpose(jvp(..))`: the reader's pass rule, pinned on this
    JAX where it would break."""
    by: dict = {}
    for n in op_names(family):
        scope = reader.scope_of(n)
        if scope is not None:
            by.setdefault(reader.pass_of(n), set()).add(scope)
    assert by["rerun"] == BLOCK_SCOPES[family]
    assert by["backward"] >= BLOCK_SCOPES[family] | {"train.loss"}
    assert by["forward"] == SCOPES[family]
    assert "train.optimizer" not in by["backward"] | by["rerun"]
    assert any("/checkpoint/rematted_computation/" in n
               for n in op_names(family))


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_lowered_text_is_the_parents(family, remat):
    eng = _engine(family, remat)
    text = eng.train_step.lower(eng.abstract_state(), _batch()).as_text()
    assert not any(f"{s}/" in text for s in SCOPES[family])  # metadata only
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        LOWERED[family, remat]


def test_fused_loss_lies_under_train_loss(reader):
    """The fused loss's head product and its scan are the loss proper."""
    eng = _engine("gpt2", False, fused_loss="scan")
    names = _compiled_op_names(
        eng.train_step.lower(eng.abstract_state(), _batch()))
    loss = [n for n in names if reader.scope_of(n) == "train.loss"]
    assert any("while" in n for n in loss)
    assert any(n.endswith("dot_general") for n in loss)
    # the model stopped at the hidden states: no logits' product
    head = [n for n in names if reader.scope_of(n) == "gpt2.head"]
    assert head and not any("bte,ve->btv" in n for n in head)


def test_lora_step_takes_the_same_two_names(reader):
    model, cfg = gpt2.make_model("tiny")
    eng = LoRAEngine(model, lora_lib.LoRAConfig(rank=2))
    base = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    state = jax.eval_shape(
        lambda b: eng.init_state(jax.random.PRNGKey(0), b), base)
    found = {reader.scope_of(n) for n in _compiled_op_names(
        eng.train_step.lower(state, base, _batch()))}
    assert {"train.loss", "train.optimizer", "gpt2.attn"} <= found
