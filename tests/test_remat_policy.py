"""What a remat-wrapped block keeps, read from the train step's jaxpr.

Tracing only, on the CPU: the selection rule is forced on
(`flash_attention._on_tpu`), so the engine's step traces the attention
kernels as `pallas_call` equations and none of them runs. Under a bare
`nn.remat` the forward kernel is called once in the forward and once more
in remat's re-run, only to hand the backward kernel the output and the
`[H, T]` log-sum-exp the first call wrote; under `attention.remat_policy()`
those two stay and the re-run's call is gone before XLA sees the program.
Each preset is the family's tiny one with ONE head of 64 (the kernel's
lane tiling refuses the presets' heads of 16) at T = 256.
"""

import dataclasses

import jax
import numpy as np
import pytest

from distributedtraining_tpu.engine.train import TrainEngine
from distributedtraining_tpu.models import gpt2, lfm2_moe, llama
from distributedtraining_tpu.ops import attention, flash_attention
from distributedtraining_tpu.parallel import MeshConfig, make_mesh

T = 256

# family -> (module, the tiny preset with a head the rule accepts,
#            attention layers)
FAMILIES = {
    "gpt2": (gpt2, dataclasses.replace(
        gpt2.PRESETS["tiny"], n_head=1, n_positions=T), 2),
    "lfm2": (lfm2_moe, dataclasses.replace(
        lfm2_moe.PRESETS["tiny-lfm2"], num_attention_heads=1,
        num_key_value_heads=1), 1),
    "llama": (llama, dataclasses.replace(
        llama.PRESETS["tiny-llama"], n_head=1, n_kv_head=1, max_seq_len=T),
        2),
}


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _step_equations(module, cfg, mesh=None, T=T):
    """Every equation of the engine's own train step over two packed rows."""
    model, _ = module.make_model(cfg)
    engine = TrainEngine(model, mesh=mesh, seq_len=T)
    seg = np.repeat(np.arange(2), T // 2)[None].repeat(2, 0).astype(np.int32)
    batch = {"input_ids": np.zeros((2, T), np.int32), "segment_ids": seg,
             "position_ids": np.tile(np.arange(T // 2, dtype=np.int32),
                                     (2, 2)),
             "loss_mask": np.ones((2, T), np.float32)}
    step = engine.train_step.__wrapped__.trace(engine.abstract_state(), batch)
    return list(_equations(step.jaxpr.jaxpr))


def _remats(eqns):
    return [e for e in eqns if e.primitive.name == "remat2"]


def _kernel_calls(eqns):
    names = [e.params["name"] for e in eqns
             if e.primitive.name == "pallas_call"]
    return (sum("flash_mha_fwd" in n for n in names),
            sum("flash_mha_dkv" in n for n in names))


@pytest.fixture
def kernel_selected(monkeypatch):
    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)


# (remat, the policy in place, scan_blocks) -> forward calls an attention
# layer; the fused backward is one a layer in every case
CASES = {
    "remat_policy": (True, True, False, 1),
    "remat_bare": (True, False, False, 2),
    "no_remat": (False, True, False, 1),
    "remat_policy_scan": (True, True, True, 1),
    "remat_bare_scan": (True, False, True, 2),
}


# Lfm2MoeConfig refuses scan_blocks
@pytest.mark.parametrize("family,case", [
    (f, c) for f in FAMILIES for c in CASES
    if not (f == "lfm2" and CASES[c][2])])
def test_forward_kernel_calls_a_layer(family, case, kernel_selected,
                                      monkeypatch):
    module, cfg, layers = FAMILIES[family]
    remat, policy, scan, forward_a_layer = CASES[case]
    if scan:
        layers = 1      # the scan's body is ONE layer, traced once
    if not policy:
        # the test's own bare nn.remat: what every site spelled before
        monkeypatch.setattr(module, "remat_policy", lambda: None)
    cfg = dataclasses.replace(cfg, remat=remat, scan_blocks=scan)
    eqns = _step_equations(module, cfg)
    assert _kernel_calls(eqns) == (forward_a_layer * layers, layers)
    remats = _remats(eqns)
    assert bool(remats) == remat
    want = attention.remat_policy() if policy else None
    assert all(e.params["policy"] is want for e in remats)


# two heads of 64 (one 128-lane block of `[B, T, H D]`) and rows of three
# blocks of 128: the smallest shape at which `flash_attention`'s rule hands
# the packed rows to this module's own rows-major kernels
LONG = 128 * (flash_attention.TABLE_MIN_BLOCKS + 1)
TWO_HEADS = {
    "gpt2": dict(n_embd=128, n_head=2, n_positions=LONG),
    "lfm2": dict(hidden_size=128, num_attention_heads=2,
                 num_key_value_heads=2),
}


@pytest.mark.parametrize("family", TWO_HEADS)
@pytest.mark.parametrize("case", ["remat_policy", "remat_bare",
                                  "no_remat"])
def test_forward_kernel_calls_a_layer_where_the_pair_list_engages(
        family, case, kernel_selected, monkeypatch):
    """Packed rows long enough for the pair list of their own ids
    (`flash_attention.TABLE_MIN_BLOCKS`) whose heads fill lane blocks run
    this module's kernels, not the library's: its forward rule names `out`
    and the log-sum-exp as the library names its own, and the policy keeps
    them, so the re-run calls no kernel."""
    module, cfg, layers = FAMILIES[family]
    remat, policy, _, forward_a_layer = CASES[case]
    if not policy:
        monkeypatch.setattr(module, "remat_policy", lambda: None)
    cfg = dataclasses.replace(cfg, remat=remat, **TWO_HEADS[family])
    eqns = _step_equations(module, cfg, T=LONG)
    assert _kernel_calls(eqns) == (forward_a_layer * layers, layers)
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert all(e.params["grid_mapping"].num_dynamic_grid_bounds == 1
               for e in kernels)
    named = [e.params["name"] for e in eqns if e.primitive.name == "name"]
    assert set(named) == {flash_attention.RESIDUAL_NAME,
                          flash_attention.PAIRS_NAME}
    if policy:
        # (out, log-sum-exp) and the backward's pair list (four arrays and
        # their count), in the forward alone
        assert named.count(flash_attention.RESIDUAL_NAME) == 2 * layers
        assert named.count(flash_attention.PAIRS_NAME) == 5 * layers


@pytest.mark.parametrize("axes,forward_a_layer", [
    (dict(dp=2), 1), (dict(fsdp=2, tp=2), 1), (dict(sp=2), 0)])
def test_the_name_is_inside_the_shard_map_and_the_policy_outside(
        axes, forward_a_layer, kernel_selected):
    """Under a mesh the kernel runs per device inside a `shard_map` inside
    the block: the residuals are named there and kept by the policy around
    the block all the same. A sequence-sharded mesh never selects the
    kernel."""
    module, cfg, layers = FAMILIES["gpt2"]
    cfg = dataclasses.replace(cfg, n_embd=128, n_head=2, remat=True)
    eqns = _step_equations(module, cfg, make_mesh(MeshConfig(**axes)))
    assert _kernel_calls(eqns) == (forward_a_layer * layers,
                                   forward_a_layer * layers)


def test_the_pair_list_is_made_inside_the_shard_map(kernel_selected):
    """Under a mesh the list is each device's own: made from the rows it
    holds, inside the `shard_map`, and the kernels' grid bound with it."""
    module, cfg, layers = FAMILIES["lfm2"]
    cfg = dataclasses.replace(cfg, remat=True, **TWO_HEADS["lfm2"])
    eqns = _step_equations(module, cfg, make_mesh(MeshConfig(dp=2)), T=LONG)
    assert _kernel_calls(eqns) == (layers, layers)
    maps = [e for e in eqns if e.primitive.name == "shard_map"]
    inside = [e for m in maps for e in _equations(m.params["jaxpr"])]
    kernels = [e for e in inside if e.primitive.name == "pallas_call"]
    assert len(kernels) == 2 * layers
    assert all(e.params["grid_mapping"].num_dynamic_grid_bounds == 1
               for e in kernels)
    # one row a device: the list holds a row's triangle
    n = flash_attention.TABLE_MIN_BLOCKS + 1
    assert all(e.invars[1].aval.shape == (n * (n + 1) // 2,)
               for e in kernels)


@pytest.mark.parametrize("axes, rows_major", [
    (dict(dp=2), True),             # the fused array, rows over dp
    (dict(fsdp=2, tp=2), False),    # one head a device: the library's
    (dict(tp=2), False)])
def test_the_fused_projection_reaches_the_kernel_unsplit_on_a_mesh(
        axes, rows_major, kernel_selected):
    """GPT-2's block hands `c_attn`'s `[B, T, 3E]` to the kernels as it
    lies where every device holds all the heads; heads split over `tp`
    (an odd count a device here) take the split entry, heads first."""
    module, cfg, layers = FAMILIES["gpt2"]
    cfg = dataclasses.replace(cfg, remat=True, **TWO_HEADS["gpt2"])
    eqns = _step_equations(module, cfg, make_mesh(MeshConfig(**axes)),
                           T=LONG)
    assert _kernel_calls(eqns) == (layers, layers)
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    fused = [any(v.aval.shape[1:] == (LONG, 3 * cfg.n_embd)
                 for v in e.invars) for e in kernels]
    assert all(fused) if rows_major else not any(fused)


@pytest.mark.parametrize("family", FAMILIES)
def test_nothing_is_named_where_the_rule_says_no(family):
    """On the CPU as it is (no kernel): the step holds no `name` equation,
    so the policy keeps what a bare remat keeps, the block's input."""
    module, cfg, _ = FAMILIES[family]
    eqns = _step_equations(module, dataclasses.replace(cfg, remat=True))
    assert _kernel_calls(eqns) == (0, 0)
    assert not [e for e in eqns if e.primitive.name == "name"]
    assert _remats(eqns)


def test_the_saved_residuals_are_the_kernels_two_outputs(kernel_selected):
    """By shape: `out` [B, H, T, D] in the compute dtype and the
    log-sum-exp [B, H, T] in float32, an attention layer, and nothing else
    carries the name."""
    module, cfg, layers = FAMILIES["gpt2"]
    eqns = _step_equations(module, dataclasses.replace(cfg, remat=True))
    named = [e for e in eqns if e.primitive.name == "name"]
    assert {e.params["name"] for e in named} == {
        flash_attention.RESIDUAL_NAME}
    kept = sorted((tuple(e.outvars[0].aval.shape),
                   str(e.outvars[0].aval.dtype)) for e in named)
    H, D = cfg.n_head, cfg.head_dim
    # one pair a layer, in the forward: the re-run reads them as saved
    assert kept == (layers * [((2, H, T), "float32")]
                    + layers * [((2, H, T, D), cfg.dtype)])


def test_the_three_models_take_one_policy_object():
    policy = attention.remat_policy()
    assert policy is attention.remat_policy() is flash_attention.KEEP_RESIDUALS
    assert (gpt2.remat_policy is llama.remat_policy
            is lfm2_moe.remat_policy is attention.remat_policy)


def test_a_gpt2_block_keeps_the_rows_major_output_and_the_log_sum_exp(
        kernel_selected):
    """Where this module's kernels run, what a remat-wrapped GPT-2 block
    keeps under the name is exactly `out` as `c_proj` reads it,
    `[B, T, E]` in the compute dtype, and the `[B, H, T]` float32
    log-sum-exp, a layer: no heads-first copy, no padded head."""
    module, cfg, layers = FAMILIES["gpt2"]
    cfg = dataclasses.replace(cfg, remat=True, **TWO_HEADS["gpt2"])
    eqns = _step_equations(module, cfg, T=LONG)
    named = [e for e in eqns if e.primitive.name == "name"]
    kept = sorted((tuple(e.outvars[0].aval.shape),
                   str(e.outvars[0].aval.dtype)) for e in named
                  if e.params["name"] == flash_attention.RESIDUAL_NAME)
    assert kept == (layers * [((2, cfg.n_head, LONG), "float32")]
                    + layers * [((2, LONG, cfg.n_embd), cfg.dtype)])
    # beside them, under its own name: the backward's pair list, at most a
    # row's triangle of block pairs in four int32 arrays and their count
    n = LONG // 128
    pairs = [e.outvars[0].aval for e in named
             if e.params["name"] == flash_attention.PAIRS_NAME]
    assert len(pairs) == 5 * layers
    assert {a.shape for a in pairs} == {(), (2 * n * (n + 1) // 2,)}
    assert {str(a.dtype) for a in pairs} == {"int32"}
