"""On the chip: the routed layer's static prefix (ops/moe.py).

The LFM2 cell's own train step, compiled and never run: its grouped
products keep the library's instruction names (`%gmm.N`, `%tgmm.N`)
outside every conditional, which is what the readers of a device trace
tell them by (PERF.md, PRs 39 and 43). And the layer itself through the
Mosaic kernels at LFM2's widths: with the prefix on, the held rows under
the bound, at it and past it (the overflow entered, its products the
compiler's own `ragged_dot`), output and gradients agree with the
full-width layer's to the limit tests/test_moe_grad.py holds the
interpreted kernel to; and with tokens that hold none, one, two and all four
of their rows here. To the bit on the CPU (tests/test_moe_grad.py); on the
chip the two layers are two programs (the prefix path adds a token's weighed
choices in their order inside one pass, `moe._sum_choices`; the full-width
layer rounds each product to float32, writes it out and reduces
`[N, k, E]`, whose terms the compiler pairs), and a few outputs in a million
come out one bfloat16 rounding apart: 12 of 6.3 million where a token holds
at most one row, 37 of a million where it holds two (PR 48's runs)."""

import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.engine import TrainEngine
from distributedtraining_tpu.models import lfm2_moe
from distributedtraining_tpu.ops import moe

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tests"))
from test_moe_grad import (  # noqa: E402
    _hold, mosaic_calls_outside_conditionals)

TOL = 0.06          # tests/test_moe_grad.py's, for `gmm` on bfloat16


def test_the_cells_step_keeps_the_kernels_names_outside_conditionals():
    cfg = dataclasses.replace(
        lfm2_moe.PRESETS["lfm2-8b-a1b-l5-e8-v16k"], remat=True)
    model, _ = lfm2_moe.make_model(cfg)
    engine = TrainEngine(model)
    batch = {k: jax.ShapeDtypeStruct((2, 8192), jnp.int32)
             for k in ("input_ids", "segment_ids", "position_ids")}
    batch["loss_mask"] = jax.ShapeDtypeStruct((2, 8192), jnp.float32)
    text = engine.train_step.lower(engine.abstract_state(),
                                   batch).compile().as_text()
    outside = mosaic_calls_outside_conditionals(text)
    # 4 routed layers: forward, re-run and the two transposed products;
    # the two weight gradients
    assert sum(bool(re.fullmatch(r"%?gmm(\.\d+)?", n))
               for n in outside) >= 24
    assert sum(bool(re.fullmatch(r"%?tgmm(\.\d+)?", n))
               for n in outside) >= 8
    # and no grouped product under another name out there
    assert not [n for n in outside if "gmm" in n and not re.fullmatch(
        r"%?t?gmm(\.\d+)?", n)]
    # the overflow's products are there, in the branches: the compiler's
    # own grouped product, under its own name
    assert "%ragged-dot" in text


@pytest.mark.parametrize("rows", [
    pytest.param(None, id="as-routed"), pytest.param(4000, id="under"),
    pytest.param(5120, id="at"), pytest.param(9000, id="past"),
    pytest.param("mixed", id="mixed-counts")])
def test_prefix_on_is_the_full_width_layer_on_the_chip(rows):
    """LFM2's widths, 4,096 tokens x 4, 8 of 32 held: a prefix of 5,120 of
    the 16,384 sorted rows. `mixed`: 0, 1, 2 and 4 held rows a token by
    turns (tests/test_moe_grad.py's `HOLDS`), 4,608 in all."""
    N, E, F, k, held, router = 4096, 2048, 1792, 4, (8, 8), 32
    assert moe.prefix_rows(N * k, held[1], router) == 5120
    rng = np.random.default_rng(43)
    h = jnp.asarray(rng.standard_normal((N, E)), jnp.bfloat16)
    w_r = jnp.asarray(rng.standard_normal((E, router)) * 0.02, jnp.float32)
    w_in = jnp.asarray(rng.standard_normal((8, E, 2 * F)) * 0.02,
                       jnp.bfloat16)
    w_down = jnp.asarray(rng.standard_normal((8, F, E)) * 0.02, jnp.bfloat16)
    target = jnp.asarray(rng.standard_normal((N, E)), jnp.float32)
    choice = _hold(moe.route(h, w_r, jnp.zeros((router,)), k, 1.0)[0], held,
                   rows)

    def run(router_experts):
        def loss(h, w_r, w_in, w_down):
            _, weights = moe.route(h, w_r, jnp.zeros((router,)), k, 1.0)
            out, stats = moe.routed_experts(
                h, choice, weights, w_in, w_down, held=held,
                router_experts=router_experts)
            return jnp.sum(out.astype(jnp.float32) * target), (out, stats)
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3), has_aux=True))(
            h, w_r, w_in, w_down)

    ((wide_loss, (wide, _)), wide_grads) = run(None)
    ((loss, (out, stats)), grads) = run(router)
    held_rows = int(jnp.sum((choice >= 8) & (choice < 16)))
    assert int(stats["moe_rows"]) == held_rows == {
        None: held_rows, "mixed": 4608}.get(rows, rows)
    assert int(stats["moe_rows_past_prefix"]) == max(held_rows - 5120, 0)
    assert int(stats["moe_layers_past_prefix"]) == (held_rows > 5120)
    out, wide = np.asarray(out, np.float32), np.asarray(wide, np.float32)
    print(f"rows held {held_rows}: output bit-equal {(out == wide).all()}, "
          f"widest gap {np.abs(out - wide).max() / np.abs(wide).max():.2e} "
          "of the widest element")
    if rows == "mixed":
        # a rounding now and then, never a row: measured 2e-6 .. 4e-5 of
        # the elements by the rows a token holds
        assert np.mean(out != wide) <= 1e-3
    assert np.abs(out - wide).max() <= TOL * np.abs(wide).max()
    assert abs(float(loss) - float(wide_loss)) <= TOL * float(
        np.abs(wide * np.asarray(target)).sum()) / np.sqrt(wide.size)
    for g, w in zip(grads, wide_grads):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all()
        assert np.abs(g - w).max() <= TOL * np.abs(w).max()
