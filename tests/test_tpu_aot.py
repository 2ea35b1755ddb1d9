"""Compile for the chip without one: the DeepSeek-V3 family's two Pallas
kernels at kanana-2's published widths and the decode bucket's shapes, and
the train step's causal attention kernels at the train cell's shape,
through the TPU's own compiler against a DESCRIBED v5e (nothing runs, no
chip is needed). Interpret mode cannot show what this does: Mosaic refused
the first latent kernel here for a 64-lane page slice, which is why the
pool stores the rotary key in a whole lane tile (engine/kv_pool.py).

All such compiles live in this one file: only the worker that runs it
loads the TPU's library, inside a fixture, after collection."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """An AOT result cannot be read back from the persistent cache
    without a chip: keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("slots, pages", [(64, 256), (8, 16)])
def test_latent_decode_kernel_compiles_at_published_widths(one_chip, slots,
                                                           pages):
    from distributedtraining_tpu.ops import mla_attention as mla

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = 1 + slots * pages
    args = (sds((slots, 1, 32, 512)), sds((slots, 1, 32, 64)),
            sds((pool, 16, 512)), sds((pool, 16, 128)),
            sds((slots, pages), jnp.int32), sds((slots,), jnp.int32),
            sds((slots, 1, 512)), sds((slots, 1, 64)))
    assert mla.kernel_supports(*args[:4])
    compiled = _compile(
        lambda *a: mla.mla_decode_attention(*a, 192 ** -0.5), *args)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "mla_decode_attention" in text
    # the pool is read as it lies: nothing pool-sized is made
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 24


@pytest.mark.parametrize("rows", [48, 384, 12288])
def test_grouped_expert_product_compiles_at_published_widths(one_chip, rows,
                                                             monkeypatch):
    from distributedtraining_tpu.ops import moe
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        lambda x, gu, d, s: moe._experts_sorted(x, gu, d, s, None),
        sds((rows, 2048)), sds((128, 2048, 1536)), sds((128, 768, 2048)),
        sds((128,), jnp.int32))
    text = compiled.as_text()
    # gate+up fused and down: two Mosaic calls, named for the trace reader
    assert text.count("tpu_custom_call") == 2
    assert "%gmm" in text


@pytest.mark.parametrize("rows", [1408, 22528])
def test_latent_expert_product_compiles_at_published_widths(one_chip, rows,
                                                            monkeypatch):
    """Nemotron-3-Super's latent experts (1024 -> 2688 -> 1024, squared
    ReLU), the 128 held of 512: 2,688 is 21 lane tiles, which neither
    1,024 nor 512 divides, so the product's n- and k-tiles are 896."""
    from distributedtraining_tpu.ops import moe
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    assert moe._lane_tiles(2688, 1024) == moe._lane_tiles(2688, 2048) == 896
    assert moe._lane_tiles(1536, 1024) == 768      # kanana's takes 512

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        lambda x, up, d, s: moe._experts_sorted(x, up, d, s, None),
        sds((rows, 1024)), sds((128, 1024, 2688)), sds((128, 2688, 1024)),
        sds((128,), jnp.int32))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 and "%gmm" in text


@pytest.mark.parametrize("impl, calls", [("kernel", 1), ("xla", 0)])
@pytest.mark.parametrize("slots", [64, 8])
def test_state_update_compiles_in_place_at_published_widths(one_chip, slots,
                                                            impl, calls):
    """`ops/ssm.ssm_decode_update` over a 64-slot pool of Nemotron-3-Super's
    states (128 heads x 64 x 128 float32, 8 groups): Mosaic takes the
    kernel, and neither it nor its XLA twin holds a temporary the size of
    the pool (the donated pool is the result's buffer)."""
    from distributedtraining_tpu.ops import ssm

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((65, 128, 64, 128))
    assert ssm.kernel_supports(pool, 8)
    compiled = jax.jit(
        lambda *a: ssm.ssm_decode_update(*a, impl=impl), donate_argnums=(0,)
    ).trace(pool, sds((slots,), jnp.int32),
            sds((slots, 128, 64), jnp.bfloat16), sds((slots, 128)),
            sds((128,)), sds((slots, 8, 128), jnp.bfloat16),
            sds((slots, 8, 128), jnp.bfloat16), sds((128,))
            ).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == calls
    # the instruction's own name, which the device-trace reader matches
    assert bool(re.search(r"%ssm_decode_update(\.\d+)? = ", text)) \
        == bool(calls)
    mem = compiled.memory_analysis()
    pool_bytes = 65 * 128 * 64 * 128 * 4
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 16


@pytest.mark.parametrize("impl, calls", [("kernel", 1), ("xla", 0)])
@pytest.mark.parametrize("slots", [64, 8])
def test_delta_rule_update_compiles_in_place_at_published_widths(
        one_chip, slots, impl, calls):
    """`ops/delta_rule.gdn_decode_update` over a 64-slot pool of
    GigaChat3.5's states (64 value heads x 128 x 128 float32 over 32 key
    heads, 16 value heads a block): Mosaic takes the kernel, and neither
    it nor its XLA twin holds a temporary the size of the pool."""
    from distributedtraining_tpu.ops import delta_rule

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((65, 64, 128, 128))
    assert delta_rule.kernel_supports(pool, 32)
    compiled = jax.jit(
        lambda *a: delta_rule.gdn_decode_update(*a, impl=impl),
        donate_argnums=(0,)
    ).trace(pool, sds((slots,), jnp.int32), sds((slots, 32, 128)),
            sds((slots, 32, 128)), sds((slots, 64, 128)), sds((slots, 64)),
            sds((slots, 64)), sds((slots,), jnp.bool_)
            ).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == calls
    # the instruction's own name, which the device-trace reader matches
    assert bool(re.search(r"%gdn_decode_update(\.\d+)? = ", text)) \
        == bool(calls)
    mem = compiled.memory_analysis()
    pool_bytes = 65 * 64 * 128 * 128 * 4
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 16


def test_latent_decode_kernel_compiles_at_64_heads_and_a_yarn_scale(
        one_chip):
    """The latent decode kernel at GigaChat3.5's 64 heads (kanana-2: 32)
    and a softmax scale that carries YaRN's `mscale^2`."""
    from distributedtraining_tpu.models import gigachat3_5 as gc
    from distributedtraining_tpu.ops import mla_attention as mla

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    slots, pages = 64, 256
    pool = 1 + slots * pages
    args = (sds((slots, 1, 64, 512)), sds((slots, 1, 64, 64)),
            sds((pool, 16, 512)), sds((pool, 16, 128)),
            sds((slots, pages), jnp.int32), sds((slots,), jnp.int32),
            sds((slots, 1, 512)), sds((slots, 1, 64)))
    assert mla.kernel_supports(*args[:4])
    scale = gc.PRESETS["gigachat3.5-432b-a28b-l5-e16-v16k"].softmax_scale
    compiled = _compile(
        lambda *a: mla.mla_decode_attention(*a, scale), *args)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "mla_decode_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 24


@pytest.mark.parametrize("packed", [True, False])
def test_causal_attention_gradient_compiles_at_the_train_cells_shape(
        one_chip, monkeypatch, packed):
    """`train-large-t1024`: [4, 1024, 20, 64] bfloat16, packed documents.
    The gradient holds two Mosaic calls (the forward that keeps its
    log-sum-exp, and ONE fused backward), and the softmax statistics never
    lie in HBM broadcast 128 or more lanes wide per (row, head)."""
    from distributedtraining_tpu.ops import flash_attention as fl
    monkeypatch.setattr(fl, "_on_tpu", lambda: True)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, w, seg):
        out = fl.flash_attention(q, k, v, segment_ids=seg)
        return jnp.sum(out.astype(jnp.float32) * w)

    qkv = sds((4, 1024, 20, 64))
    seg = sds((4, 1024), jnp.int32) if packed else None
    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv,
                        sds((4, 1024, 20, 64), jnp.float32), seg)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    tag = "_segmented" if packed else ""
    # the names the device-trace readers find the kernels by
    assert f"%flash_mha_fwd{tag}_residuals" in text
    assert f"%flash_mha_dkv{tag}_no_residuals" in text
    wide = re.findall(r"= f32\[4,20,1024,(\d+)\]\S* broadcast\(", text)
    assert not [n for n in wide if int(n) >= 128], wide
    # forward alone (the validator's eval, remat's first pass): one call
    fwd = _compile(lambda q, k, v, seg: fl.flash_attention(
        q, k, v, segment_ids=seg), qkv, qkv, qkv, seg)
    assert fwd.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("cell", ["train-large-t1024", "train-lfm2-t8192"])
def test_rows_major_attention_gradient_compiles_with_nothing_heads_first(
        one_chip, monkeypatch, cell):
    """The two train cells' attention as their blocks call it: GPT-2's
    fused `[4, 1024, 3 x 1280]` through `flash_attention_qkv`, LFM2's
    `[2, 8192, 32, 64]` through `flash_attention`. Mosaic takes the static
    64-lane slices of a 128-lane block (two heads a block), the gradient
    holds the two kernels under the names the trace readers know, and no
    array of the compiled program lies heads first or carries a statistic
    128 lanes wide."""
    from distributedtraining_tpu.ops import flash_attention as fl
    monkeypatch.setattr(fl, "_on_tpu", lambda: True)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if cell == "train-large-t1024":
        B, T, H, D = 4, 1024, 20, 64

        def loss(qkv, w, seg):
            out = fl.flash_attention_qkv(qkv, H, segment_ids=seg)
            return jnp.sum(out.astype(jnp.float32) * w)
        args = (sds((B, T, 3 * H * D)), sds((B, T, H * D), jnp.float32))
        wrt = 0
    else:
        B, T, H, D = 2, 8192, 32, 64

        def loss(q, k, v, w, seg):
            out = fl.flash_attention(q, k, v, segment_ids=seg)
            return jnp.sum(out.astype(jnp.float32) * w)
        args = (sds((B, T, H, D)),) * 3 + (sds((B, T, H, D), jnp.float32),)
        wrt = (0, 1, 2)
    text = _compile(jax.grad(loss, argnums=wrt), *args,
                    sds((B, T), jnp.int32)).as_text()
    assert text.count("tpu_custom_call") == 2
    assert "%flash_mha_fwd_segmented_residuals" in text
    assert "%flash_mha_dkv_segmented_no_residuals" in text
    assert not re.findall(rf"\[{B},{H},{T},(?:{D}|128)\]", text)


@pytest.mark.parametrize("tokens", [16384, 1024])
def test_held_expert_layer_gradient_compiles_at_published_widths(
        one_chip, monkeypatch, tokens):
    """`train-lfm2-t8192`: LFM2-8B-A1B's routed layer (2,048 -> 2 x 1,792 ->
    2,048, 4 of 32 a token, 8 held) under `jax.grad`, 16,384 tokens a step
    (and a serve prefill's 1,024): two grouped products forward, two on
    the transposed stacks and two `tgmm` backward, all within Mosaic's 16
    MiB of VMEM at the one tiling `ops/moe.py` has."""
    from distributedtraining_tpu.ops import moe
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(h, w_r, bias, w_in, w_down, target):
        choice, weights = moe.route(h, w_r, bias, 4, 1.0, True, 1e-6)
        out, _ = moe.routed_experts(h, choice, weights, w_in, w_down,
                                    held=(0, 8))
        return jnp.sum(out.astype(jnp.float32) * target)

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 3, 4)), sds((tokens, 2048)),
        sds((2048, 32), jnp.float32), sds((32,), jnp.float32),
        sds((8, 2048, 3584)), sds((8, 1792, 2048)),
        sds((tokens, 2048), jnp.float32))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 6
    assert "%gmm" in text and "%tgmm" in text


def test_prefix_paths_kernels_keep_their_names_outside_every_conditional(
        one_chip, monkeypatch):
    """`train-lfm2-t8192`'s routed layer with the router's expert count
    stated (8 of 32 held: 20,480 of the 65,536 sorted rows), under remat
    and `jax.grad` as a block of the step holds it. A device trace's
    readers tell a grouped product by its instruction's OWN name, and the
    compiler takes that name from the innermost wrapper of the call's
    `op_name`: `gmm` / `tgmm` at the top level, `jvp_jit_gmm__` for a call
    differentiated inside a `cond` (or by a `jax.vjp` of the layer's own).
    The prefix path's eight calls stand at the top level under the
    library's names; the overflow's products, in the conditionals'
    branches, are the compiler's own (`ragged_dot`), so the step holds no
    second set of Pallas kernels."""
    from test_moe_grad import mosaic_calls_outside_conditionals

    from distributedtraining_tpu.ops import moe
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    @jax.checkpoint
    def layer(h, w_r, bias, w_in, w_down):
        choice, weights = moe.route(h, w_r, bias, 4, 1.0, True, 1e-6)
        return moe.routed_experts(h, choice, weights, w_in, w_down,
                                  held=(0, 8), router_experts=32)[0]

    def loss(h, w_r, bias, w_in, w_down, target):
        return jnp.sum(layer(h, w_r, bias, w_in, w_down).astype(jnp.float32)
                       * target)

    assert moe.prefix_rows(16384 * 4, 8, 32) == 20480
    text = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 3, 4)), sds((16384, 2048)),
        sds((2048, 32), jnp.float32), sds((32,), jnp.float32),
        sds((8, 2048, 3584)), sds((8, 1792, 2048)),
        sds((16384, 2048), jnp.float32)).as_text()
    outside = mosaic_calls_outside_conditionals(text)
    # forward, remat's re-run, the two transposed products; the two tgmm
    assert sum(bool(re.fullmatch(r"%gmm(\.\d+)?", n)) for n in outside) == 6
    assert sum(bool(re.fullmatch(r"%tgmm(\.\d+)?", n)) for n in outside) == 2
    assert len(outside) == 8
    # the overflow: 2 products in the forward's branch, 2 + 4 in the
    # backward's, none of them the library's kernel
    assert len(re.findall(r"%ragged-dot[\w.\-]* = \w+\[", text)) >= 2 + 6
    assert len(re.findall(r"%\w*gmm[\w.]* = ", text)) == 8
    assert "[65536,3584]" not in text.split("ENTRY")[1].split("\n}")[0]


def test_prefix_paths_step_makes_no_array_of_all_the_sorted_rows(
        one_chip, monkeypatch):
    """`train-lfm2-t8192`'s whole step (batch 2 x 8,192, remat, the role's
    AdamW) for a described v5e: the un-sort and the dispatch's backward go
    by token, so the top level of the compiled step (what runs whether or
    not a layer overflows) holds none of the full-width layer's arrays of
    all 65,536 sorted rows, in either tiling; and the grouped products are
    where the readers look for them, 6 `gmm` and 2 `tgmm` a routed layer
    under the library's names outside every conditional."""
    import dataclasses

    from test_moe_grad import mosaic_calls_outside_conditionals

    from distributedtraining_tpu.engine import TrainEngine
    from distributedtraining_tpu.models import lfm2_moe
    from distributedtraining_tpu.ops import flash_attention, moe
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    model, _ = lfm2_moe.make_model(dataclasses.replace(
        lfm2_moe.PRESETS["lfm2-8b-a1b-l5-e8-v16k"], remat=True))
    engine = TrainEngine(model)

    def placed(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    batch = {k: placed(jax.ShapeDtypeStruct((2, 8192), jnp.int32))
             for k in ("input_ids", "segment_ids", "position_ids")}
    batch["loss_mask"] = placed(jax.ShapeDtypeStruct((2, 8192), jnp.float32))
    text = engine.train_step.__wrapped__.trace(
        jax.tree_util.tree_map(placed, engine.abstract_state()),
        batch).lower(lowering_platforms=("tpu",)).compile().as_text()
    top = text.split("ENTRY")[1].split("\n}")[0]
    assert "[20480,2048]" in top
    for full_width in ("[65536,2048]", "[4,16384,2048]", "[16384,4,2048]"):
        assert full_width not in top, full_width
    outside = mosaic_calls_outside_conditionals(text)
    assert sum(bool(re.fullmatch(r"%gmm(\.\d+)?", n)) for n in outside) == 24
    assert sum(bool(re.fullmatch(r"%tgmm(\.\d+)?", n)) for n in outside) == 8


@pytest.mark.parametrize("preset, mosaic_calls", [("gpt2-774m", 36),
                                                  ("gpt2-1.5b", 0)])
def test_decode_program_reads_each_matrix_in_the_dtype_it_multiplies_in(
        one_chip, monkeypatch, preset, mosaic_calls):
    """`serve-large-chat` / `serve-xl-chat`, the 8-slot x 16-page decode
    program over the serving tree (engine/serve_weights.py): the only
    float32 matrices among its arguments are the lookup's two tables; no
    matrix is converted to bfloat16 inside the step; the weights'
    argument bytes are at most 0.6 of the float32 base's; the paged
    kernel runs once a layer where it ran (heads of 64 x 20 = 1280
    lanes) and nowhere where it did not (25 x 64 = 1600). What stays,
    and is held here so that the PR that removes it sees this fail: the
    TPU lays a table 1,600 wide (no multiple of 128 lanes) column-major,
    the head reads it as it lies, and the LOOKUP copies all of
    `f32[50304,1600]` to gather 8 rows of it, on every step (PERF.md,
    PR 30: the copy is the lookup's, not the head's)."""
    from distributedtraining_tpu.engine import serve, serve_weights
    from distributedtraining_tpu.models import gpt2
    from distributedtraining_tpu.ops import paged_attention as pa
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    slots, pages, P = 8, 16, 16

    model, cfg = gpt2.make_model(gpt2.PRESETS[preset])
    base = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0))))
    tree = serve_weights.abstract(cfg, base)
    eng = serve.GenerationEngine(model, None, max_slots=slots, page_size=P,
                                 max_seq_len=1024)
    try:
        eng._layers, eng._donate = serve._layer_keys(base), True

        def sds(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        half = (sds((eng.pool_pages, P, cfg.n_embd), jnp.bfloat16),
                ) * cfg.n_layer
        compiled = eng._decode_prog(slots, pages).__wrapped__.trace(
            tree, half, half, sds((slots, pages)), sds((slots,)),
            sds((slots,))).lower(lowering_platforms=("tpu",)).compile()
    finally:
        eng.close()
    text = compiled.as_text()
    entry = text[text.index("ENTRY "):]
    f32_matrices = set(re.findall(
        r"= f32\[(\d+),(\d+)\]\S* parameter\(", entry))
    V, E = str(cfg.padded_vocab), str(cfg.n_embd)
    assert f32_matrices == {(V, E), (str(cfg.n_positions), E)}
    big = [m for m in re.findall(r"= bf16\[(\d+),(\d+)\]\S* convert\(", text)
           if int(m[0]) * int(m[1]) >= 2 ** 20]
    assert not big, big
    pool_bytes = 2 * cfg.n_layer * eng.pool_pages * P * cfg.n_embd * 2
    base_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(base))
    weights = compiled.memory_analysis().argument_size_in_bytes - pool_bytes
    assert 0.5 * base_bytes < weights <= 0.6 * base_bytes
    assert text.count("tpu_custom_call") == mosaic_calls
    table_copies = re.findall(rf"= f32\[{V},{E}\]\S* copy\(", text)
    assert len(table_copies) == (int(E) % 128 != 0)


@pytest.mark.parametrize("impl, calls", [("kernel", 1), ("xla", 0)])
@pytest.mark.parametrize("slots", [64, 8])
def test_channel_delta_rule_update_compiles_in_place_at_published_widths(
        one_chip, slots, impl, calls):
    """`ops/delta_rule.gdn_decode_update` with a decay a key CHANNEL over a
    64-slot pool of Solar-Open2's states (64 heads x 128 x 128 float32,
    as many key heads, `g` of [slots, 64, 128]): the one kernel takes the
    decay as a `[dk, heads]` block where the scalar gate's is `[1,
    heads]`, Mosaic takes it, and neither it nor its XLA twin holds a
    temporary the size of the pool."""
    from distributedtraining_tpu.ops import delta_rule

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((65, 64, 128, 128))
    assert delta_rule.kernel_supports(pool, 64)
    compiled = jax.jit(
        lambda *a: delta_rule.gdn_decode_update(*a, impl=impl),
        donate_argnums=(0,)
    ).trace(pool, sds((slots,), jnp.int32), sds((slots, 64, 128)),
            sds((slots, 64, 128)), sds((slots, 64, 128)),
            sds((slots, 64, 128)), sds((slots, 64)),
            sds((slots,), jnp.bool_)
            ).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == calls
    assert bool(re.search(r"%gdn_decode_update(\.\d+)? = ", text)) \
        == bool(calls)
    mem = compiled.memory_analysis()
    pool_bytes = 65 * 64 * 128 * 128 * 4
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 16


def _solar_programs(one_chip, monkeypatch, cache: bool):
    """The engine of `serve-solar-sessions` over avals: (engine, the
    arguments' avals by name)."""
    from distributedtraining_tpu.engine import kv_pool, serve, serve_weights
    from distributedtraining_tpu.models import solar_open2 as so
    from distributedtraining_tpu.ops import delta_rule, moe, paged_attention
    for module in (delta_rule, paged_attention, moe):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    slots, P, pool_pages = 64, 16, 73728
    model, cfg = so.make_model("solar-open2-250b-l4-e40-v24k")

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    base = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0))))
    eng = serve.GenerationEngine(
        model, None, max_slots=slots, page_size=P, pool_pages=pool_pages,
        max_seq_len=40960, prefix_cache=cache, snapshot_rows=96,
        prefill_chunk=1024)
    eng._layers, eng._donate = serve._layer_keys(base), True
    halves = tuple((sds((pool_pages, P, w), jnp.bfloat16),)
                   for w in kv_pool.row_widths(cfg))
    state = (tuple(sds((slots + 1, 64, 128, 128), jnp.float32)
                   for _ in range(3)),
             tuple(sds((slots + 1, 3, 24576), jnp.bfloat16)
                   for _ in range(3)))
    return eng, serve_weights.abstract(cfg, base), halves, state, sds


@pytest.mark.parametrize("cache", [True, False])
def test_solar_decode_program_compiles_at_the_sessions_cells_size(
        one_chip, monkeypatch, cache):
    """The largest decode program of `serve-solar-sessions`: 64 slots x
    2,560 pages (40,960 positions a slot: the paged kernel's
    scalar-prefetched table takes them at page size 16), weights, a 4.5
    GiB page pool and the state pool in place. Its Mosaic calls are the
    cell's `expect_paths`; the prefix cache changes no decode program."""
    eng, tree, halves, state, sds = _solar_programs(one_chip, monkeypatch,
                                                    cache)
    try:
        compiled = eng._decode_prog(64, 2560).__wrapped__.trace(
            tree, *halves, sds((64, 2560)), sds((64,)), sds((64,)), *state,
            sds((64,))).lower(lowering_platforms=("tpu",)).compile()
    finally:
        eng.close()
    own = [ln.split(" = ")[0].strip() for ln in compiled.as_text(
        ).splitlines() if "tpu_custom_call" in ln]
    calls = {k: sum(bool(re.fullmatch(rf"%?{k}(\.\d+)?", n)) for n in own)
             for k in ("gdn_decode_update", "paged_decode_attention", "gmm")}
    assert calls == {"gdn_decode_update": 3, "paged_decode_attention": 1,
                     "gmm": 8}
    m = compiled.memory_analysis()
    # pools updated in place: no temporary the size of either
    assert m.temp_size_in_bytes < 2 ** 28
    assert m.argument_size_in_bytes < 11.6 * 2 ** 30


def test_solar_suffix_prefill_compiles_with_no_context_wide_scores(
        one_chip, monkeypatch):
    """The largest suffix-prefill program: 1,024 fresh tokens over a table
    of 2,560 pages, continuing from the slot's state row. The attention
    layer attends the paged context in blocks: no [heads, 1024, 40960]
    tensor (10 GiB), under half a GiB of temporaries in all; the experts'
    grouped products are Mosaic's."""
    eng, tree, halves, state, sds = _solar_programs(one_chip, monkeypatch,
                                                    True)
    try:
        compiled = eng._prefill_ctx_prog(1024, 2560).__wrapped__.trace(
            tree, sds((1, 1024)), sds(()), sds(()), *halves,
            sds((1, 2560)), *state, sds(())
        ).lower(lowering_platforms=("tpu",)).compile()
    finally:
        eng.close()
    text = compiled.as_text()
    # no array as long as the table's reach in any dimension
    assert not re.findall(r"\[(?:\d+,)*40960(?:,\d+)*\]", text)
    assert text.count("tpu_custom_call") == 8
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 29


def test_paged_decode_kernel_takes_a_table_of_2560_pages_a_slot(one_chip):
    """`paged_decode_attention` at this cell's shape: 64 query / 8 K/V
    heads of 128 (1,024 lanes), 64 slots, 2,560 table entries a slot in
    scalar memory (640 KiB), pages of 16."""
    from distributedtraining_tpu.ops import paged_attention as pa

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((64, 1, 64, 128)), sds((73728, 16, 1024)),
            sds((73728, 16, 1024)), sds((64, 2560), jnp.int32),
            sds((64,), jnp.int32), sds((64, 1, 8, 128)),
            sds((64, 1, 8, 128)))
    assert pa.kernel_supports(args[0], args[1])
    compiled = _compile(pa.paged_decode_attention, *args)
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_paged_decode_kernel_at_gpt2_larges_bucket(one_chip):
    """`serve-large-chat`'s largest decode bucket: 8 rows, a table of 64
    pages of 16, 20 heads of 64 (1,280 lanes, a query group of 1). The
    chunk is 16 pages (256 positions, 640 KiB a buffer); the two K and two
    V buffers stay inside four of `CHUNK_BYTES`, far under the 16 MiB a
    kernel may hold on this chip, and Mosaic takes the whole of it."""
    from distributedtraining_tpu.ops import paged_attention as pa

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((8, 1, 20, 64)), sds((513, 16, 1280)), sds((513, 16, 1280)),
            sds((8, 64), jnp.int32), sds((8,), jnp.int32),
            sds((8, 1, 20, 64)), sds((8, 1, 20, 64)))
    assert pa.kernel_supports(args[0], args[1])
    ppc = pa._chunk_pages(64, 16, 1280, jnp.bfloat16)
    assert ppc == 16 and 4 * ppc * 16 * 1280 * 2 <= 4 * pa.CHUNK_BYTES
    compiled = _compile(pa.paged_decode_attention, *args)
    own = [ln.split(" = ")[0].strip() for ln in compiled.as_text(
        ).splitlines() if "tpu_custom_call" in ln]
    assert len(own) == 1 and re.fullmatch(
        r"%?paged_decode_attention(\.\d+)?", own[0]), own


@pytest.mark.parametrize("window, pages, name", [
    (2048, 136, "paged_window_decode_attention"),
    (None, 2112, "paged_decode_attention")])
def test_windowed_and_plain_decode_kernels_compile_at_32_over_4_heads(
        one_chip, window, pages, name):
    """The two paged decode kernels of `serve-trinity-mixed`: 32 query / 4
    K/V heads of 128 (512 lanes, a query group of 8), 64 slots, pages of
    16; the window group's table 136 entries a slot with the lower bound,
    the global layer's 2,112 without. Each under its own Mosaic name."""
    from distributedtraining_tpu.ops import paged_attention as pa

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = 12417 if window else 65536
    args = (sds((64, 1, 32, 128)), sds((pool, 16, 512)),
            sds((pool, 16, 512)), sds((64, pages), jnp.int32),
            sds((64,), jnp.int32), sds((64, 1, 4, 128)),
            sds((64, 1, 4, 128)))
    assert pa.kernel_supports(args[0], args[1])
    compiled = _compile(
        lambda *a: pa.paged_decode_attention(*a, window=window), *args)
    own = [ln.split(" = ")[0].strip() for ln in compiled.as_text(
        ).splitlines() if "tpu_custom_call" in ln]
    assert len(own) == 1 and re.fullmatch(rf"%?{name}(\.\d+)?", own[0]), own
