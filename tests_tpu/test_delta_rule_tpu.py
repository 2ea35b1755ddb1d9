"""On the chip, at GigaChat3.5's published shapes: the delta-rule decode
kernel against its XLA twin (both in place on a donated pool) with its time
a call beside the states' read and write at the memory's speed, the chunked
WY prefill against the token-by-token recurrence, and the memory report of
a 64-slot state update showing no temporary the size of the pool."""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.ops import delta_rule as dr

HK, HV, DK, DV = 32, 64, 128, 128


def _inputs(key, lead):
    k = jax.random.split(key, 5)
    q = jax.random.normal(k[0], (*lead, HK, DK))
    kk = jax.random.normal(k[1], (*lead, HK, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    kk = kk / jnp.linalg.norm(kk, axis=-1, keepdims=True)
    v = jax.random.normal(k[2], (*lead, HV, DV))
    g = -jnp.exp(jax.random.uniform(k[3], (*lead, HV), minval=-7.0,
                                    maxval=1.1))
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(k[4], (*lead, HV)))
    return q, kk, v, g, beta


def _decode_case(slots, bucket, seed=0):
    key = jax.random.PRNGKey(seed)
    state = 0.1 * jax.random.normal(key, (slots + 1, HV, DK, DV))
    rows = np.full((bucket,), slots, np.int32)
    live = bucket - 3
    rows[:live] = np.random.default_rng(seed).permutation(slots)[:live]
    return (state, jnp.asarray(rows),
            *_inputs(jax.random.fold_in(key, 1), (bucket,)), live)


def test_state_update_kernel_against_its_twin_at_published_shapes():
    state, rows, q, k, v, g, beta, live = _decode_case(64, 64)
    assert dr.kernel_supports(state, HK)
    run = {impl: jax.jit(functools.partial(dr.gdn_decode_update, impl=impl),
                         donate_argnums=(0,)) for impl in ("kernel", "xla")}
    mask = jnp.arange(rows.shape[0]) < live      # the rest is padding
    o_k, s_k = run["kernel"](state + 0.0, rows, q, k, v, g, beta, mask)
    o_x, s_x = run["xla"](state + 0.0, rows, q, k, v, g, beta, mask)
    # the same float32 arithmetic in another order of sums over dk
    assert float(jnp.max(jnp.abs(o_k[:live] - o_x[:live]))) < 2e-4
    used = np.asarray(rows[:live])
    assert float(jnp.max(jnp.abs(s_k[used] - s_x[used]))) < 1e-5
    # a slot the bucket does not name is not touched
    # nor is the row the padding names
    idle = np.setdiff1d(np.arange(65), used)
    assert (np.asarray(s_k[idle]) == np.asarray(state[idle])).all()
    # the rule picks the kernel here
    text = jax.jit(dr.gdn_decode_update).lower(
        state, rows, q, k, v, g, beta, mask).compile().as_text()
    assert "gdn_decode_update" in text
    for impl, fn in run.items():
        s = state + 0.0
        t0 = time.perf_counter()
        for _ in range(20):
            o, s = fn(s, rows, q, k, v, g, beta, mask)
        jax.block_until_ready(s)
        ms = (time.perf_counter() - t0) / 20 * 1e3
        least = 2 * live * HV * DK * DV * 4 / 819e9 * 1e3
        print(f"gdn_decode_update {impl}: {ms:.3f} ms a call, {live} live "
              f"slots; the states' read and write at 819 GB/s: "
              f"{least:.3f} ms")


def test_state_update_holds_no_temporary_the_size_of_the_pool():
    state, rows, q, k, v, g, beta, _ = _decode_case(64, 64)
    for impl in ("kernel", "xla"):
        mem = jax.jit(functools.partial(dr.gdn_decode_update, impl=impl),
                      donate_argnums=(0,)).lower(
            state, rows, q, k, v, g, beta).compile().memory_analysis()
        assert mem.temp_size_in_bytes < state.nbytes // 8, (
            impl, mem.temp_size_in_bytes)
        assert mem.alias_size_in_bytes >= state.nbytes


@pytest.mark.parametrize("T, live", [(256, 256), (1024, 700)])
def test_chunked_prefill_against_the_recurrence(T, live):
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(T), (1, T))
    n = jnp.asarray([live], jnp.int32)
    o_c, s_c = jax.jit(dr.delta_rule_prefill)(q, k, v, g, beta, n)
    o_p, s_p = jax.jit(dr.delta_rule_scan)(q, k, v, g, beta, n)
    scale = float(jnp.max(jnp.abs(o_p[:, :live])))
    assert float(jnp.max(jnp.abs(o_c[:, :live] - o_p[:, :live]))) \
        < 1e-4 * max(scale, 1.0)
    assert float(jnp.max(jnp.abs(s_c - s_p))) < 1e-4
    fn = jax.jit(dr.delta_rule_prefill)
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(fn(q, k, v, g, beta, n))
    print(f"delta_rule_prefill T={T}: "
          f"{(time.perf_counter() - t0) / 5 * 1e3:.2f} ms a call")


# ---------------------------------------------------------------------------
# a decay a key CHANNEL, at Solar-Open2's published shapes (64 key heads)
# ---------------------------------------------------------------------------

def _channel_inputs(key, lead, heads=64):
    k = jax.random.split(key, 5)
    q = jax.random.normal(k[0], (*lead, heads, DK))
    kk = jax.random.normal(k[1], (*lead, heads, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    kk = kk / jnp.linalg.norm(kk, axis=-1, keepdims=True)
    v = jax.random.normal(k[2], (*lead, heads, DV))
    g = -jnp.exp(jax.random.uniform(k[3], (*lead, heads, DK), minval=-7.0,
                                    maxval=1.1))
    g = g.at[..., ::4].set(-4.0)       # overflows a factorised chunk
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(k[4],
                                                        (*lead, heads)))
    return q, kk, v, g, beta


def test_channel_state_update_kernel_against_its_twin_at_published_shapes():
    state, rows, _, _, _, _, _, live = _decode_case(64, 64)
    q, k, v, g, beta = _channel_inputs(jax.random.PRNGKey(5), (64,))
    assert dr.kernel_supports(state, 64)
    run = {impl: jax.jit(functools.partial(dr.gdn_decode_update, impl=impl),
                         donate_argnums=(0,)) for impl in ("kernel", "xla")}
    mask = jnp.arange(rows.shape[0]) < live
    o_k, s_k = run["kernel"](state + 0.0, rows, q, k, v, g, beta, mask)
    o_x, s_x = run["xla"](state + 0.0, rows, q, k, v, g, beta, mask)
    assert float(jnp.max(jnp.abs(o_k[:live] - o_x[:live]))) < 2e-4
    used = np.asarray(rows[:live])
    assert float(jnp.max(jnp.abs(s_k[used] - s_x[used]))) < 1e-5
    idle = np.setdiff1d(np.arange(65), used)
    assert (np.asarray(s_k[idle]) == np.asarray(state[idle])).all()
    for impl, fn in run.items():
        s = state + 0.0
        t0 = time.perf_counter()
        for _ in range(20):
            o, s = fn(s, rows, q, k, v, g, beta, mask)
        jax.block_until_ready(s)
        ms = (time.perf_counter() - t0) / 20 * 1e3
        least = live * (2 * HV * DK * DV + 4 * 64 * DK) * 4 / 819e9 * 1e3
        print(f"gdn_decode_update (decay a channel) {impl}: {ms:.3f} ms a "
              f"call, {live} live slots; states and rows at 819 GB/s: "
              f"{least:.3f} ms")


@pytest.mark.parametrize("T, live, s0", [(256, 256, False),
                                         (1024, 700, True)])
def test_channel_chunked_prefill_against_the_recurrence(T, live, s0):
    q, k, v, g, beta = _channel_inputs(jax.random.PRNGKey(T), (1, T))
    n = jnp.asarray([live], jnp.int32)
    s = (0.1 * jax.random.normal(jax.random.PRNGKey(1), (1, 64, DK, DV))
         if s0 else None)
    o_c, s_c = jax.jit(dr.delta_rule_prefill)(q, k, v, g, beta, n, s)
    o_p, s_p = jax.jit(dr.delta_rule_scan)(q, k, v, g, beta, n, s)
    assert bool(jnp.all(jnp.isfinite(o_c)))
    scale = float(jnp.max(jnp.abs(o_p[:, :live])))
    assert float(jnp.max(jnp.abs(o_c[:, :live] - o_p[:, :live]))) \
        < 1e-4 * max(scale, 1.0)
    assert float(jnp.max(jnp.abs(s_c - s_p))) < 1e-4
    fn = jax.jit(dr.delta_rule_prefill)
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(fn(q, k, v, g, beta, n, s))
    print(f"delta_rule_prefill (decay a channel) T={T}: "
          f"{(time.perf_counter() - t0) / 5 * 1e3:.2f} ms a call")
