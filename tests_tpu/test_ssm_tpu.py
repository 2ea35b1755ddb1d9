"""On the chip, at Nemotron-3-Super's published shapes: the state-space
decode kernel against its XLA twin (both in place on a donated pool), the
chunked prefill scan against the plain one, the grouped paged attention
kernel (32 query heads over 2 K/V heads of 128: a group of 16, which no
cell ran before this family) against the XLA path, the latent expert
product against ragged_dot, and the memory report of a 64-slot state
update showing no temporary the size of the pool."""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.ops import moe, paged_attention as pa, ssm

H, P, N, G = 128, 64, 128, 8


def _decode_case(slots, bucket, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    state = 0.1 * jax.random.normal(k[0], (slots + 1, H, P, N), jnp.float32)
    rows = np.full((bucket,), slots, np.int32)
    live = bucket - 3
    rows[:live] = np.random.default_rng(seed).permutation(slots)[:live]
    x = jax.random.normal(k[1], (bucket, H, P), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[2], (bucket, H)) - 3.0)
    A = -jnp.exp(jax.random.uniform(k[3], (H,), minval=0.0, maxval=2.77))
    B = jax.random.normal(k[4], (bucket, G, N), jnp.bfloat16)
    C = jax.random.normal(k[5], (bucket, G, N), jnp.bfloat16)
    return state, jnp.asarray(rows), x, dt, A, B, C, jnp.ones((H,)), live


def test_state_update_kernel_against_its_twin_at_published_shapes():
    state, rows, x, dt, A, B, C, D, live = _decode_case(64, 64)
    assert ssm.kernel_supports(state, G)
    run = {impl: jax.jit(functools.partial(ssm.ssm_decode_update, impl=impl),
                         donate_argnums=(0,)) for impl in ("kernel", "xla")}
    y_k, s_k = run["kernel"](state + 0.0, rows, x, dt, A, B, C, D)
    y_x, s_x = run["xla"](state + 0.0, rows, x, dt, A, B, C, D)
    # the same float32 arithmetic in another order of sums over N
    assert float(jnp.max(jnp.abs(y_k[:live] - y_x[:live]))) < 2e-4
    used = np.asarray(rows[:live])
    assert float(jnp.max(jnp.abs(s_k[used] - s_x[used]))) < 1e-5
    # a slot the bucket does not name is not touched
    idle = np.setdiff1d(np.arange(64), used)
    assert (np.asarray(s_k[idle]) == np.asarray(state[idle])).all()
    # the rule picks the kernel here
    text = jax.jit(ssm.ssm_decode_update).lower(
        state, rows, x, dt, A, B, C, D).compile().as_text()
    assert "ssm_decode_update" in text
    for impl, fn in run.items():
        s = state + 0.0
        t0 = time.perf_counter()
        for _ in range(20):
            y, s = fn(s, rows, x, dt, A, B, C, D)
        jax.block_until_ready(s)
        ms = (time.perf_counter() - t0) / 20 * 1e3
        least = 2 * live * H * P * N * 4 / 819e9 * 1e3
        print(f"ssm_decode_update {impl}: {ms:.3f} ms a call, {live} live "
              f"slots; the states' read and write at 819 GB/s: "
              f"{least:.3f} ms")


def test_state_update_holds_no_temporary_the_size_of_the_pool():
    state, rows, x, dt, A, B, C, D, _ = _decode_case(64, 64)
    for impl in ("kernel", "xla"):
        mem = jax.jit(functools.partial(ssm.ssm_decode_update, impl=impl),
                      donate_argnums=(0,)).lower(
            state, rows, x, dt, A, B, C, D).compile().memory_analysis()
        assert mem.temp_size_in_bytes < state.nbytes // 8, (
            impl, mem.temp_size_in_bytes)
        assert mem.alias_size_in_bytes >= state.nbytes


@pytest.mark.parametrize("T, live", [(256, 256), (1024, 700)])
def test_chunked_prefill_scan_against_the_plain_scan(T, live):
    k = jax.random.split(jax.random.PRNGKey(T), 6)
    x = jax.random.normal(k[0], (1, T, H, P), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[1], (1, T, H)) - 3.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.77))
    B = jax.random.normal(k[3], (1, T, G, N), jnp.bfloat16)
    C = jax.random.normal(k[4], (1, T, G, N), jnp.bfloat16)
    D = jnp.ones((H,))
    n = jnp.asarray([live], jnp.int32)
    y_c, s_c = jax.jit(ssm.ssd_prefill)(x, dt, A, B, C, D, n)
    y_p, s_p = jax.jit(ssm.ssd_scan_reference)(x, dt, A, B, C, D, n)
    scale = float(jnp.max(jnp.abs(y_p[:, :live])))
    assert float(jnp.max(jnp.abs(y_c[:, :live] - y_p[:, :live]))) \
        < 1e-4 * scale
    assert float(jnp.max(jnp.abs(s_c - s_p))) \
        < 1e-4 * float(jnp.max(jnp.abs(s_p)))


def test_paged_kernel_takes_a_group_of_sixteen_query_heads():
    """32 query heads over 2 K/V heads of 128, one attention layer's
    decode at 64 slots x 128 pages: the Pallas kernel against the XLA
    path on the same pool."""
    B_, MP, Pg, Hq, Hkv, Dh = 64, 128, 16, 32, 2, 128
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    pool = 1 + B_ * MP
    k_pages = jax.random.normal(k[0], (pool, Pg, Hkv * Dh), jnp.bfloat16)
    v_pages = jax.random.normal(k[1], (pool, Pg, Hkv * Dh), jnp.bfloat16)
    q = jax.random.normal(k[2], (B_, 1, Hq, Dh), jnp.bfloat16)
    k_new = jax.random.normal(k[3], (B_, 1, Hkv, Dh), jnp.bfloat16)
    v_new = jax.random.normal(k[4], (B_, 1, Hkv, Dh), jnp.bfloat16)
    rng = np.random.default_rng(0)
    lens = rng.integers(64, 1536, B_).astype(np.int32)
    tables = np.zeros((B_, MP), np.int32)
    perm = rng.permutation(pool - 1) + 1
    at = 0
    for b in range(B_):
        n = lens[b] // Pg + 1
        tables[b, :n] = perm[at:at + n]
        at += n
    assert pa.kernel_supports(q, k_pages)
    args = (q, k_pages, v_pages, jnp.asarray(tables), jnp.asarray(lens),
            k_new, v_new)
    got = jax.jit(pa.paged_decode_attention)(*args)
    want = jax.jit(pa.paged_decode_reference)(*args)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    # bfloat16 outputs of O(1): one rounding of the last bit
    assert err < 2e-2, err


@pytest.mark.parametrize("rows", [1408, 22528])
def test_latent_expert_product_against_ragged_dot(rows):
    """The two grouped products of a latent expert (1024 -> 2688 -> 1024,
    squared ReLU) over the 128 held experts of 512, at a decode step's and
    a prefill's row counts: megablox against ragged_dot, with three
    quarters of the choices held elsewhere."""
    L, F, held, k = 1024, 2688, 128, 22
    key = jax.random.split(jax.random.PRNGKey(rows), 5)
    n = rows // k
    h = jax.random.normal(key[0], (n, L), jnp.bfloat16)
    w_up = (0.02 * jax.random.normal(key[1], (held, L, F))).astype(
        jnp.bfloat16)
    w_down = (0.02 * jax.random.normal(key[2], (held, F, L))).astype(
        jnp.bfloat16)
    choice = jnp.argsort(jax.random.uniform(key[3], (n, 512)), axis=-1)[
        :, :k].astype(jnp.int32)
    weights = jax.random.uniform(key[4], (n, k), jnp.float32)
    run = {impl: jax.jit(functools.partial(
        moe.routed_experts, held=(0, held), impl=impl))
        for impl in ("gmm", "ragged_dot")}
    got, stats = run["gmm"](h, choice, weights, w_up, w_down)
    want, _ = run["ragged_dot"](h, choice, weights, w_up, w_down)
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32)))) < 2e-2 * scale
    here = int(jnp.sum(choice < held))
    assert int(stats["moe_rows"]) == here
    assert int(stats["moe_rows_elsewhere"]) == rows - here
    for impl, fn in run.items():
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn(h, choice, weights, w_up, w_down)
        jax.block_until_ready(out)
        print(f"latent experts {impl} rows {rows}: "
              f"{(time.perf_counter() - t0) / 10 * 1e3:.3f} ms a call")
