"""The gated delta rule's spellings (ops/delta_rule.py) against the
recurrence as written, in float32: the chunked WY prefill at lengths that
are and are not multiples of the chunk, with and without an incoming state
and with a bucket's padding behind the live rows; the one-token update's
Pallas kernel (interpret mode) against its XLA twin on a pool with idle and
spare rows; decode continuing what prefill left. Every tolerance is a few
float32 roundings of sums of O(1) terms."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.ops import delta_rule as dr

HK, HV, DK, DV = 2, 4, 16, 128


def _case(B, T, seed=0, dk=DK):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(k[0], (B, T, HK, dk))
    key = jax.random.normal(k[1], (B, T, HK, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    key = key / jnp.linalg.norm(key, axis=-1, keepdims=True)
    v = jax.random.normal(k[2], (B, T, HV, DV))
    # decays from 0.999 to 0.05 a step, writes of every strength
    g = -jnp.exp(jax.random.uniform(k[3], (B, T, HV), minval=-7.0,
                                    maxval=1.1))
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(k[4], (B, T, HV)))
    return q, key, v, g, beta


@pytest.mark.parametrize("s0", [False, True], ids=["zero", "incoming"])
@pytest.mark.parametrize("T, live, chunk", [
    (40, [40, 17], 16), (64, [64, 1], 64), (150, [150, 97], 64),
    (300, [7, 300], 64), (33, [33, 32], 8)])
def test_chunked_prefill_is_the_recurrence(T, live, chunk, s0):
    args = _case(2, T, seed=T)
    live = jnp.asarray(live, jnp.int32)
    s = (jax.random.normal(jax.random.PRNGKey(9), (2, HV, DK, DV))
         if s0 else None)
    o_p, s_p = dr.delta_rule_scan(*args, live, s)
    o_c, s_c = dr.delta_rule_prefill(*args, live, s, chunk=chunk)
    mask = (np.arange(T)[None, :] < np.asarray(live)[:, None])[..., None,
                                                              None]
    assert float(jnp.max(jnp.abs((o_p - o_c) * mask))) < 2e-5
    assert float(jnp.max(jnp.abs(s_p - s_c))) < 2e-5


@pytest.mark.parametrize("chunk", [16, 64])
def test_padding_behind_the_live_rows_does_not_move_the_state(chunk):
    """A prefill bucket's pad rows hold whatever the pad token embeds to:
    the state after them is the state after the last live row."""
    q, k, v, g, beta = _case(1, 96, seed=3)
    live = jnp.asarray([41], jnp.int32)
    _, padded = dr.delta_rule_prefill(q, k, v, g, beta, live, chunk=chunk)
    _, exact = dr.delta_rule_prefill(q[:, :41], k[:, :41], v[:, :41],
                                     g[:, :41], beta[:, :41], live,
                                     chunk=chunk)
    assert float(jnp.max(jnp.abs(padded - exact))) < 1e-5
    # and it would have: the same rows taken for live move it
    _, moved = dr.delta_rule_prefill(q, k, v, g, beta,
                                     jnp.asarray([96], jnp.int32),
                                     chunk=chunk)
    assert float(jnp.max(jnp.abs(moved - exact))) > 1e-2


def test_the_step_reads_before_it_writes():
    """What sets the rule apart from a decayed outer-product sum: a key
    written twice with the same value adds nothing the second time at
    beta = 1, because the step subtracts what it reads."""
    k = jnp.zeros((1, 2, 1, DK)).at[..., 0].set(1.0)
    v = jnp.ones((1, 2, 1, DV))
    one, zero = jnp.ones((1, 2, 1)), jnp.zeros((1, 2, 1))
    o, S = dr.delta_rule_scan(k, k, v, zero, one, jnp.asarray([2]))
    assert float(jnp.max(jnp.abs(S[0, 0, 0] - 1.0))) == 0.0      # not 2
    assert float(jnp.max(jnp.abs(S[0, 0, 1:]))) == 0.0
    assert float(jnp.max(jnp.abs(o - 1.0))) == 0.0
    _, S_c = dr.delta_rule_prefill(k, k, v, zero, one, jnp.asarray([2]),
                                   chunk=2)
    assert float(jnp.max(jnp.abs(S_c - S))) < 1e-6


def _pool_case(seed=3, dk=DK):
    q, k, v, g, beta = _case(5, 1, seed=seed, dk=dk)
    state = jax.random.normal(jax.random.PRNGKey(1), (7, HV, dk, DV))
    # rows 3 and 0 live, three padding rows on the pool's spare row 6
    slots = jnp.asarray([3, 0, 6, 6, 6], jnp.int32)
    return (state, slots, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
            jnp.asarray([True, True, False, False, False]))


@pytest.mark.parametrize("dk", [16, 128])
def test_decode_kernel_in_interpret_mode_against_its_twin(dk):
    args = _pool_case(dk=dk)
    o_x, s_x = dr.gdn_decode_update(*args, impl="xla")
    o_k, s_k = dr.gdn_decode_update(*args, impl="kernel_interpret")
    assert float(jnp.max(jnp.abs(o_x[:2] - o_k[:2]))) < 1e-5
    assert float(jnp.max(jnp.abs(s_x - s_k))) < 1e-6
    # rows the bucket does not name are not touched, by either, and the
    # row its padding names keeps what it held
    for s in (s_x, s_k):
        assert (np.asarray(s[jnp.asarray([1, 2, 4, 5, 6])])
                == np.asarray(args[0][jnp.asarray([1, 2, 4, 5, 6])])).all()
    # and the live rows moved
    assert float(jnp.max(jnp.abs(s_k[3] - args[0][3]))) > 1e-3
    # a row that is not live costs no arithmetic and reads zero, in both
    assert float(jnp.max(jnp.abs(o_x[2:]))) == 0.0
    assert float(jnp.max(jnp.abs(o_k[2:]))) == 0.0


@pytest.mark.parametrize("impl", ["xla", "kernel_interpret"])
def test_a_pool_with_no_spare_row_loses_no_slot(impl):
    """Liveness is the caller's to say, not the pool's layout: with no
    mask every row is live, the pool's last row among them; with one, a
    dead row names whatever slot it likes (no live row's) and leaves it
    be."""
    state, _, q, k, v, g, beta, _ = _pool_case(seed=7)
    pool = state[:5]
    slots = jnp.asarray([4, 2, 0, 1, 3], jnp.int32)
    o, new = dr.gdn_decode_update(pool, slots, q, k, v, g, beta, impl=impl)
    for i, s in enumerate(np.asarray(slots)):
        o1, s1 = dr.delta_rule_scan(
            q[i][None, None], k[i][None, None], v[i][None, None],
            g[i][None, None], beta[i][None, None], jnp.asarray([1]),
            pool[s][None])
        assert float(jnp.max(jnp.abs(o1[0, 0] - o[i]))) < 1e-5
        assert float(jnp.max(jnp.abs(s1[0] - new[s]))) < 1e-6
    live = jnp.asarray([True, False, True, False, False])
    o_m, masked = dr.gdn_decode_update(pool, slots, q, k, v, g, beta, live,
                                       impl=impl)
    assert float(jnp.max(jnp.abs(masked[jnp.asarray([4, 0])]
                                 - new[jnp.asarray([4, 0])]))) == 0.0
    assert (np.asarray(masked[jnp.asarray([2, 1, 3])])
            == np.asarray(pool[jnp.asarray([2, 1, 3])])).all()
    assert float(jnp.max(jnp.abs(o_m[jnp.asarray([1, 3, 4])]))) == 0.0
    assert float(jnp.max(jnp.abs(o_m[0] - o[0]))) == 0.0


@pytest.mark.parametrize("impl", ["xla", "kernel_interpret"])
def test_decode_update_is_one_step_of_the_recurrence(impl):
    state, slots, q, k, v, g, beta, live = _pool_case(seed=5)
    o, new = dr.gdn_decode_update(state, slots, q, k, v, g, beta, live,
                                  impl=impl)
    for i, s in enumerate([3, 0]):
        o1, s1 = dr.delta_rule_scan(
            q[i][None, None], k[i][None, None], v[i][None, None],
            g[i][None, None], beta[i][None, None], jnp.asarray([1]),
            state[s][None])
        assert float(jnp.max(jnp.abs(o1[0, 0] - o[i]))) < 1e-5
        assert float(jnp.max(jnp.abs(s1[0] - new[s]))) < 1e-6


def test_decode_continues_what_prefill_left():
    """n rows at once, or n - 3 rows and three one-token updates from the
    state they left in a used pool row."""
    q, k, v, g, beta = _case(1, 50, seed=11)
    live = jnp.asarray([50], jnp.int32)
    o_all, s_all = dr.delta_rule_prefill(q, k, v, g, beta, live)
    _, s = dr.delta_rule_prefill(q, k, v, g, beta, jnp.asarray([47]))
    pool = jax.random.normal(jax.random.PRNGKey(2), (3, HV, DK, DV))
    pool = pool.at[1].set(s[0])
    for t in (47, 48, 49):
        o, pool = dr.gdn_decode_update(
            pool, jnp.asarray([1], jnp.int32), q[:, t], k[:, t], v[:, t],
            g[:, t], beta[:, t], impl="kernel_interpret")
        assert float(jnp.max(jnp.abs(o[0] - o_all[0, t]))) < 2e-5
    assert float(jnp.max(jnp.abs(pool[1] - s_all[0]))) < 2e-5


def test_kernel_is_selected_by_shape_and_refuses_others():
    state = jnp.zeros((3, HV, DK, DV))
    assert dr.kernel_supports(state, HK)
    assert dr.kernel_supports(jnp.zeros((65, 64, 128, 128)), 32)
    assert not dr.kernel_supports(state.astype(jnp.bfloat16), HK)
    assert not dr.kernel_supports(jnp.zeros((3, HV, DK, 64)), HK)
    assert not dr.kernel_supports(jnp.zeros((3, HV, 4, DV)), HK)
    assert not dr.kernel_supports(state, 3)
    # 16 value heads a block at the published shape, whole key heads always
    assert dr._block_heads(64, 2) == 16 and dr._block_heads(4, 2) == 4
    assert dr._block_heads(6, 3) == 6 and dr._block_heads(40, 2) == 10
    args = _pool_case()
    with pytest.raises(ValueError, match="unsupported shapes"):
        dr.gdn_decode_update(args[0][..., :64], *args[1:4],
                             args[4][..., :64], *args[5:],
                             impl="kernel_interpret")
    with pytest.raises(ValueError, match="unknown state update"):
        dr.gdn_decode_update(*args, impl="fast")


# ---------------------------------------------------------------------------
# a decay a key CHANNEL (Kimi Delta Attention), beta past 1
# ---------------------------------------------------------------------------

def _channel_case(B, T, seed=0, dk=DK, steep=False):
    """`_case` with `g` [B, T, HV, dk] and `beta` in (0, 2). `steep`: a
    quarter of the channels lose 4 nats a step, so inside a chunk of 64
    their running sum passes 88 (at position 22) and the factorised
    `(k_i exp(G_i)) . (k_j exp(-G_j))` would be inf * 0."""
    q, key, v, _, _ = _case(B, T, seed=seed, dk=dk)
    k = jax.random.split(jax.random.PRNGKey(1000 + seed), 2)
    g = -jnp.exp(jax.random.uniform(k[0], (B, T, HV, dk), minval=-7.0,
                                    maxval=1.1))
    if steep:
        g = g.at[..., ::4].set(-4.0)
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(k[1], (B, T, HV)))
    return q, key, v, g, beta


@pytest.mark.parametrize("s0", [False, True], ids=["zero", "incoming"])
@pytest.mark.parametrize("steep", [False, True], ids=["mild", "steep"])
@pytest.mark.parametrize("T, live, chunk", [
    (40, [40, 17], 16), (150, [150, 97], 64), (200, [7, 200], 64),
    (33, [33, 32], 8)])
def test_channel_decay_chunked_prefill_is_the_recurrence(T, live, chunk,
                                                         steep, s0):
    args = _channel_case(2, T, seed=T, steep=steep)
    assert float(jnp.max(args[4])) > 1.5         # beta does pass 1
    live = jnp.asarray(live, jnp.int32)
    s = (jax.random.normal(jax.random.PRNGKey(9), (2, HV, DK, DV))
         if s0 else None)
    o_p, s_p = dr.delta_rule_scan(*args, live, s)
    o_c, s_c = dr.delta_rule_prefill(*args, live, s, chunk=chunk)
    assert bool(jnp.all(jnp.isfinite(o_c))) and bool(
        jnp.all(jnp.isfinite(s_c)))
    mask = (np.arange(T)[None, :] < np.asarray(live)[:, None])[..., None,
                                                              None]
    # beta near 2 lets a step flip what it read: sums a little larger
    # than the scalar gate's, still a few float32 roundings
    assert float(jnp.max(jnp.abs((o_p - o_c) * mask))) < 5e-5
    assert float(jnp.max(jnp.abs(s_p - s_c))) < 5e-5


def test_the_steep_case_does_overflow_the_factorised_chunk():
    """The hazard the sub-chunks are for, shown and not assumed: at the
    steep decay the naive product is not finite inside one chunk of 64."""
    _, key, _, g, _ = _channel_case(1, 64, seed=1, steep=True)
    cum = jnp.cumsum(g[:, :, 0], axis=1)                     # [1, 64, dk]
    naive = jnp.einsum("bid,bjd->bij", key[:, :, 0] * jnp.exp(cum),
                       key[:, :, 0] * jnp.exp(-cum))
    assert not bool(jnp.all(jnp.isfinite(naive)))


def test_a_head_scalar_is_the_channel_vector_of_equal_entries():
    """One code: `g` [.., HV] and the same number in every channel give
    the same outputs, in all three spellings."""
    q, k, v, g, beta = _case(2, 70, seed=4)
    wide = jnp.broadcast_to(g[..., None], g.shape + (DK,))
    live = jnp.asarray([70, 33], jnp.int32)
    for fn in (dr.delta_rule_scan, dr.delta_rule_prefill):
        o_s, s_s = fn(q, k, v, g, beta, live)
        o_w, s_w = fn(q, k, v, wide, beta, live)
        assert float(jnp.max(jnp.abs(o_s - o_w))) < 2e-5
        assert float(jnp.max(jnp.abs(s_s - s_w))) < 2e-5
    state, slots, q1, k1, v1, g1, b1, alive = _pool_case()
    wide1 = jnp.broadcast_to(g1[..., None], g1.shape + (DK,))
    for impl in ("xla", "kernel_interpret"):
        o_s, s_s = dr.gdn_decode_update(state, slots, q1, k1, v1, g1, b1,
                                        alive, impl=impl)
        o_w, s_w = dr.gdn_decode_update(state, slots, q1, k1, v1, wide1, b1,
                                        alive, impl=impl)
        assert float(jnp.max(jnp.abs(o_s - o_w))) < 1e-6
        assert float(jnp.max(jnp.abs(s_s - s_w))) < 1e-6


def _channel_pool_case(seed=3, dk=DK):
    q, k, v, g, beta = _channel_case(5, 1, seed=seed, dk=dk, steep=True)
    state = jax.random.normal(jax.random.PRNGKey(1), (7, HV, dk, DV))
    slots = jnp.asarray([3, 0, 6, 6, 6], jnp.int32)
    return (state, slots, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
            jnp.asarray([True, True, False, False, False]))


@pytest.mark.parametrize("dk", [16, 128])
def test_channel_decay_decode_kernel_against_twin_and_recurrence(dk):
    args = _channel_pool_case(dk=dk)
    state, slots, q, k, v, g, beta, _ = args
    o_x, s_x = dr.gdn_decode_update(*args, impl="xla")
    o_k, s_k = dr.gdn_decode_update(*args, impl="kernel_interpret")
    assert float(jnp.max(jnp.abs(o_x - o_k))) < 1e-5
    assert float(jnp.max(jnp.abs(s_x - s_k))) < 1e-6
    assert (np.asarray(s_k[jnp.asarray([1, 2, 4, 5, 6])])
            == np.asarray(state[jnp.asarray([1, 2, 4, 5, 6])])).all()
    assert float(jnp.max(jnp.abs(o_k[2:]))) == 0.0
    for i, s in enumerate([3, 0]):
        o1, s1 = dr.delta_rule_scan(
            q[i][None, None], k[i][None, None], v[i][None, None],
            g[i][None, None], beta[i][None, None], jnp.asarray([1]),
            state[s][None])
        assert float(jnp.max(jnp.abs(o1[0, 0] - o_k[i]))) < 1e-5
        assert float(jnp.max(jnp.abs(s1[0] - s_k[s]))) < 1e-6


def test_channel_decay_decode_continues_what_prefill_left():
    q, k, v, g, beta = _channel_case(1, 50, seed=11)
    o_all, s_all = dr.delta_rule_prefill(q, k, v, g, beta,
                                         jnp.asarray([50], jnp.int32))
    _, s = dr.delta_rule_prefill(q, k, v, g, beta, jnp.asarray([47]))
    pool = jax.random.normal(jax.random.PRNGKey(2), (3, HV, DK, DV))
    pool = pool.at[1].set(s[0])
    for t in (47, 48, 49):
        o, pool = dr.gdn_decode_update(
            pool, jnp.asarray([1], jnp.int32), q[:, t], k[:, t], v[:, t],
            g[:, t], beta[:, t], impl="kernel_interpret")
        assert float(jnp.max(jnp.abs(o[0] - o_all[0, t]))) < 5e-5
    assert float(jnp.max(jnp.abs(pool[1] - s_all[0]))) < 5e-5
