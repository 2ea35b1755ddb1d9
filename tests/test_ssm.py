"""The Mamba-2 recurrence's two spellings (ops/ssm.py) against the
recurrence as written, in float32: the chunked prefill scan at lengths that
are and are not multiples of the chunk, with and without an incoming state
and with a bucket's padding behind the live rows; the one-token update's
Pallas kernel (interpret mode) against its XLA twin on a pool with idle and
spare rows; decode continuing what prefill left; the convolution's tail.
Every tolerance is a few float32 roundings of sums of O(1) terms."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.ops import ssm

H, P, G, N = 8, 8, 2, 128


def _case(B, T, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 3.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.77))
    Bm = jax.random.normal(k[3], (B, T, G, N))
    Cm = jax.random.normal(k[4], (B, T, G, N))
    return x, dt, A, Bm, Cm, jnp.ones((H,))


@pytest.mark.parametrize("h0", [False, True], ids=["zero", "incoming"])
@pytest.mark.parametrize("T, live", [(40, [40, 17]), (128, [128, 1]),
                                     (256, [256, 130]), (300, [7, 300])])
def test_chunked_scan_is_the_plain_scan(T, live, h0):
    args = _case(2, T, seed=T)
    live = jnp.asarray(live, jnp.int32)
    h = (jax.random.normal(jax.random.PRNGKey(9), (2, H, P, N))
         if h0 else None)
    y_p, s_p = ssm.ssd_scan_reference(*args, live, h)
    y_c, s_c = ssm.ssd_prefill(*args, live, h, chunk=128)
    mask = (np.arange(T)[None, :] < np.asarray(live)[:, None])[..., None,
                                                              None]
    assert float(jnp.max(jnp.abs((y_p - y_c) * mask))) < 2e-4
    assert float(jnp.max(jnp.abs(s_p - s_c))) < 1e-5


@pytest.mark.parametrize("chunk", [16, 64])
def test_padding_behind_the_live_rows_does_not_move_the_state(chunk):
    """A prefill bucket's pad rows hold whatever the pad token embeds to:
    the state after them is the state after the last live row."""
    x, dt, A, Bm, Cm, D = _case(1, 96, seed=3)
    live = jnp.asarray([41], jnp.int32)
    _, padded = ssm.ssd_prefill(x, dt, A, Bm, Cm, D, live, chunk=chunk)
    _, exact = ssm.ssd_prefill(x[:, :41], dt[:, :41], A, Bm[:, :41],
                               Cm[:, :41], D, live, chunk=chunk)
    assert float(jnp.max(jnp.abs(padded - exact))) < 1e-5
    # and it would have: the same rows taken for live move it
    _, moved = ssm.ssd_prefill(x, dt, A, Bm, Cm, D,
                               jnp.asarray([96], jnp.int32), chunk=chunk)
    assert float(jnp.max(jnp.abs(moved - exact))) > 1e-2


def _pool_case(seed=3):
    x, dt, A, Bm, Cm, D = _case(5, 1, seed=seed)
    state = jax.random.normal(jax.random.PRNGKey(1), (7, H, P, N))
    # rows 3 and 0 live, three padding rows on the pool's spare row 6
    slots = jnp.asarray([3, 0, 6, 6, 6], jnp.int32)
    return state, slots, x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D


def test_decode_kernel_in_interpret_mode_against_its_twin():
    args = _pool_case()
    y_x, s_x = ssm.ssm_decode_update(*args, impl="xla")
    y_k, s_k = ssm.ssm_decode_update(*args, impl="kernel_interpret")
    assert float(jnp.max(jnp.abs(y_x[:2] - y_k[:2]))) < 1e-5
    assert float(jnp.max(jnp.abs(s_x[:6] - s_k[:6]))) < 1e-6
    # rows the bucket does not name are not touched, by either
    for s in (s_x, s_k):
        assert (np.asarray(s[jnp.asarray([1, 2, 4, 5])])
                == np.asarray(args[0][jnp.asarray([1, 2, 4, 5])])).all()
    # and the live rows moved
    assert float(jnp.max(jnp.abs(s_k[3] - args[0][3]))) > 1e-3


def test_decode_update_is_one_step_of_the_recurrence():
    state, slots, x, dt, A, Bm, Cm, D = _pool_case(seed=5)
    y, new = ssm.ssm_decode_update(state, slots, x, dt, A, Bm, Cm, D,
                                   impl="xla")
    for i, s in enumerate([3, 0]):
        y1, h1 = ssm.ssd_scan_reference(
            x[i][None, None], dt[i][None, None], A, Bm[i][None, None],
            Cm[i][None, None], D, jnp.asarray([1]), state[s][None])
        assert float(jnp.max(jnp.abs(y1[0, 0] - y[i]))) < 1e-5
        assert float(jnp.max(jnp.abs(h1[0] - new[s]))) < 1e-6


def test_kernel_is_selected_by_shape_and_refuses_others():
    state = jnp.zeros((3, H, P, N))
    assert ssm.kernel_supports(state, G)
    assert not ssm.kernel_supports(state.astype(jnp.bfloat16), G)
    assert not ssm.kernel_supports(jnp.zeros((3, H, P, 64)), G)
    assert not ssm.kernel_supports(jnp.zeros((3, H, 4, N)), G)
    assert not ssm.kernel_supports(state, 3)
    args = _pool_case()
    with pytest.raises(ValueError, match="unsupported shapes"):
        ssm.ssm_decode_update(args[0][..., :64], *args[1:5],
                              args[5][..., :64], args[6][..., :64], args[7],
                              impl="kernel_interpret")
    with pytest.raises(ValueError, match="unknown state update"):
        ssm.ssm_decode_update(*args, impl="fast")


@pytest.mark.parametrize("n", [1, 2, 3, 20])
def test_convolution_tail_carries_prefill_into_decode(n):
    """The convolution over n + 1 rows at once is the convolution over n
    rows, then one decode step from the tail they left; a prompt shorter
    than the kernel leaves zeros in front."""
    K, C = 4, 24
    k = jax.random.split(jax.random.PRNGKey(n), 3)
    u = jax.random.normal(k[0], (1, 32, C))
    w, b = jax.random.normal(k[1], (K, C)), jax.random.normal(k[2], (C,))
    whole, _ = ssm.causal_conv1d(u, w, b, jnp.asarray([n + 1]))
    first, tail = ssm.causal_conv1d(u, w, b, jnp.asarray([n]))
    assert float(jnp.max(jnp.abs(first[0, :n] - whole[0, :n]))) == 0.0
    assert (np.asarray(tail[0, :max(0, K - 1 - n)]) == 0).all()
    pool = jnp.zeros((3, K - 1, C)).at[1].set(tail[0])
    step, pool = ssm.conv_decode_update(pool, jnp.asarray([1]), u[:, n], w,
                                        b)
    assert float(jnp.max(jnp.abs(step[0] - whole[0, n]))) < 1e-6
    assert float(jnp.max(jnp.abs(
        pool[1] - ssm.causal_conv1d(u, w, b, jnp.asarray([n + 1]))[1][0]
    ))) == 0.0
    assert (np.asarray(pool[jnp.asarray([0, 2])]) == 0).all()
