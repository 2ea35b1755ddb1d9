"""`jax.grad` through ops/moe.routed_experts and the segment-aware
convolution of ops/ssm.py: the first tests that DIFFERENTIATE the routed
layer (the serve tests of models/deepseek_v3.py and models/nemotron_h.py
run it forward only).

The oracle is a dense masked sum: every row through every held expert,
weighted by what it was routed there with. float32 on `ragged_dot` is
exact to summation order; the interpreted Pallas grouped matmul takes
bfloat16 operands, so it is held to a bfloat16 rounding of its own
operands."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.ops import moe, ssm

N, E, F, ROUTER, K = 96, 128, 128, 8, 3


def _setup(seed, dtype, held):
    rng = np.random.default_rng(seed)
    count = ROUTER if held is None else held[1]
    h = jnp.asarray(rng.standard_normal((N, E)), dtype)
    w_r = jnp.asarray(rng.standard_normal((E, ROUTER)) * 0.2, jnp.float32)
    bias = jnp.asarray(rng.standard_normal((ROUTER,)) * 0.05, jnp.float32)
    w_in = jnp.asarray(rng.standard_normal((count, E, 2 * F)) * 0.1, dtype)
    w_down = jnp.asarray(rng.standard_normal((count, F, E)) * 0.1, dtype)
    return h, w_r, bias, w_in, w_down


def _dense(h, weights, choice, w_in, w_down, held):
    """sum over the held experts of gate_e * E_e(h), all rows through all
    experts, in float32."""
    first = 0 if held is None else held[0]
    h32 = h.astype(jnp.float32)
    out = jnp.zeros((N, E), jnp.float32)
    for e in range(w_in.shape[0]):
        gate = jnp.sum(jnp.where(choice == first + e, weights, 0.0), -1)
        up = h32 @ w_in[e].astype(jnp.float32)
        act = jax.nn.silu(up[:, :F]) * up[:, F:]
        out = out + gate[:, None] * (act @ w_down[e].astype(jnp.float32))
    return out


def _loss(fn, h, w_r, bias, w_in, w_down, target):
    choice, weights = moe.route(h, w_r, bias, K, 1.0, norm_eps=1e-6)
    return jnp.sum(fn(h, weights, choice, w_in, w_down).astype(jnp.float32)
                   * target)


@pytest.mark.parametrize("impl,dtype,tol", [
    ("ragged_dot", jnp.float32, 2e-4), ("gmm_interpret", jnp.bfloat16, 0.06)])
@pytest.mark.parametrize("held", [None, (2, 4)])
def test_gradient_matches_the_dense_masked_sum(impl, dtype, tol, held):
    h, w_r, bias, w_in, w_down = _setup(11, dtype, held)
    target = jnp.asarray(np.random.default_rng(5).standard_normal((N, E)),
                         jnp.float32)

    def routed(h, weights, choice, w_in, w_down):
        return moe.routed_experts(h, choice, weights, w_in, w_down,
                                  held=held, impl=impl)[0]

    def dense(h, weights, choice, w_in, w_down):
        return _dense(h, weights, choice, w_in, w_down, held)

    args = (h, w_r, bias, w_in, w_down)
    got = jax.grad(lambda *a: _loss(routed, *a, target), (0, 1, 3, 4))(*args)
    want = jax.grad(lambda *a: _loss(dense, *a, target), (0, 1, 3, 4))(*args)
    for g, w, name in zip(got, want, ("h", "router", "w_in", "w_down")):
        scale = float(jnp.max(jnp.abs(w.astype(jnp.float32))))
        gap = float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                    - w.astype(jnp.float32))))
        assert gap <= tol * scale, (name, gap, scale)
    # the selection bias moves the choice and never the weights
    assert float(jnp.max(jnp.abs(jax.grad(
        lambda b: _loss(routed, h, w_r, b, w_in, w_down, target))(bias)))) == 0


def _poisoned(real):
    """A grouped product that behaves as the Pallas one does on the chip
    with the rows of no group: it never reads them, and it leaves NaN
    where it never writes, forward (the output's rows) and backward (the
    rows of the cotangent it hands back)."""
    def clean(x, sizes):
        none = jnp.arange(x.shape[0]) >= jnp.sum(sizes)
        return jnp.where(none[:, None], 0.0, x), none

    @jax.custom_vjp
    def product(lhs, rhs, sizes):
        lhs, none = clean(lhs, sizes)
        return jnp.where(none[:, None], jnp.nan, real(lhs, rhs, sizes))

    def fwd(lhs, rhs, sizes):
        return product(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        lhs, none = clean(lhs, sizes)
        g, _ = clean(g, sizes)
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = vjp(g)
        return jnp.where(none[:, None], jnp.nan, d_lhs), d_rhs, None

    product.defvjp(fwd, bwd)
    return product


@pytest.mark.parametrize("impl,dtype", [("ragged_dot", jnp.float32),
                                        ("gmm_interpret", jnp.bfloat16)])
def test_rows_held_elsewhere_give_exactly_zero_whatever_the_product_left(
        monkeypatch, impl, dtype):
    """Three quarters of a share's rows belong to no held group. With NaN
    in every one of them after each product, forward and backward, the
    gradients stay finite and are those of the clean product: the masks
    stand before anything can read such a row."""
    held = (2, 2)
    h, w_r, bias, w_in, w_down = _setup(13, dtype, held)
    target = jnp.asarray(np.random.default_rng(6).standard_normal((N, E)),
                         jnp.float32)
    real = moe.grouped_matmul

    def routed(h, weights, choice, w_in, w_down):
        return moe.routed_experts(h, choice, weights, w_in, w_down,
                                  held=held, impl=impl)[0]

    args = (h, w_r, bias, w_in, w_down)
    grad = jax.grad(lambda *a: _loss(routed, *a, target), (0, 1, 3, 4))
    want = grad(*args)
    monkeypatch.setattr(
        moe, "grouped_matmul",
        lambda lhs, rhs, sizes, impl=None: _poisoned(
            lambda a, b, s: real(a, b, s, impl=impl))(lhs, rhs, sizes))
    got = grad(*args)
    choice, _ = moe.route(h, w_r, bias, K, 1.0)
    assert float(jnp.mean((choice < 2) | (choice >= 4))) > 0.6
    for g, w in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
    # a token none of whose experts is held gets NOTHING from this layer
    none_here = np.asarray(jnp.all((choice < 2) | (choice >= 4), axis=-1))
    assert none_here.any()
    assert not np.asarray(got[0], np.float32)[none_here].any()


def scatters(jaxpr, scope=""):
    """(primitive, operand shape, name stack) of every scatter of a jaxpr,
    those of its sub-jaxprs (remat, pjit, a custom rule's) among them."""
    for eqn in jaxpr.eqns:
        stack = f"{scope}/{eqn.source_info.name_stack}"
        if eqn.primitive.name.startswith("scatter"):
            yield eqn.primitive.name, eqn.invars[0].aval.shape, stack
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from scatters(sub, stack)


@pytest.mark.parametrize("impl,dtype", [("ragged_dot", jnp.float32),
                                        ("gmm_interpret", jnp.bfloat16)])
@pytest.mark.parametrize("held", [None, (2, 4)])
def test_the_gradient_moves_rows_by_gathers_alone(impl, dtype, held):
    """Both row moves own their transpose (`_dispatch`, `_combine`), so
    the gradient with respect to `h`, the weights and both stacks holds no
    scatter into anything a row wide: what stays is the `bincount` over
    the groups, the kernel's small int32 tables and the router's
    `[N, ROUTER]` scores."""
    h, w_r, bias, w_in, w_down = _setup(17, dtype, held)
    choice, weights = moe.route(h, w_r, bias, K, 1.0)

    def loss(h, weights, w_in, w_down):
        out, _ = moe.routed_experts(h, choice, weights, w_in, w_down,
                                    held=held, impl=impl)
        return jnp.sum(out.astype(jnp.float32))

    grad = jax.grad(loss, (0, 1, 2, 3))
    found = list(scatters(jax.make_jaxpr(grad)(h, weights, w_in,
                                               w_down).jaxpr))
    assert found                            # the bincount's, at least
    wide = [(name, shape) for name, shape, _ in found
            if len(shape) >= 2 or shape[0] > N * K]
    assert not wide, wide
    # and the whole of it, router included: [N, ROUTER] is the widest
    found = list(scatters(jax.make_jaxpr(jax.grad(
        lambda *a: _loss(lambda h, w, c, a, b: moe.routed_experts(
            h, c, w, a, b, held=held, impl=impl)[0], *a, 1.0),
        (0, 1, 3, 4)))(h, w_r, bias, w_in, w_down).jaxpr))
    assert max(shape[-1] for _, shape, _ in found if len(shape) >= 2) < E


@pytest.mark.parametrize("impl,dtype", [("ragged_dot", jnp.float32),
                                        ("ragged_dot", jnp.bfloat16),
                                        ("gmm_interpret", jnp.bfloat16)])
@pytest.mark.parametrize("held", [None, (2, 4)])
def test_the_forward_is_the_plain_formula_to_the_bit(impl, dtype, held):
    """take, the products, a float32 weigh, the mask, take by
    argsort(order), the sum over k: the rules change what `jax.grad`
    builds and nothing of what the layer returns."""
    h, w_r, bias, w_in, w_down = _setup(19, dtype, held)
    choice, weights = moe.route(h, w_r, bias, K, 1.0)
    flat = choice.reshape(-1)
    G = w_in.shape[0]
    if held is not None:
        here = (flat >= held[0]) & (flat < held[0] + held[1])
        flat = jnp.where(here, flat - held[0], G)
    order = jnp.argsort(flat, stable=True)
    y = moe._experts_sorted(
        jnp.take(h, order // K, axis=0), w_in, w_down,
        jnp.bincount(flat, length=G).astype(jnp.int32), impl)
    y = y.astype(jnp.float32) * jnp.take(weights.reshape(-1), order)[:, None]
    if held is not None:
        y = jnp.where(jnp.take(here, order)[:, None], y, 0.0)
    y = jnp.take(y, jnp.argsort(order), axis=0)
    want = jnp.sum(y.reshape(N, K, -1), axis=1).astype(dtype)
    got, _ = jax.jit(lambda *a: moe.routed_experts(
        *a, held=held, impl=impl))(h, choice, weights, w_in, w_down)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 0.02)])
def test_a_tokens_k_held_rows_sum_into_its_gradient(dtype, tol):
    """The dispatch's backward gathers a token's k sorted rows and sums
    them. Token 0 has all three of its experts held, token 1 the same
    held expert THREE times (k colliding rows of one group), token 2 none,
    the rest as routed: `dh` is the dense masked sum's, row by row."""
    held = (2, 4)
    h, w_r, bias, w_in, w_down = _setup(23, dtype, held)
    choice, weights = moe.route(h, w_r, bias, K, 1.0)
    choice = choice.at[0].set(jnp.asarray([2, 3, 5])).at[1].set(3).at[
        2].set(jnp.asarray([0, 1, 7]))
    target = jnp.asarray(np.random.default_rng(7).standard_normal((N, E)),
                         jnp.float32)

    def routed(h, weights):
        return jnp.sum(moe.routed_experts(
            h, choice, weights, w_in, w_down, held=held,
            impl="ragged_dot")[0].astype(jnp.float32) * target)

    def dense(h, weights):
        return jnp.sum(_dense(h, weights, choice, w_in, w_down, held)
                       * target)

    got = jax.grad(routed, (0, 1))(h, weights)
    want = jax.grad(dense, (0, 1))(h, weights)
    for g, w in zip(got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= tol * np.abs(w).max()
        assert np.abs(g[:2] - w[:2]).max() <= tol * np.abs(w[:2]).max()
        assert w[:2].any() and not g[2].any()


@pytest.mark.parametrize("m", [4096, 1024])
def test_the_interpreted_kernel_gives_ragged_dots_product(m):
    """At a train step's thousands of rows an expert and at a serve
    prefill's hundreds, with rows of no group behind the last one: the
    one tiling gives the product `ragged_dot` gives."""
    rng = np.random.default_rng(4)
    G, k, n = 2, 128, 256
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((G, k, n)) * 0.1, jnp.bfloat16)
    sizes = jnp.asarray([m // 2 - 7, m // 4], jnp.int32)
    got = moe.grouped_matmul(lhs, rhs, sizes, impl="gmm_interpret")
    want = moe.grouped_matmul(lhs, rhs, sizes, impl="ragged_dot")
    live = int(jnp.sum(sizes))
    assert float(jnp.max(jnp.abs(
        got[:live].astype(jnp.float32)
        - want[:live].astype(jnp.float32)))) <= 0.04


# -- the convolution under packing -------------------------------------------

def _conv_inputs(seed=3, B=2, T=48, C=16, Kc=3):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.standard_normal((B, T, C)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((Kc, C)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((C,)), jnp.float32)
    return u, w, b


def test_conv_with_segments_equals_per_document_calls():
    u, w, b = _conv_inputs()
    lens = ([5, 1, 20, 22], [48])          # a one-token document too
    seg = np.stack([np.repeat(np.arange(len(row)), row) for row in lens])
    got, tail = ssm.causal_conv1d(u, w, None, None, jnp.asarray(seg))
    assert tail is None
    for r, row in enumerate(lens):
        at = 0
        for n in row:
            alone, _ = ssm.causal_conv1d(u[r:r + 1, at:at + n], w, None, None)
            np.testing.assert_allclose(got[r, at:at + n], alone[0],
                                       rtol=0, atol=1e-6)
            at += n

    # and the gradient of a document's output reaches that document alone
    def first_doc(u):
        out, _ = ssm.causal_conv1d(u, w, None, None, jnp.asarray(seg))
        return jnp.sum(out[0, 5:6])        # the one-token document
    g = np.asarray(jax.grad(first_doc)(u))
    assert g[0, 5].any() and not g[0, :5].any() and not g[0, 6:].any()


def test_conv_without_segments_is_the_function_it_was_to_the_bit():
    u, w, b = _conv_inputs()
    live = jnp.asarray([48, 17], jnp.int32)
    out, tail = ssm.causal_conv1d(u, w, b, live)
    padded = jnp.pad(u, ((0, 0), (2, 0), (0, 0)))
    want = b
    for k in range(3):
        want = want + padded[:, k:k + 48] * w[k]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(tail[1]),
                                  np.asarray(u[1, 15:17]))
    one_segment, _ = ssm.causal_conv1d(u, w, b, live,
                                       jnp.zeros((2, 48), jnp.int32))
    np.testing.assert_array_equal(np.asarray(one_segment), np.asarray(out))
