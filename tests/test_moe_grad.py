"""`jax.grad` through ops/moe.routed_experts and the segment-aware
convolution of ops/ssm.py: the first tests that DIFFERENTIATE the routed
layer (the serve tests of models/deepseek_v3.py and models/nemotron_h.py
run it forward only).

The oracle is a dense masked sum: every row through every held expert,
weighted by what it was routed there with. float32 on `ragged_dot` is
exact to summation order; the interpreted Pallas grouped matmul takes
bfloat16 operands, so it is held to a bfloat16 rounding of its own
operands.

Every case runs the full-width layer and, where the caller states the
router's expert count, the static PREFIX of the sorted rows: with the held
rows under the bound, exactly at it, and past it (the overflow's `cond`
entered), which must change nothing the layer returns and no gradient
beyond a rounding. On the prefix the un-sort and the dispatch's backward
go by token, a choice at a time (`moe._sum_choices`): the routings of
`HOLDS` give a token none, one, two or all of its rows here."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtraining_tpu.ops import moe, ssm

N, E, F, ROUTER, K = 96, 128, 128, 8, 3
# a router of 16 for the prefix cases: the 4 held are a quarter, and
# `moe.prefix_rows` gives 128 of the N * K = 288 sorted rows; 250 held rows
# fill groups of about 62, so the bound cuts the third group and the fourth
# lies whole behind it
WIDE, BOUND = 16, 128
# (held, the router's experts, held rows forced; None: as routed, and the
# caller does not state the router's count: the full-width layer)
# or a routing made by hand, named: how many of its K choices each token
# holds here, by turns (a deployment's share holds 0..K a token), and
# whether they go to the held experts by turns or all to ONE (the train
# cell routes so: one row a token, every one in one group). 96 held rows
# either way, inside the bound, with other chips' rows behind them in the
# prefix
ALL = 1 << 10                     # of a token's choices: more than any k
HOLDS = {"mixed": ((0, 1, 1, 2, ALL, 1, 0, 0), False),
         "one-group": ((1,), True)}
LAYERS = [
    pytest.param((None, ROUTER, None), id="all"),
    pytest.param(((2, 4), ROUTER, None), id="held"),
    pytest.param(((2, 4), WIDE, 100), id="prefix-under"),
    pytest.param(((2, 4), WIDE, BOUND), id="prefix-at"),
    pytest.param(((2, 4), WIDE, 250), id="prefix-past"),
    pytest.param(((2, 4), WIDE, "mixed"), id="prefix-mixed"),
    pytest.param(((2, 4), WIDE, "one-group"), id="prefix-one-group"),
]


def _setup(seed, dtype, held, router=ROUTER):
    rng = np.random.default_rng(seed)
    count = router if held is None else held[1]
    h = jnp.asarray(rng.standard_normal((N, E)), dtype)
    w_r = jnp.asarray(rng.standard_normal((E, router)) * 0.2, jnp.float32)
    bias = jnp.asarray(rng.standard_normal((router,)) * 0.05, jnp.float32)
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


def _hold(choice, held, rows):
    """`choice` with exactly `rows` of its flat rows held (None: as it
    is): held rows past that many go to expert 0, which no case holds, or
    the first rows held elsewhere come to the held experts by turns."""
    if rows is None:
        return choice
    first, count = held
    if rows in HOLDS:
        # choice i of token j is held while (i + j) % k is under the
        # token's count, so a token's held rows are not its leading ones
        counts, one_group = HOLDS[rows]
        j, i = jnp.indices(choice.shape)
        ours = (i + j) % choice.shape[1] < jnp.asarray(counts)[j % len(counts)]
        # any expert past the held ones will do: no case holds the last
        return jnp.where(ours, first + (0 if one_group else (i + j) % count),
                         first + count + (i + j) % 2).astype(choice.dtype)
    flat = choice.reshape(-1)
    here = (flat >= first) & (flat < first + count)
    nth_here, nth_away = jnp.cumsum(here) - 1, jnp.cumsum(~here) - 1
    flat = jnp.where(here & (nth_here >= rows), 0, flat)
    flat = jnp.where(~here & (nth_away < rows - jnp.sum(here)),
                     first + nth_away % count, flat)
    return flat.reshape(choice.shape)


def _routed(layer, impl):
    """The layer under test as a function of (h, weights, choice, stacks),
    with the case's held rows forced into the choice."""
    held, router, rows = layer

    def fn(h, weights, choice, w_in, w_down):
        return moe.routed_experts(
            h, _hold(choice, held, rows), weights, w_in, w_down, held=held,
            router_experts=None if rows is None else router, impl=impl)[0]
    return fn


def _loss(fn, h, w_r, bias, w_in, w_down, target):
    choice, weights = moe.route(h, w_r, bias, K, 1.0, norm_eps=1e-6)
    return jnp.sum(fn(h, weights, choice, w_in, w_down).astype(jnp.float32)
                   * target)


def test_the_cases_lie_where_their_names_say():
    assert moe.prefix_rows(N * K, 4, WIDE) == BOUND < N * K
    h, w_r, bias, _, _ = _setup(11, jnp.float32, (2, 4), WIDE)
    choice, _ = moe.route(h, w_r, bias, K, 1.0)
    for rows in (100, BOUND, 250):
        flat = np.asarray(_hold(choice, (2, 4), rows)).reshape(-1)
        sizes = np.bincount(flat, minlength=WIDE)[2:6]
        assert sizes.sum() == rows
    # past the bound: a group cut by it and a whole group behind it
    assert sizes[:2].sum() < BOUND < sizes[:3].sum() and sizes[3] > 0
    # by hand: tokens with none, one, two and all K rows here, inside the
    # bound; and a row a token, every one in ONE group
    ours = np.asarray(_hold(choice, (2, 4), "mixed"))
    ours = (ours >= 2) & (ours < 6)
    assert set(ours.sum(1)) == {0, 1, 2, K} and ours.sum() == 96 < BOUND
    assert not ours[:, 0].all() and not ours[ours.sum(1) == 1][:, 0].all()
    ours = np.asarray(_hold(choice, (2, 4), "one-group"))
    assert ((ours == 2).sum(1) == 1).all() and not ((ours > 2) & (ours < 6)).any()


@pytest.mark.parametrize("impl,dtype,tol", [
    ("ragged_dot", jnp.float32, 2e-4), ("gmm_interpret", jnp.bfloat16, 0.06)])
@pytest.mark.parametrize("layer", LAYERS)
def test_gradient_matches_the_dense_masked_sum(impl, dtype, tol, layer):
    held, router, rows = layer
    h, w_r, bias, w_in, w_down = _setup(11, dtype, held, router)
    target = jnp.asarray(np.random.default_rng(5).standard_normal((N, E)),
                         jnp.float32)
    routed = _routed(layer, impl)

    def dense(h, weights, choice, w_in, w_down):
        return _dense(h, weights, _hold(choice, held, rows), w_in, w_down,
                      held)

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
@pytest.mark.parametrize("rows", [
    pytest.param(None, id="full-width"), pytest.param(100, id="prefix-under"),
    pytest.param(BOUND, id="prefix-at"), pytest.param(200, id="prefix-past"),
    pytest.param("mixed", id="prefix-mixed"),
    pytest.param("one-group", id="prefix-one-group")])
def test_rows_held_elsewhere_give_exactly_zero_whatever_the_product_left(
        monkeypatch, impl, dtype, rows):
    """Three quarters of a share's rows belong to no held group. With NaN
    in every one of them after each product, forward and backward, the
    gradients stay finite and are those of the clean product: the masks
    stand before anything can read such a row. With the prefix (2 of 8
    held: 128 of the 288 sorted rows) the rows of no group lie inside it
    behind the held ones, and behind the overflow's; a token's sum over
    its choices reads them between its held rows."""
    held = (2, 2)
    assert moe.prefix_rows(N * K, 2, ROUTER) == BOUND
    h, w_r, bias, w_in, w_down = _setup(13, dtype, held)
    target = jnp.asarray(np.random.default_rng(6).standard_normal((N, E)),
                         jnp.float32)
    real = moe.grouped_matmul
    routed = _routed((held, ROUTER, rows), impl)

    args = (h, w_r, bias, w_in, w_down)
    grad = jax.grad(lambda *a: _loss(routed, *a, target), (0, 1, 3, 4))
    want = grad(*args)
    monkeypatch.setattr(
        moe, "grouped_matmul",
        lambda lhs, rhs, sizes, impl=None: _poisoned(
            lambda a, b, s: real(a, b, s, impl=impl))(lhs, rhs, sizes))
    got = grad(*args)
    choice = _hold(moe.route(h, w_r, bias, K, 1.0)[0], held, rows)
    assert float(jnp.mean((choice < 2) | (choice >= 4))) > (
        0.6 if rows is None else 0.3)
    for g, w in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
    # a token none of whose experts is held gets NOTHING from this layer
    none_here = np.asarray(jnp.all((choice < 2) | (choice >= 4), axis=-1))
    assert none_here.any() == (rows != "one-group")
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
@pytest.mark.parametrize("layer", LAYERS)
def test_the_gradient_moves_rows_by_gathers_alone(impl, dtype, layer):
    """Both row moves own their transpose (`_dispatch`, `_combine`), so
    the gradient with respect to `h`, the weights and both stacks holds no
    scatter into anything a row wide: what stays is the `bincount` over
    the groups, the kernel's small int32 tables and the router's
    `[N, ROUTER]` scores. The prefix brings none back, in its own path or
    in the overflow's branch."""
    held, router, _ = layer
    h, w_r, bias, w_in, w_down = _setup(17, dtype, held, router)
    choice, weights = moe.route(h, w_r, bias, K, 1.0)
    routed = _routed(layer, impl)

    def loss(h, weights, w_in, w_down):
        return jnp.sum(routed(h, weights, choice, w_in, w_down).astype(
            jnp.float32))

    grad = jax.grad(loss, (0, 1, 2, 3))
    found = list(scatters(jax.make_jaxpr(grad)(h, weights, w_in,
                                               w_down).jaxpr))
    assert found                            # the bincount's, at least
    wide = [(name, shape) for name, shape, _ in found
            if len(shape) >= 2 or shape[0] > N * K]
    assert not wide, wide
    # and the whole of it, router included: [N, ROUTER] is the widest
    found = list(scatters(jax.make_jaxpr(jax.grad(
        lambda *a: _loss(routed, *a, 1.0),
        (0, 1, 3, 4)))(h, w_r, bias, w_in, w_down).jaxpr))
    assert max(shape[-1] for _, shape, _ in found if len(shape) >= 2) < E


def all_rows_wide(jaxpr):
    """The shape of every array of `N * K` rows, a row `E` wide or wider,
    that a jaxpr or a sub-jaxpr of it makes (`[N * K, E]`, `[N, K, E]`,
    `[K, N, E]`), those inside a `cond`'s branches left out."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            if (len(shape) >= 2 and shape[-1] >= E
                    and int(np.prod(shape[:-1])) == N * K):
                yield eqn.primitive.name, shape
        if eqn.primitive.name != "cond":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from all_rows_wide(sub)


@pytest.mark.parametrize("impl,dtype", [("ragged_dot", jnp.float32),
                                        ("gmm_interpret", jnp.bfloat16)])
@pytest.mark.parametrize("rows", ["mixed", 250])
def test_the_prefix_path_makes_no_array_of_all_the_sorted_rows(impl, dtype,
                                                               rows):
    """With the router's count the prefix's rows go back to token order a
    choice at a time, `K` gathers of `N` rows: forward and under
    `jax.grad`, nothing `N * K` rows long and a row wide is made outside
    the overflow's conditionals (the un-sort's `[N * K, E]` and its
    `[N, K, E]`, the dispatch's backward's `[K, N, E]`: the full-width
    layer's, which the overflow's branch keeps), whether the step would
    enter them or not, and no row is scattered."""
    held = (2, 4)
    h, w_r, bias, w_in, w_down = _setup(41, dtype, held, WIDE)
    choice, weights = moe.route(h, w_r, bias, K, 1.0)
    choice = _hold(choice, held, rows)

    def programs(router_experts):
        def layer(h, weights, w_in, w_down):
            return moe.routed_experts(
                h, choice, weights, w_in, w_down, held=held,
                router_experts=router_experts, impl=impl)[0]

        def loss(*args):
            return jnp.sum(layer(*args).astype(jnp.float32))
        args = (h, weights, w_in, w_down)
        return (jax.make_jaxpr(layer)(*args).jaxpr,
                jax.make_jaxpr(jax.grad(loss, (0, 1, 2, 3)))(*args).jaxpr)

    for jaxpr in programs(WIDE):
        assert not list(all_rows_wide(jaxpr))
        assert not [(name, shape) for name, shape, _ in scatters(jaxpr)
                    if len(shape) >= 2]
    # the reader finds them where they are: the full-width layer's un-sort
    # forward, and its planes under the gradient
    forward, grad = (list(all_rows_wide(j)) for j in programs(None))
    assert (N * K, E) in [shape for _, shape in forward]
    assert (K, N, E) in [shape for _, shape in grad]


@pytest.mark.parametrize("impl,dtype", [("ragged_dot", jnp.float32),
                                        ("ragged_dot", jnp.bfloat16),
                                        ("gmm_interpret", jnp.bfloat16)])
@pytest.mark.parametrize("layer", LAYERS)
def test_the_forward_is_the_plain_formula_to_the_bit(impl, dtype, layer):
    """take, the products, a float32 weigh, the mask, take by
    argsort(order), the sum over k: the rules change what `jax.grad`
    builds and nothing of what the layer returns, and neither does the
    prefix, whether it holds every held row or the overflow runs."""
    held, router, rows = layer
    h, w_r, bias, w_in, w_down = _setup(19, dtype, held, router)
    choice, weights = moe.route(h, w_r, bias, K, 1.0)
    choice = _hold(choice, held, rows)
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
    got, stats = jax.jit(lambda *a: moe.routed_experts(
        *a, held=held, router_experts=None if rows is None else router,
        impl=impl))(h, choice, weights, w_in, w_down)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    if rows is not None:
        past = max(int(jnp.sum(here)) - BOUND, 0)
        assert past == (max(rows - BOUND, 0) if rows not in HOLDS else 0)
        assert int(stats["moe_rows_past_prefix"]) == past
        assert int(stats["moe_layers_past_prefix"]) == (past > 0)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 0.02)])
@pytest.mark.parametrize("rows", [
    pytest.param(None, id="full-width"), pytest.param(200, id="prefix-under"),
    pytest.param(256, id="prefix-at"), pytest.param(280, id="prefix-past")])
def test_a_tokens_k_held_rows_sum_into_its_gradient(dtype, tol, rows):
    """The dispatch's backward gathers a token's k sorted rows and sums
    them. Token 0 has all three of its experts held, token 1 the same
    held expert THREE times (k colliding rows of one group), token 2 none,
    the rest as routed (or, with the prefix, 4 of 8 held: 256 of the 288
    sorted rows, forced to the case's count): `dh` is the dense masked
    sum's, row by row."""
    held = (2, 4)
    assert moe.prefix_rows(N * K, 4, ROUTER) == 256
    h, w_r, bias, w_in, w_down = _setup(23, dtype, held)
    choice, weights = moe.route(h, w_r, bias, K, 1.0)
    three = jnp.asarray([[2, 3, 5], [3, 3, 3], [0, 1, 7]], choice.dtype)
    choice = jnp.concatenate([three, _hold(
        choice[3:], held, None if rows is None else rows - 6)])
    target = jnp.asarray(np.random.default_rng(7).standard_normal((N, E)),
                         jnp.float32)

    def routed(h, weights):
        return jnp.sum(moe.routed_experts(
            h, choice, weights, w_in, w_down, held=held,
            router_experts=None if rows is None else ROUTER,
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


def test_the_bound_follows_from_what_the_caller_states():
    # the train cell: 16,384 tokens x 4, 8 of 32 held: a quarter and a
    # quarter of it more
    assert moe.prefix_rows(65536, 8, 32) == 20480
    # whole row tiles of the grouped product, rounded UP
    assert moe.prefix_rows(1000, 1, 8) == 256 == 2 * moe.GMM_TILE_M
    # never more than the rows there are: a decode step, all held
    assert moe.prefix_rows(384, 16, 256) == 128
    assert moe.prefix_rows(96, 4, 8) == 96
    assert moe.prefix_rows(65536, 32, 32) == 65536
    # a share that is not one of the router's is refused
    h, _, _, w_in, w_down = _setup(3, jnp.float32, (2, 4))
    choice = jnp.zeros((N, K), jnp.int32)
    with pytest.raises(ValueError, match="held"):
        moe.routed_experts(h, choice, jnp.ones((N, K)), w_in, w_down,
                           held=(2, 4), router_experts=4)


def test_clipped_group_sizes_against_a_hand_count():
    sizes = jnp.asarray([50, 0, 60, 30, 40], jnp.int32)
    for bound, inside in ((128, [50, 0, 60, 18, 0]),      # cuts group 3
                          (110, [50, 0, 60, 0, 0]),       # between two
                          (40, [40, 0, 0, 0, 0]),
                          (180, [50, 0, 60, 30, 40]),     # all of them
                          (256, [50, 0, 60, 30, 40])):
        got = moe._clip_sizes(sizes, bound)
        assert np.asarray(got[0]).tolist() == inside, bound
        assert np.asarray(got[0] + got[1]).tolist() == [50, 0, 60, 30, 40]
        assert int(jnp.sum(got[0])) == min(bound, 180)


@pytest.mark.parametrize("rows", [0, 1, 100, BOUND, BOUND + 1, 250, N * K])
def test_rows_past_the_prefix_are_counted(rows):
    """`moe_rows_past_prefix` against a count made row by row: the sorted
    positions at or past the bound whose row is held."""
    held = (2, 4)
    h, w_r, bias, w_in, w_down = _setup(29, jnp.float32, held, WIDE)
    choice, weights = moe.route(h, w_r, bias, K, 1.0)
    choice = _hold(choice, held, rows)
    _, stats = moe.routed_experts(h, choice, weights, w_in, w_down,
                                  held=held, router_experts=WIDE,
                                  impl="ragged_dot")
    flat = np.asarray(choice).reshape(-1)
    here = (flat >= 2) & (flat < 6)
    order = np.argsort(np.where(here, flat - 2, 4), kind="stable")
    assert int(stats["moe_rows_past_prefix"]) == int(
        here[order][BOUND:].sum()) == max(rows - BOUND, 0)
    # and the layer-step that left the fast path, once, however many rows
    assert int(stats["moe_layers_past_prefix"]) == (rows > BOUND)
    assert int(stats["moe_rows"]) == rows
    # a caller that states no count has no prefix and no such counter; one
    # whose share is all the router's experts has it, and it reads zero
    _, plain = moe.routed_experts(h, choice, weights, w_in, w_down,
                                  held=held, impl="ragged_dot")
    assert not {"moe_rows_past_prefix", "moe_layers_past_prefix"} & set(plain)
    _, whole = moe.routed_experts(h, choice, weights, w_in, w_down,
                                  held=(0, 4), router_experts=4,
                                  impl="ragged_dot")
    assert int(whole["moe_rows_past_prefix"]) == 0
    assert int(whole["moe_layers_past_prefix"]) == 0


# (held, under jax.grad): `signature` of the layer at 04ef3cb, the commit
# before the prefix
PARENTS = {(None, False): "dccbc5c216fee36f", (None, True): "f9983ed7e3f2a58b",
           ((2, 4), False): "e39f639e3428dc40",
           ((2, 4), True): "7311fdc19ed6f7ba"}


GROUPED = ("ragged_dot", "ragged_dot_general", "pallas_call")


def grouped_products(jaxpr, wrapped=False):
    """Of every grouped product of a jaxpr and its sub-jaxprs: whether a
    `cond` or a `while` lies around it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in GROUPED:
            yield wrapped
        inside = wrapped or eqn.primitive.name in ("cond", "while")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from grouped_products(sub, inside)


def mosaic_calls_outside_conditionals(hlo: str) -> list[str]:
    """The names of a compiled program's Mosaic custom calls that no
    `conditional` can reach: those of the computations that are neither a
    conditional's branch nor called from one. A device trace's readers tell
    a kernel by this name (`%gmm.3`)."""
    bodies: dict[str, str] = {}
    name = None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            name = head.group(1)
            bodies[name] = ""
        elif name is not None:
            bodies[name] += line + "\n"

    def named(pattern, text):
        return {n.strip().lstrip("%") for found in re.findall(pattern, text)
                for n in found.split(",")}

    inside = set().union(*(
        named(r"branch_computations=\{([^}]+)\}", b)
        | named(r"(?:true|false)_computation=([^,\s)]+)", b)
        for b in bodies.values()))
    grown = True
    while grown:
        called = set().union(*(named(
            r"(?:calls|to_apply|body|condition)=([^,\s)]+)", bodies[n])
            for n in inside if n in bodies)) - inside
        grown = bool(called)
        inside |= called
    return [n for comp, body in bodies.items() if comp not in inside
            for n in re.findall(
                r"(%[\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                body)]


def signature(jaxpr, out=None):
    """Every equation of a jaxpr and its sub-jaxprs: primitive and the
    shapes it reads and writes, in order."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        out.append((eqn.primitive.name,
                    tuple(str(v.aval) for v in eqn.invars),
                    tuple(str(v.aval) for v in eqn.outvars)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.append("(")
            signature(sub, out)
            out.append(")")
    return out


@pytest.mark.parametrize("impl,dtype", [("ragged_dot", jnp.float32),
                                        ("gmm_interpret", jnp.bfloat16)])
def test_the_prefix_paths_grouped_products_lie_outside_every_cond(impl,
                                                                  dtype):
    """A device trace tells a kernel by its event's own name, and inside a
    `cond` (under any transformation applied within the layer) the
    compiler names it from its `op_name`: the prefix path's products, 2
    forward and 2 + 2 backward, stand at the top level of the gradient,
    and only the overflow's lie in a branch."""
    held = (2, 4)
    h, w_r, bias, w_in, w_down = _setup(31, dtype, held, WIDE)
    choice, weights = moe.route(h, w_r, bias, K, 1.0)

    def grad_of(router_experts):
        def loss(h, weights, w_in, w_down):
            return jnp.sum(moe.routed_experts(
                h, choice, weights, w_in, w_down, held=held,
                router_experts=router_experts, impl=impl)[0].astype(
                    jnp.float32))
        return jax.make_jaxpr(jax.grad(loss, (0, 1, 2, 3)))(
            h, weights, w_in, w_down).jaxpr

    found = list(grouped_products(grad_of(WIDE)))
    assert found.count(False) == 2 + 2 + 2
    # the overflow: its forward in one branch, forward again and backward
    # in the other's
    assert found.count(True) == 2 + 2 + 2 + 2
    assert list(grouped_products(grad_of(None))) == [False] * 6


def test_without_the_routers_count_the_gradient_is_the_parents_jaxpr():
    """A caller that does not state the router's expert count (every
    serve family) gets the full-width layer, equation for equation: the
    hashes are those of the commit before the prefix (04ef3cb), `held`
    and not, forward and under `jax.grad`."""
    for (held, grad), want in PARENTS.items():
        h, w_r, bias, w_in, w_down = _setup(37, jnp.float32, held)

        def loss(h, w_r, w_in, w_down):
            choice, weights = moe.route(h, w_r, bias, K, 1.0)
            return jnp.sum(moe.routed_experts(
                h, choice, weights, w_in, w_down, held=held,
                impl="ragged_dot")[0])

        fn = jax.grad(loss, (0, 1, 2, 3)) if grad else loss
        text = repr(signature(jax.make_jaxpr(fn)(h, w_r, w_in,
                                                 w_down).jaxpr))
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == want, (
            held, grad)


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
