"""A dropless routed-expert layer: route, sort by expert, two grouped
products, weighted un-sort and sum.

One function serves a prefill's thousands of rows and a decode step's few
hundred (``slots x top_k``): the ``tokens x top_k`` rows are sorted by
their expert, so each expert's rows lie together and a GROUPED product
multiplies every group by its own expert's matrix. Each touched expert's
weights are read once per call; an expert that no row chose is not read
(the Pallas path) and no row is dropped, whatever the skew: there is no
capacity factor anywhere.

The grouped product, by a rule over backend and shape (never a probe):
JAX's own Pallas grouped matmul
(``jax.experimental.pallas.ops.tpu.megablox.gmm``) on a TPU at shapes its
tiling takes, ``jax.lax.ragged_dot`` otherwise (the CPU path, and what
the kernel is tested against). On the chip at the decode shape (384 rows
over 128 experts) the kernel takes 1.07 + 0.57 ms a layer where
``ragged_dot`` takes 1.42 + 0.94 (PERF.md, PR 27).

Under ``jax.grad`` the rows move by gathers alone: the layer has ONE
permutation (``order``: token order -> expert order) and its inverse, and
the two functions that move rows own their transposes, :func:`_dispatch`
and :func:`_combine`. Autodiff would turn each ``take`` into a row-wide
scatter-add, on the chip six times the equivalent gather's time (PERF.md,
PR 34). With ``held``, both rules zero the rows of no held group before
and behind any product (:func:`_held_rows`). Forward they add and move no
instruction: a serve program lowers to the text it had, which is why each
rule sorts for the inverse in its own forward rule (the compiler folds the
two sorts into one) and none is made ahead of the dispatch.

With ``held`` the rows of a held expert sort FIRST, so a chip that holds
``count`` of the router's experts has its work in rows ``0 ..
sum(group_sizes) - 1`` of the sorted order and nothing but zeros behind
them. A caller that also states the router's expert count gets a static
PREFIX of the sorted rows (:func:`prefix_rows`: the expected share and a
margin, whole row tiles): the dispatch gathers ``order[:P]``, both grouped
products, the activation, the weigh, the mask and the casts run on ``[P,
.]`` arrays with the group sizes clipped to the prefix, forward, in remat's
re-run and backward. The two moves back to token order, the un-sort with
its sum over ``k`` and the dispatch's backward, read the inverse
permutation BY TOKEN (``[N, k]``: token ``j``'s ``k`` sorted rows) one
choice at a time (:func:`_sum_choices`): ``k`` gathers of ``N`` rows out of
the ``[P, E]`` source in ITS dtype (a position outside the prefix reads no
row and gives zero), with the weigh, the mask and the cast to float32 made
from the gathered rows inside the one pass that adds the ``k`` planes in
the order of the choice. No array of ``N k`` rows is made and nothing is
re-tiled: the full-width un-sort writes ``f32[N k, E]``, three quarters of
it the fill, re-tiles it as ``[N, k, E]`` and reduces it, which cost the
train cell's step 5.3 ms a layer more than this does; and a spelling that
first sorts the prefix's rows by token and folds each token's adjacent rows
lost to this one, because a row gather costs by the rows it READS (the
fold gathers ``P`` and then ``N`` of them, the planes ``P``) and a row it
only fills costs its bytes (PERF.md, PR 48). The prefix path ALWAYS runs, at
the top level of the program. Held rows past ``P`` (the OVERFLOW: a router
further out of balance than the margin) are the one thing behind a
``lax.cond``: that branch runs rows ``P .. R - 1`` with the remaining
group sizes through the same functions, so no row is ever dropped; a step
that enters it pays the full-width cost on top of the prefix's, and its
grouped products are ``jax.lax.ragged_dot`` (on a TPU the compiler's own
grouped product): the dropless guarantee, not the fast path, and every run
of a step that never takes it is spared the tracing and lowering of six
more Pallas kernels (3.5 s of a 34 s warm set-up: PERF.md, PR 43). No kernel
call of the prefix path may sit under a ``cond``, nor be differentiated by
a ``jax.vjp`` of this module's own: every reader of a device trace tells a
kernel by the event's own name (``gmm``, ``tgmm``), and the compiler takes
that name from the innermost wrapper of the call's ``op_name``: ``jit(gmm)``
gives ``gmm``, the ``jvp(jit(gmm))`` that a transformation applied INSIDE
the layer leaves (a differentiated ``cond``'s branches, a rule that calls
``jax.vjp``) gives ``jvp_jit_gmm__`` (PERF.md, PRs 39 and 43). So the
prefix is differentiated by the caller's ``jax.grad``, like the full-width
layer, and only the overflow's gradient is made inside a rule.

The router is DeepSeek-V3's ``noaux_tc`` with one group: sigmoid scores
in float32, the choice made on ``scores + bias`` (the bias moves the
choice and never the weights), the chosen scores normalised to sum to one
and scaled.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# the Pallas grouped product's tiles. Rows are padded up to a multiple
# of the m-tile; a decode step has ~3 rows an expert, so every visited
# (expert, tile) pair multiplies a whole m-tile for a few live rows, and
# 128 keeps that under the time the expert's weight read takes. The whole
# contraction in one k-tile and a 512- or 1,024-wide n-tile read the
# touched experts at 704 / 664 GB/s (gate+up / down) at the decode shape,
# where (128, 512, 768) read 636 and jax.lax.ragged_dot 531 / 400
# (benchmarks/tools/ab_mla_moe.py on the chip, PERF.md PR 27)
GMM_TILE_M = 128
_GMM_TILE_K = 2048
_HIGHEST = jax.lax.Precision.HIGHEST


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def route(h: jax.Array, w_router: jax.Array, bias: jax.Array, top_k: int,
          scale: float, norm: bool = True, norm_eps: float = 1e-20
          ) -> tuple[jax.Array, jax.Array]:
    """``h`` [N, E], ``w_router`` [E, G], ``bias`` [G] -> (choice
    [N, top_k] int32, weights [N, top_k] float32). Scores are float32
    whatever the activations' dtype: a near-tie between the k-th and the
    next expert is decided here. ``norm_eps`` stands under the
    normalisation's sum (a family's release says which)."""
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(jnp.dot(
            h.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=_HIGHEST))
        _, choice = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        picked = jnp.take_along_axis(scores, choice, axis=-1)
        if norm:
            picked = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                               + norm_eps)
        return choice.astype(jnp.int32), picked * scale


def gmm_supports(k: int, n: int, dtype) -> bool:
    """The shapes the Pallas grouped matmul is given: bfloat16 operands
    (its tiles are laid out for them) and k, n that its tiles divide or
    cover in whole lane tiles."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and k % 128 == 0
            and n % 128 == 0)


def _lane_tiles(dim: int, cap: int) -> int:
    """The widest whole number of 128-lane tiles that divides ``dim`` and
    does not pass ``cap`` (2,688 = 21 tiles: 896 under 1,024 or 2,048)."""
    tiles = dim // 128
    return 128 * max(d for d in range(1, cap // 128 + 1) if tiles % d == 0)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, impl: str | None = None) -> jax.Array:
    """``lhs`` [m, k] with rows sorted by group, ``rhs`` [G, k, n],
    ``group_sizes`` [G] -> [m, n]: rows of group g times ``rhs[g]``.
    ``impl`` is for the tests and the chip's A/B; None is the rule."""
    m, k = lhs.shape
    n = rhs.shape[-1]
    if impl is None:
        impl = ("gmm" if _on_tpu() and gmm_supports(k, n, lhs.dtype)
                else "ragged_dot")
    if impl == "ragged_dot":
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes,
            precision=_HIGHEST if lhs.dtype == jnp.float32 else None,
            preferred_element_type=lhs.dtype)
    if impl not in ("gmm", "gmm_interpret"):
        raise ValueError(f"unknown grouped product {impl!r}")
    from jax.experimental.pallas.ops.tpu import megablox
    pad = -m % GMM_TILE_M
    if pad:
        # rows past sum(group_sizes) belong to no group: the kernel
        # leaves them alone and the caller never reads them
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tk = min(_GMM_TILE_K, k)
    tiling = (GMM_TILE_M, tk if k % tk == 0 else _lane_tiles(k, _GMM_TILE_K),
              1024 if n % 1024 == 0 else 512 if n % 512 == 0
              else _lane_tiles(n, 1024))
    # positional: the public gmm is a custom_vjp
    out = megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tiling, None, None,
                       False, impl == "gmm_interpret")
    return out[:m] if pad else out


def _held_rows(x: jax.Array, here: jax.Array | None, order: jax.Array
               ) -> jax.Array:
    """``x`` with every row of no held group set to ZERO (row ``r`` of
    ``x`` is flat ``(token, choice)`` row ``order[r]``), whatever lay
    there: a select, never a product. Every cotangent of both rules goes
    through it (``benchmarks/tools/lfm2_moe.py`` breaks the masks from
    outside by this name and :func:`_weigh_held`'s)."""
    if here is None:
        return x
    return jnp.where(jnp.take(here, order)[..., None], x, 0.0)


def _weigh_held(y: jax.Array, w_sorted: jax.Array, here: jax.Array | None,
                order: jax.Array) -> jax.Array:
    """``w y`` in float32 where the row's expert is held, zero elsewhere:
    a row of no group is whatever the grouped product left there."""
    return _held_rows(y.astype(jnp.float32) * w_sorted[:, None], here, order)


# how far past its expected share of the sorted rows the prefix reaches. A
# router balanced to a few percent (an auxiliary loss, a selection bias)
# must stay inside, so a share that sits exactly AT its expectation (the
# train cell: 16,384 of 65,536 rows a layer) may not sit at the bound; a
# layer past it still computes every row and pays the full width on top,
# about 12% of that cell's step. On the chip each sixteenth of margin
# costs the cell 1.0-1.7% of its rate (a quarter 54,106 tokens/s, three
# sixteenths 55,051, an eighth 55,590; the parent 47,470: PERF.md, PR 43):
# an eighth pays only where fewer than one layer-step in 18 lands between
# the two bounds, which `moe_layers_past_prefix` is there to tell
# (`moe_rows_past_prefix` counts the rows, not the layer-steps they fell in)
PREFIX_MARGIN = 0.25


def prefix_rows(rows: int, count: int, router_experts: int) -> int:
    """The static bound ``P``: of ``rows`` sorted rows, those a chip that
    holds ``count`` of the router's ``router_experts`` expects (an even
    router's share) and :data:`PREFIX_MARGIN` more, in whole row tiles of
    the grouped product, never more than ``rows``."""
    bound = math.ceil(rows * count / router_experts * (1 + PREFIX_MARGIN))
    return min(rows, -(-bound // GMM_TILE_M) * GMM_TILE_M)


def _clip_sizes(group_sizes: jax.Array, bound: int
                ) -> tuple[jax.Array, jax.Array]:
    """``group_sizes`` split at sorted row ``bound``: the rows of each
    group that lie before it and those at or past it."""
    ends = jnp.minimum(jnp.cumsum(group_sizes), bound)
    inside = jnp.diff(ends, prepend=0)
    return inside, group_sizes - inside


def _window(order: jax.Array, window: tuple[int, int] | None) -> jax.Array:
    return order if window is None else order[window[0]:window[1]]


def _take_sorted(src: jax.Array, at: jax.Array,
                 window: tuple[int, int] | None) -> jax.Array:
    """Sorted rows ``at`` of ``src``, which holds the sorted rows
    ``window = (lo, hi)`` alone: a row outside them reads ZERO (a select
    on the index, whatever ``src`` holds)."""
    axis = 0 if src.ndim > 1 else None      # as the full-width text has it
    if window is None:
        return jnp.take(src, at, axis=axis)
    lo, hi = window
    if lo:
        # jnp.take wraps a negative index: send it past the end
        at = jnp.where(at < lo, hi - lo, at - lo)
    return jnp.take(src, at, axis=axis, mode="fill", fill_value=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(h: jax.Array, here: jax.Array | None, order: jax.Array,
              window: tuple[int, int] | None) -> jax.Array:
    """Token order -> expert order: ``h[order // k]``, of all sorted rows
    or of those of ``window`` alone. Backward, a token's ``k`` sorted rows
    are GATHERED along the inverse permutation and summed in float32: no
    scatter-add into ``[N, E]`` (with a ``window``, a plane of ``N`` rows a
    gather: :func:`_sum_choices`). A row of no held group gives ZERO,
    whatever the grouped product's own backward left in an output it never
    wrote (``gmm``'s is unwritten memory): that row's expert is another
    chip's, and nothing of it may reach ``dh``; so does a row outside the
    window. ``here`` is read in the backward alone."""
    k = order.shape[0] // h.shape[0]
    return jnp.take(h, _window(order, window) // k, axis=0)


def _dispatch_fwd(h, here, order, window):
    return (_dispatch(h, here, order, window),
            (here, jnp.argsort(order).reshape(h.shape[0], -1).T))


def _sum_choices(src: jax.Array, inverse: jax.Array,
                 window: tuple[int, int], made) -> jax.Array:
    """``src`` holds the sorted rows of ``window``, ``inverse`` [k, N] the
    sorted row of every token's ``i``-th choice: ``made(rows [N, E], i,
    flat [N])`` float32, of the rows gathered for choice ``i`` (zero
    outside the window) at flat positions ``flat``, added up in the order
    of the choice. A token's float32 sum over ``k`` as the full-width layer
    makes it, to the bit on the CPU; a chip's compiler pairs the terms of
    the reduce over ``[N, k, E]`` and fuses this pass otherwise, and a few
    outputs in a million come out one rounding apart (PERF.md, PR 48). A
    plane of ``N`` rows a gather, nothing ``N k`` rows long."""
    k, N = inverse.shape
    return functools.reduce(jnp.add, (
        made(_take_sorted(src, inverse[i], window), i, jnp.arange(N) * k + i)
        for i in range(k)))


def _dispatch_bwd(window, res, g):
    # inverse [k, N]: the i-th sorted row of every token, so that the
    # gather writes k whole [N, E] planes and the sum re-tiles nothing;
    # plane i's row j is flat row j * k + i
    here, inverse = res
    k, N = inverse.shape
    if window is not None:
        return (_sum_choices(g, inverse, window, lambda rows, _, flat: (
            _held_rows(rows, here, flat).astype(jnp.float32))).astype(
                g.dtype), None, None)
    g = _take_sorted(g, inverse, window)                  # [k, N, E]
    g = _held_rows(g, here, jnp.arange(N * k).reshape(N, k).T)
    return (jnp.sum(g.astype(jnp.float32), axis=0).astype(g.dtype),
            None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _combine_fwd(y, weights, here, order, window):
    N, k = weights.shape
    if window is None:
        out = _weigh_held(y, jnp.take(weights.reshape(-1), order), here,
                          order)
        # un-sort: row r of the sorted order came from flat row order[r]
        inverse = jnp.argsort(order)
        out = jnp.sum(jnp.take(out, inverse, axis=0).reshape(N, k, -1),
                      axis=1)
    else:
        # by token: choice i of every token out of the window's rows as
        # they left the experts, weighed where it is held
        inverse = jnp.argsort(order)
        out = _sum_choices(
            y, inverse.reshape(N, k).T, window, lambda rows, i, flat: (
                _weigh_held(rows, weights[:, i], here, flat)))
    return out.astype(y.dtype), (y, weights, here, order, inverse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine(y: jax.Array, weights: jax.Array, here: jax.Array | None,
             order: jax.Array, window: tuple[int, int] | None) -> jax.Array:
    """Expert order -> token order: the held rows weighed, un-sorted along
    the inverse permutation and summed over ``k``; ``y`` holds all sorted
    rows, or those of ``window`` alone (the others add zero, and the
    un-sort gathers ``k`` planes of ``N`` rows out of ``y`` itself, weighed
    as they are added: :func:`_sum_choices`). Backward,
    the ``[N, E]`` cotangent comes to sorted order by ONE gather
    (``order // k``: no ``[N, k, E]`` broadcast, no scatter-add) and a
    weight's goes back as a gather of ``[N * k]`` scalars. A row held
    elsewhere gives zero to ``y`` AND to its weight: the mask stands
    before and behind the product with whatever the grouped product left
    in that row of ``y`` (0 x NaN)."""
    return _combine_fwd(y, weights, here, order, window)[0]


def _combine_bwd(window, res, g):
    y, weights, here, order, inverse = res
    k = weights.shape[1]
    rows = _window(order, window)
    g = _held_rows(jnp.take(g, rows // k, axis=0).astype(jnp.float32),
                   here, rows)
    dw = jnp.sum(_held_rows(g * y.astype(jnp.float32), here, rows), axis=-1)
    dy = g * jnp.take(weights.reshape(-1), rows)[:, None]
    return (dy.astype(y.dtype),
            _take_sorted(dw, inverse, window).reshape(weights.shape),
            None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


def _swiglu_gate(gate: jax.Array, limit: float | None) -> jax.Array:
    gate = gate.astype(jnp.float32)
    return jax.nn.silu(gate if limit is None else jnp.minimum(gate, limit))


def _swiglu_up(up: jax.Array, limit: float | None) -> jax.Array:
    up = up.astype(jnp.float32)
    return up if limit is None else jnp.clip(up, -limit, limit)


def clamped_swiglu(gate: jax.Array, up: jax.Array,
                   limit: float | None) -> jax.Array:
    """``silu(gate) * up`` in float32; with ``limit`` (a family's
    ``swiglu_limit``) the gate is held under it and the up half inside
    ``[-limit, limit]`` first. One spelling for the routed rows, the
    shared expert and a dense FFN."""
    return _swiglu_gate(gate, limit) * _swiglu_up(up, limit)


def _experts_sorted(x_sorted, w_in, w_down, group_sizes, impl,
                    swiglu_limit=None):
    """The two grouped products over rows already sorted by expert. What
    an expert IS follows from its two stacks: a first stack twice as wide
    as the second is deep holds gate and up fused (``[G, E, 2F]``, gate
    columns first) and the body is SwiGLU (clamped where the family
    states a ``swiglu_limit``); one as wide as the second is deep
    (``[G, L, F]``) is one matrix and the body is squared ReLU."""
    F = w_down.shape[1]
    up = grouped_matmul(x_sorted, w_in, group_sizes, impl=impl)
    if w_in.shape[-1] == 2 * F:
        # the gate's half first, whole, then the up half's slice: the
        # order the serve programs' pinned text has
        act = (_swiglu_gate(up[:, :F], swiglu_limit)
               * _swiglu_up(up[:, F:], swiglu_limit)).astype(x_sorted.dtype)
    elif w_in.shape[-1] == F:
        act = jnp.square(jax.nn.relu(up.astype(jnp.float32))).astype(
            x_sorted.dtype)
    else:
        raise ValueError(f"expert stacks {w_in.shape} / {w_down.shape} are "
                         "neither a fused SwiGLU nor a squared-ReLU pair")
    return grouped_matmul(act, w_down, group_sizes, impl=impl)


def _rows(h, weights, w_in, w_down, here, order, sizes, window, impl,
          swiglu_limit):
    """What the sorted rows of ``window`` (None: all of them), ``sizes`` of
    them to each group, add to the layer's ``[N, E]``; and those rows as
    they left the experts."""
    y = _experts_sorted(_dispatch(h, here, order, window), w_in, w_down,
                        sizes, impl, swiglu_limit)
    return _combine(y, weights, here, order, window), y


# The overflow: held rows past the prefix, behind a ``lax.cond`` on their
# count. The prefix itself is differentiated by the caller's own
# ``jax.grad``, at the top level; two functions frame it so that the step
# WITHOUT an overflow pays nothing for the branch it does not take.
# :func:`_overflow_out`, behind the prefix, makes the forward;
# :func:`_overflow_in`, ahead of it, is the identity forward, so its
# transpose runs AFTER the prefix's and is handed the prefix's gradients:
# the branch adds the overflow's to them, the other branch hands them
# through untouched. Left to autodiff, the branch not taken would fill a
# zero for every residual and cotangent of the one that is, and add them.
# ``static = (bound, impl, swiglu_limit)``.

@functools.partial(jax.jit, static_argnames=("static",))
def _overflow_rows(y, operands, here, order, beyond, *, static):
    """The layer at its full width: the rows past the prefix through the
    experts, behind the prefix's own ``y``, and ONE un-sort and sum over
    ``k`` of them all, so that the result is the plain formula's to the bit
    whatever the routing (a sum of two ``[N, E]`` would round a token's
    ``k`` rows in another order). Jitted, like :func:`_overflow_grads`: a
    step's routed layers then trace and lower ONE such function, not one a
    layer (a warm set-up pays for every branch the step holds)."""
    bound, impl, swiglu_limit = static
    h, weights, w_in, w_down = operands
    rest = _experts_sorted(
        _dispatch(h, here, order, (bound, order.shape[0])), w_in, w_down,
        beyond, impl, swiglu_limit)
    return _combine(jnp.concatenate([y, rest]), weights, here, order, None)


@functools.partial(jax.jit, static_argnames=("static",))
def _overflow_grads(g, operands, here, order, beyond, *, static):
    """The gradients, with respect to ``operands``, of what the sorted
    rows past the prefix add to the layer's ``[N, E]``."""
    bound, impl, swiglu_limit = static
    _, rest = jax.vjp(
        lambda *operands: _rows(
            *operands, here, order, beyond, (bound, order.shape[0]), impl,
            swiglu_limit)[0], *operands)
    return rest(g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _overflow_in(operands, here, order, beyond, static):
    """``operands = (h, weights, w_in, w_down)`` as the prefix reads them,
    and a ``[N, E]`` of zeros that :func:`_overflow_out` takes and never
    reads: its cotangent is the layer's output's, which the overflow's
    gradients need here."""
    return operands, jnp.zeros_like(operands[0])


def _overflow_in_fwd(operands, here, order, beyond, static):
    return ((operands, jnp.zeros_like(operands[0])),
            (operands, here, order, beyond))


def _overflow_in_bwd(static, res, cotangents):
    operands, here, order, beyond = res
    grads, g = cotangents
    return (jax.lax.cond(
        jnp.sum(beyond) > 0,
        lambda grads: jax.tree.map(jnp.add, grads, _overflow_grads(
            g, operands, here, order, beyond, static=static)),
        lambda grads: grads, grads), None, None, None)


_overflow_in.defvjp(_overflow_in_fwd, _overflow_in_bwd)


def _overflow_out_fwd(out, carrier, y, operands, here, order, beyond,
                      static):
    return jax.lax.cond(
        jnp.sum(beyond) > 0,
        lambda out, y: _overflow_rows(y, operands, here, order, beyond,
                                      static=static),
        lambda out, y: out, out, y), None


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _overflow_out(out, carrier, y, operands, here, order, beyond, static):
    """``out`` where the prefix held every held row, else
    :func:`_overflow_rows`'s."""
    return _overflow_out_fwd(out, carrier, y, operands, here, order, beyond,
                             static)[0]


_overflow_out.defvjp(
    _overflow_out_fwd,
    # the prefix's share of the output is the output's cotangent whole,
    # and so is what the overflow's gradients are made from
    lambda static, _, g: (g, g, None, None, None, None, None))


def routed_experts(h: jax.Array, choice: jax.Array, weights: jax.Array,
                   w_in: jax.Array, w_down: jax.Array, *,
                   held: tuple[int, int] | None = None,
                   router_experts: int | None = None,
                   live: jax.Array | None = None,
                   impl: str | None = None,
                   count_fullest: bool = False,
                   swiglu_limit: float | None = None
                   ) -> tuple[jax.Array, dict]:
    """``sum_e w_e E_e(h)`` over the chosen experts of every row THAT ARE
    HELD HERE.

    ``h`` [N, E]; ``choice``/``weights`` [N, k], the choice made over all
    the router's experts; ``w_in`` / ``w_down`` the stacks of the ``G``
    experts held (:func:`_experts_sorted` says which body they mean).
    ``held = (first, count)``: the stacks are experts ``first .. first +
    count - 1`` of the router's; a choice outside them is another chip's
    to compute (expert parallelism): its row is sorted behind the last
    held group, where the grouped product leaves rows alone, and adds
    zero here, forward AND backward: its gradient with respect to ``h``,
    to its weight and to every stack is exactly zero by construction, on
    every path of the grouped product. None: the stacks are all the
    router's experts, and the same two functions move the rows with no
    mask (:func:`_dispatch` owns the transpose of token -> expert order,
    :func:`_combine` that of the weigh, the un-sort and the sum over
    ``k``: gathers along ``order`` and its inverse, no scatter).
    ``router_experts``, beside ``held``: how many experts the router
    chooses among. With it only the first :func:`prefix_rows` sorted rows
    are gathered, multiplied, activated, weighed and un-sorted, under no
    ``cond``; held rows past them (counted: "moe_rows_past_prefix") run
    behind one, through the same functions, so the layer stays dropless
    whatever the routing, and a step that has any pays the full width's
    cost PLUS the prefix's (through ``ragged_dot`` unless ``impl`` says
    otherwise). Without it (and where the bound is all the
    rows) the layer is the full-width one, instruction for instruction.
    ``swiglu_limit`` clamps a SwiGLU body (:func:`clamped_swiglu`).
    ``live`` [N] bool marks the rows that are not padding: every row is
    computed
    (shapes are static) and only live ones are counted. Returns ([N, E]
    in ``h``'s dtype, int32 scalars {"moe_rows": live rows computed here,
    "moe_experts_touched"} and, with ``held``, "moe_rows_elsewhere";
    with ``router_experts`` "moe_rows_past_prefix" and
    "moe_layers_past_prefix" (1 where this layer has any such row: it left
    the fast path); with ``count_fullest`` also "moe_rows_fullest", the
    rows of the fullest held expert)."""
    N, k = choice.shape
    G = w_in.shape[0]
    flat, here = choice.reshape(-1), None                # [N*k]
    bound = N * k
    if held is not None:
        first, count = held
        if count != G:
            raise ValueError(f"held {held} but the stacks hold {G} experts")
        here = (flat >= first) & (flat < first + count)
        flat = jnp.where(here, flat - first, G)          # G: not ours
        if router_experts is not None:
            if first + count > router_experts:
                raise ValueError(f"held {held} of a router's "
                                 f"{router_experts} experts")
            bound = prefix_rows(N * k, count, router_experts)
    order = jnp.argsort(flat, stable=True)
    group_sizes = jnp.bincount(flat, length=G).astype(jnp.int32)
    past = jnp.int32(0)
    with jax.named_scope("moe.experts"):
        if bound == N * k:
            out, _ = _rows(h, weights, w_in, w_down, here, order,
                           group_sizes, None, impl, swiglu_limit)
        else:
            inside, beyond = _clip_sizes(group_sizes, bound)
            past = jnp.sum(beyond)
            # the overflow's products are `ragged_dot` unless the caller
            # chose: the compiler's own grouped product on a TPU, so a
            # step that never overflows traces and lowers no second set of
            # Pallas kernels for the rows it never runs
            static = (bound, impl or "ragged_dot", swiglu_limit)
            operands, carrier = _overflow_in((h, weights, w_in, w_down),
                                             here, order, beyond, static)
            out, y = _rows(*operands, here, order, inside, (0, bound), impl,
                           swiglu_limit)
            out = _overflow_out(out, carrier, y, (h, weights, w_in, w_down),
                                here, order, beyond, static)
    if live is None:
        live_flat = None
        rows, touched = jnp.int32(N * k), jnp.sum(group_sizes > 0)
    else:
        live_flat = jnp.repeat(live.astype(jnp.int32), k)
        rows = jnp.sum(live_flat)
        # a choice held elsewhere indexes past the held experts: dropped
        touched = jnp.sum(jnp.zeros((G,), jnp.int32).at[flat].add(
            live_flat, mode="drop" if held is not None else None) > 0)
    stats = {"moe_rows": rows.astype(jnp.int32),
             "moe_experts_touched": touched.astype(jnp.int32)}
    if held is not None:
        ours = jnp.sum(here if live_flat is None else here * live_flat)
        stats.update(moe_rows=ours.astype(jnp.int32),
                     moe_rows_elsewhere=(rows - ours).astype(jnp.int32))
    if router_experts is not None:
        stats["moe_rows_past_prefix"] = past
        stats["moe_layers_past_prefix"] = (past > 0).astype(jnp.int32)
    if count_fullest:
        stats["moe_rows_fullest"] = jnp.max(group_sizes)
    return out, stats
