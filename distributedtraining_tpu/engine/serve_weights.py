"""The tree the serve programs take as their first argument, made once
per revision.

A base is published in the storage dtype (float32 for GPT-2 and Llama),
and the forward rounds most of it to the compute dtype before it uses it:
``nn.Dense`` casts kernel and bias, the head casts its operand. A server
never updates a weight, so that rounding is the same function of the same
input on every step of a revision's life; done per step it streams four
bytes a parameter from HBM to keep two, and on ``gpt2-large`` that stream
IS the decode program (PERF.md, PR 30). :func:`make` does it once, where
the base is placed on the device: at boot (``install_params``) and in the
watcher's thread when a revision is staged, so the swap stays a rebind.

Which leaves, the model family states (``cfg.rounds_first(path)``, the
way ``cfg.cache_row_widths`` states the pool's rows): only those whose
EVERY use in the serving forward casts first, so the programs compute the
numbers they computed from the float32 base, bit for bit. A tied head
(``cfg.serving_head``) multiplies by its table rounded while the lookup
reads the table as stored: its operand is a leaf of its own.

A leaf that is what the forward wants already comes back as the same
array: a tree in the compute dtype (``kanana-2-30b-a3b-l8``) passes
through, and so does a serving tree handed in again.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax

from ..utils import obs

Params = Any


@functools.lru_cache(maxsize=None)
def _cast(dtype) -> Callable:
    return jax.jit(lambda x: x.astype(dtype))  # devprof: exempt (once per leaf per revision, at install)


def _walk(cfg, base: Params, leaf: Callable) -> Params:
    """``base`` with ``leaf(x, rounded)`` in place of each leaf ``x``,
    ``rounded`` where the family says the forward rounds it first; and a
    tied head's operand, rounded from its table, beside it, unless the
    table is in the compute dtype itself."""
    head, tied = cfg.serving_head
    tree = jax.tree_util.tree_map_with_path(
        lambda path, x: leaf(x, cfg.rounds_first(
            tuple(k.key for k in path))), base)
    if head and head not in tree and tree[tied].dtype != cfg.compute_dtype():
        tree[head] = leaf(tree[tied], True)
    return tree


def make(cfg, base: Params) -> Params:
    """The serving tree of ``base`` (a host or device tree, or a serving
    tree already), on the device. A host base is placed and rounded leaf
    by leaf: its float32 tree never lies on the device whole."""
    dtype = cfg.compute_dtype()
    cast = _cast(dtype)
    rounded = 0

    def leaf(x, rounds):
        nonlocal rounded
        x = jax.device_put(x)
        if rounds and x.dtype != dtype:
            x = cast(x)
            rounded += 1
        return x

    with obs.phase("serve.weights.prepare"):
        tree = _walk(cfg, base, leaf)
        jax.block_until_ready(tree)
    obs.gauge("serve.weights.bytes", float(nbytes(tree)))
    obs.count("serve.weights.rounded_leaves", rounded)
    return tree


def abstract(cfg, base: Params) -> Params:
    """The avals of :func:`make`'s tree for a base's avals: what
    compiling a serve program ahead of time takes in place of the tree
    (tests/test_tpu_aot.py). Nothing is allocated."""
    dtype = cfg.compute_dtype()
    return _walk(cfg, base, lambda a, rounds: jax.ShapeDtypeStruct(
        a.shape, dtype if rounds else a.dtype, sharding=a.sharding))


def nbytes(tree: Params) -> int:
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))
