"""A plain GPT-2, written from the GPT-2 description, for the benchmark's
`correct` decision. It imports nothing of the program.

`jax.numpy`, float32, every matrix product at `highest` precision, no
kernels, no KV cache, no remat. One block is one jitted function that a
Python loop calls layer by layer, so that 36 or 48 layers compile as one
small program and the training reference never holds more than one layer's
activations and gradients.

Layout of the parameters (the GPT-2 release's own names):
    wte [V, E], wpe [P, E], ln_f {g, b},
    h[i] {ln_1 {g,b}, c_attn {w [E,3E], b}, c_proj {w [E,E], b},
          ln_2 {g,b}, c_fc {w [E,4E], b}, mlp_proj {w [4E,E], b}}

Departures from the release, each because the system under test states it:
the vocabulary is padded to `padded_vocab` rows (logits beyond `vocab_size`
are never targets and are cut before any argmax), and packed rows carry
`segment_ids` / `position_ids` (attention is block-diagonal inside a row,
positions restart per document).

`precision` puts the same mathematics through a lower precision for the
controls: "bfloat16" rounds both operands of every matrix product to
bfloat16, "fp8" to float8_e4m3 with one scale per tensor. Sums stay
float32 in all of them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "fp8")
_HIGHEST = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A raw threefry key from any whole number (the driver's seeds pass
    2**31): the two 32-bit halves of the seed, with `stream` folded in."""
    seed = int(seed)
    data = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.fold_in(jnp.asarray(data), stream)


def leaf_specs(cfg: dict) -> list[tuple[str, tuple, str, float]]:
    """(name, shape, kind, std) for every parameter, in a fixed order.
    Matrices are N(0, 0.02), the two projections into the residual stream
    scaled by 1/sqrt(2 L) as in the release; `wpe` N(0, 0.01); biases
    N(0, 0.01) and gains 1 + N(0, 0.02) so that no gradient is trivially
    zero or shared between leaves."""
    E, L = cfg["n_embd"], cfg["n_layer"]
    V, P = cfg["padded_vocab"], cfg["n_positions"]
    res = 0.02 / math.sqrt(2 * L)
    out = [("wte", (V, E), "normal", 0.02), ("wpe", (P, E), "normal", 0.01)]
    for i in range(L):
        p = f"h.{i}."
        out += [(p + "ln_1.g", (E,), "gain", 0.02),
                (p + "ln_1.b", (E,), "normal", 0.01),
                (p + "c_attn.w", (E, 3 * E), "normal", 0.02),
                (p + "c_attn.b", (3 * E,), "normal", 0.01),
                (p + "c_proj.w", (E, E), "normal", res),
                (p + "c_proj.b", (E,), "normal", 0.01),
                (p + "ln_2.g", (E,), "gain", 0.02),
                (p + "ln_2.b", (E,), "normal", 0.01),
                (p + "c_fc.w", (E, 4 * E), "normal", 0.02),
                (p + "c_fc.b", (4 * E,), "normal", 0.01),
                (p + "mlp_proj.w", (4 * E, E), "normal", res),
                (p + "mlp_proj.b", (E,), "normal", 0.01)]
    out += [("ln_f.g", (E,), "gain", 0.02), ("ln_f.b", (E,), "normal", 0.01)]
    return out


def _nest(flat: dict, n_layer: int) -> dict:
    tree = {"wte": flat["wte"], "wpe": flat["wpe"],
            "ln_f": {"g": flat["ln_f.g"], "b": flat["ln_f.b"]}, "h": []}
    for i in range(n_layer):
        blk: dict = {}
        for name, val in flat.items():
            if name.startswith(f"h.{i}."):
                mod, leaf = name[len(f"h.{i}."):].split(".")
                blk.setdefault(mod, {})[leaf] = val
        tree["h"].append(blk)
    return tree


def init_weights(cfg: dict, seed: int) -> dict:
    """Every parameter, float32, on the default device, in ONE jitted call
    from the seed. The same seed gives the same values on any call."""
    specs = leaf_specs(cfg)

    def make(key):
        flat = {}
        for i, (name, shape, kind, std) in enumerate(specs):
            x = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
            flat[name] = 1.0 + x if kind == "gain" else x
        return _nest(flat, cfg["n_layer"])

    return jax.jit(make)(seed_key(seed))


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _round_to(x, precision: str):
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    raise ValueError(f"precision must be one of {PRECISIONS}")


def _q(x, precision: str):
    """An operand of a matrix product, rounded to `precision`. The rounding
    is straight-through for the backward pass (the cotangent stays float32
    and meets the rounded operands), which is how a lower-precision
    forward is trained."""
    if precision == "float32":
        return x
    return x + jax.lax.stop_gradient(_round_to(x, precision) - x)


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _q(a, precision), _q(b, precision),
                      precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(p, x, seg, *, n_head: int, eps: float, precision: str):
    """One pre-LN transformer block. x [B, T, E] float32; seg [B, T]."""
    B, T, E = x.shape
    D = E // n_head
    h = _ln(x, p["ln_1"], eps)
    qkv = _mm("bte,ef->btf", h, p["c_attn"]["w"], precision) \
        + p["c_attn"]["b"]
    q, k, v = (t.reshape(B, T, n_head, D) for t in jnp.split(qkv, 3, -1))
    s = _mm("bthd,bshd->bhts", q, k, precision) / math.sqrt(D)
    pos = jnp.arange(T)
    allowed = (pos[:, None] >= pos[None, :])[None, None] \
        & (seg[:, None, :, None] == seg[:, None, None, :])
    w = jax.nn.softmax(jnp.where(allowed, s, -1e30), axis=-1)
    a = _mm("bhts,bshd->bthd", w, v, precision).reshape(B, T, E)
    x = x + _mm("bte,ef->btf", a, p["c_proj"]["w"], precision) \
        + p["c_proj"]["b"]
    h = _ln(x, p["ln_2"], eps)
    h = _gelu_new(_mm("bte,ef->btf", h, p["c_fc"]["w"], precision)
                  + p["c_fc"]["b"])
    return x + _mm("bte,ef->btf", h, p["mlp_proj"]["w"], precision) \
        + p["mlp_proj"]["b"]


def _embed(wte, wpe, ids, pos):
    return wte[ids] + wpe[pos]


def _head(wte, ln_f, x, eps, precision):
    return _mm("bte,ve->btv", _ln(x, ln_f, eps), wte, precision)


def _masked_loss(logits, ids, loss_mask):
    """Shifted next-token cross-entropy over the padded vocabulary (the
    padding rows are ordinary, never-targeted classes), weighted by
    `loss_mask` at the label's position as the program's loss states it."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    m = loss_mask[:, 1:].astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)


class Reference:
    """The jitted pieces for one configuration and one precision."""

    def __init__(self, cfg: dict, precision: str = "float32"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}")
        self.cfg = cfg
        self.precision = precision
        kw = dict(n_head=cfg["n_head"], eps=cfg["layer_norm_epsilon"],
                  precision=precision)
        eps = cfg["layer_norm_epsilon"]
        blk = functools.partial(block, **kw)
        self.block = jax.jit(blk)
        self.embed = jax.jit(_embed)
        self.head = jax.jit(functools.partial(_head, eps=eps,
                                              precision=precision))

        def block_bwd(p, x, seg, dy):
            _, vjp = jax.vjp(lambda p_, x_: blk(p_, x_, seg), p, x)
            return vjp(dy)

        def head_loss(wte, ln_f, x, ids, loss_mask):
            return _masked_loss(_head(wte, ln_f, x, eps, precision), ids,
                                loss_mask)

        def embed_bwd(wte_shape_like, wpe_shape_like, ids, pos, dx):
            dwte = jnp.zeros_like(wte_shape_like).at[ids].add(dx)
            dwpe = jnp.zeros_like(wpe_shape_like).at[pos].add(dx)
            return dwte, dwpe

        self.block_bwd = jax.jit(block_bwd)
        self.head_loss_grad = jax.jit(
            jax.value_and_grad(head_loss, argnums=(0, 1, 2)))
        self.embed_bwd = jax.jit(embed_bwd)

    # -- inference ----------------------------------------------------------
    def logits(self, params, ids, *, segment_ids=None, position_ids=None):
        """[B, T, padded_vocab] float32 logits of a full forward pass."""
        ids = jnp.asarray(ids, jnp.int32)
        B, T = ids.shape
        seg = (jnp.zeros((B, T), jnp.int32) if segment_ids is None
               else jnp.asarray(segment_ids, jnp.int32))
        pos = (jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
               if position_ids is None
               else jnp.asarray(position_ids, jnp.int32))
        x = self.embed(params["wte"], params["wpe"], ids, pos)
        for p in params["h"]:
            x = self.block(p, x, seg)
        return self.head(params["wte"], params["ln_f"], x)

    # -- training -----------------------------------------------------------
    def loss_and_grads(self, params, batch, on_leaf_grads):
        """Loss of one packed batch and its gradients, layer by layer:
        the forward pass keeps each block's input, the backward pass
        recomputes one block at a time and hands every finished gradient
        to `on_leaf_grads(where, grads)` (where = "ln_f", ("h", i),
        "wpe", "wte"), so that no more than one block's gradients are
        alive. `wte` comes last: it is tied, and takes the head's and the
        embedding's gradient together."""
        ids = jnp.asarray(batch["input_ids"], jnp.int32)
        seg = jnp.asarray(batch["segment_ids"], jnp.int32)
        pos = jnp.asarray(batch["position_ids"], jnp.int32)
        mask = jnp.asarray(batch["loss_mask"], jnp.float32)
        xs = [self.embed(params["wte"], params["wpe"], ids, pos)]
        for p in params["h"]:
            xs.append(self.block(p, xs[-1], seg))
        loss, (dwte, dlnf, dx) = self.head_loss_grad(
            params["wte"], params["ln_f"], xs.pop(), ids, mask)
        on_leaf_grads("ln_f", dlnf)
        for i in reversed(range(len(params["h"]))):
            dp, dx = self.block_bwd(params["h"][i], xs.pop(), seg, dx)
            on_leaf_grads(("h", i), dp)
        dwte_e, dwpe = self.embed_bwd(params["wte"], params["wpe"], ids, pos,
                                      dx)
        on_leaf_grads("wpe", dwpe)
        on_leaf_grads("wte", dwte + dwte_e)
        return loss


# ---------------------------------------------------------------------------
# AdamW (Loshchilov & Hutter, decoupled decay on every parameter)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "wd"),
                   donate_argnums=(0, 1, 2))
def _adamw(p, m, v, g, t, *, lr, b1, b2, eps, wd):
    def one(p_, m_, v_, g_):
        m_ = b1 * m_ + (1 - b1) * g_
        v_ = b2 * v_ + (1 - b2) * g_ * g_
        mhat = m_ / (1 - b1 ** t)
        vhat = v_ / (1 - b2 ** t)
        return p_ - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p_), m_, v_

    out = jax.tree_util.tree_map(one, p, m, v, g)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


_norms = jax.jit(lambda t: jax.tree_util.tree_map(
    lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))
_diff_norms = jax.jit(lambda a, b: jax.tree_util.tree_map(
    lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))


def _flat_where(where, sub) -> dict:
    """One piece of the tree, as `loss_and_grads` names it -> release
    names."""
    if where in ("wte", "wpe"):
        return {where: sub}
    if where == "ln_f":
        return {f"ln_f.{leaf}": x for leaf, x in sub.items()}
    return {f"h.{where[1]}.{mod}.{leaf}": x
            for mod, leaves in sub.items() for leaf, x in leaves.items()}


def flat_names(tree: dict) -> dict:
    """Reference-layout tree -> {release name: leaf}."""
    out = {}
    for where in ("wte", "wpe", "ln_f"):
        out.update(_flat_where(where, tree[where]))
    for i, blk in enumerate(tree["h"]):
        out.update(_flat_where(("h", i), blk))
    return out


def train_reference(cfg: dict, seed: int, batches: list, *, lr: float,
                    weight_decay: float, b1: float = 0.9, b2: float = 0.999,
                    eps: float = 1e-8, precision: str = "float32") -> dict:
    """Follow the first `len(batches)` AdamW steps from the seed's weights.

    Returns the loss of each step, the norm of every leaf of the FIRST
    gradient, and the norm of every leaf's change over all the steps, the
    latter two as {release name: float}."""
    ref = Reference(cfg, precision)
    params = init_weights(cfg, seed)
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    m, v = zeros(params), zeros(params)
    losses, grad_norms = [], {}
    hp = dict(lr=lr, b1=b1, b2=b2, eps=eps, wd=weight_decay)

    for step, batch in enumerate(batches, start=1):
        t = jnp.float32(step)

        def on_leaf_grads(where, g, step=step, t=t):
            if step == 1:
                grad_norms.update(_flat_where(where, _norms(g)))
            if isinstance(where, str):
                params[where], m[where], v[where] = _adamw(
                    params[where], m[where], v[where], g, t, **hp)
            else:
                i = where[1]
                params["h"][i], m["h"][i], v["h"][i] = _adamw(
                    params["h"][i], m["h"][i], v["h"][i], g, t, **hp)

        losses.append(float(ref.loss_and_grads(params, batch,
                                               on_leaf_grads)))
    del m, v
    change = flat_names(_diff_norms(params, init_weights(cfg, seed)))
    return {"losses": losses,
            "grad_norms": {k: float(x) for k, x in grad_norms.items()},
            "change_norms": {k: float(x) for k, x in change.items()}}
