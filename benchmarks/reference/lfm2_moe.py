"""A plain LFM2-MoE-family decoder (`model_type: lfm2_moe`) with its loss,
its gradients and its AdamW, written from the layer equations, for the
benchmark's `correct` decision. It imports nothing of the program.

`jax.numpy`, float32, every matrix product at `highest` precision, layer by
layer: no kernels, no sorting, no remat. All matrices without bias;
`rmsnorm(x) = x rsqrt(mean(x^2) + norm_eps) g`. With `x` the residual
stream, a layer is `x <- x + mixer(rmsnorm_operator(x))`, then `x <- x +
ffn(rmsnorm_ffn(x))`; after the last layer `embedding_norm`, then the
logits through the TIED embedding.

  conv            `[B | C | u] = h W_in` (three thirds, in that order);
                  `v = B * u`; `c_t = sum_j w_j * v_{t-2+j}` (j = 0..2) per
                  channel, written as three shifted adds, a source position
                  before the row's start or in another SEGMENT of a packed
                  row adding zero; `out = (C * c) W_out`. No activation:
                  the gates are linear.
  full_attention  `q = h W_q` as H_q heads of D, `k`, `v` as H_kv heads;
                  an rmsnorm over the D of each head on q and on k (one
                  gain of D each); rotary on q and k at `rope_theta`, the
                  HALVES rotated (lane i with lane i + D/2), positions from
                  `position_ids` (they restart per document); causal
                  softmax at D^-0.5 under a segment mask, K/V head g
                  serving query heads g H_q/H_kv .. (g+1) H_q/H_kv - 1;
                  `out = concat(P v) W_out`. Dense [T, T] scores, one query
                  head of one row at a time so that they fit.
  dense FFN       (layers before `num_dense_layers`) `W2(silu(W1 h) * W3
                  h)`.
  routed FFN      `s = sigmoid(h W_r)` over ALL `num_experts`; the choice is
                  the `num_experts_per_tok` largest of `s + expert_bias`;
                  `w = s[choice] / (sum s[choice] + 1e-6) *
                  routed_scaling_factor`; `out = sum over the chosen experts
                  that are HELD of w_e W2_e(silu(W1_e h) * W3_e h)`, a dense
                  masked sum over the held experts (`experts_held = (first,
                  count)`: the chip's share of an expert-parallel
                  deployment). What the absent experts would have added is
                  left out, here as in the program. No shared expert.
  loss            mean next-token cross-entropy over the held ids, position
                  t's logits against token t+1, weighted by `loss_mask[t +
                  1]`: the mask is read AT THE LABEL'S POSITION, as the
                  program's `causal_lm_loss` reads it (the packer marks "has
                  a successor in its document" on the token itself; PERF.md
                  section 7 keeps that off-by-one as an open question).
  AdamW           decoupled decay on every parameter; `expert_bias` is a
                  buffer: no gradient reaches it, and it is neither moved
                  nor decayed.

Departures from the published description, each also under the
configuration's `assumed`: the head is TIED to the embedding (the catalog's
row drops the key; 8.34B with a tied head matches the stated 8.3B); the
1e-6 under the normalisation's sum is from memory of the release's code;
`expert_bias` is frozen (the config publishes neither a balancing loss nor
an update rule); the initialisers are this file's. The vocabulary is the
chip's slice: ids, logits and loss are over `vocab_size` held ids.

Weights are made leaf by leaf from the seed and the leaf's NAME (an expert's
from its index among ALL the router's experts, so that every share of a
deployment draws its own experts and the shares tile the uncut layer):
matrices N(0, 0.02), gains 1 + N(0, 0.02), `expert_bias` N(0, 0.02), the
convolution's taps U(-1/sqrt(K), 1/sqrt(K)). float32, as the configuration
states its parameters.

A configuration of ONE share of an expert-parallel layer may state
`assumed.expert_bias_tiers` (`bias_tiers`): the base's `expert_bias` then
HOLDS THE SHARE'S LOAD where the deployment's balancing would hold it. On
top of the N(0, 0.02), `elsewhere_count` experts held elsewhere (which ones
is the seed's and the layer's lot) stand `elsewhere` higher and every held
expert `held` higher; with the scores `s` in (0, 1) and steps wider than 1
between the tiers, every token's choice is those experts elsewhere and,
for what is left of its `num_experts_per_tok`, the held experts with its
largest `s`. The rows a share computes are then the same in every step of
every seed whatever the router learns, which a share trained ALONE, with
neither the exchange nor a rule for the bias, does not give by itself
(PERF.md section 6, PR 33). Without the key the bias is the plain draw.

`precision` puts the same mathematics through a lower precision for the
control: "bfloat16" rounds both operands of every matrix product (the
router's too) to bfloat16, "fp8" to float8_e4m3 with one scale per tensor,
straight-through for the backward pass. Sums stay float32.
"""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "fp8")
_HIGHEST = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0
MATRIX_STD = 0.02
BUFFERS = ("feed_forward.expert_bias",)


def model_cfg(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file. The
    file's `num_experts` and `vocab_size` count what is HELD; the router's
    width is the published count beside them."""
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers", "layer_types",
            "num_dense_layers", "num_attention_heads", "num_key_value_heads",
            "conv_L_cache", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "norm_eps", "rope_theta")
    cfg = {k: config[k] for k in keys}
    cfg["num_experts"] = config.get("published", {}).get(
        "num_experts", config["num_experts"])
    cfg["experts_held"] = tuple(config.get(
        "experts_held", (0, config["num_experts"])))
    assumed = config.get("assumed", {})
    cfg["padded_vocab"] = assumed.get("padded_vocab", config["vocab_size"])
    cfg["route_norm_eps"] = assumed.get("route_norm_eps", 1e-6)
    cfg["matrix_std"] = assumed.get("matrix_std", MATRIX_STD)
    cfg["bias_tiers"] = assumed.get("expert_bias_tiers")
    return cfg


def _routed(cfg: dict, i: int) -> bool:
    return i >= cfg["num_dense_layers"]


# ---------------------------------------------------------------------------
# weights from the seed and the leaf's name
# ---------------------------------------------------------------------------

def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A raw threefry key from any whole number (the driver's seeds pass
    2**31): the two 32-bit halves of the seed, with `stream` folded in."""
    seed = int(seed)
    data = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.fold_in(jnp.asarray(data), stream)


def layer_specs(cfg: dict, i: int) -> list[tuple[str, tuple, str]]:
    """(leaf, shape, kind) of layer i. Matrices are stored [in, out]; an
    expert stack is [held, in, out], `experts_in` holding W1 (the gate)
    and W3 side by side, W1's columns first."""
    E = cfg["hidden_size"]
    out = [("operator_norm", (E,), "gain"), ("ffn_norm", (E,), "gain")]
    if cfg["layer_types"][i] == "conv":
        out += [("conv.in_proj", (E, 3 * E), "matrix"),
                ("conv.conv", (cfg["conv_L_cache"], E), "conv"),
                ("conv.out_proj", (E, E), "matrix")]
    else:
        Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        D = E // Hq
        out += [("self_attn.q_proj", (E, Hq * D), "matrix"),
                ("self_attn.k_proj", (E, Hkv * D), "matrix"),
                ("self_attn.v_proj", (E, Hkv * D), "matrix"),
                ("self_attn.out_proj", (Hq * D, E), "matrix"),
                ("self_attn.q_layernorm", (D,), "gain"),
                ("self_attn.k_layernorm", (D,), "gain")]
    if not _routed(cfg, i):
        F = cfg["intermediate_size"]
        return out + [("feed_forward.w1", (E, F), "matrix"),
                      ("feed_forward.w3", (E, F), "matrix"),
                      ("feed_forward.w2", (F, E), "matrix")]
    F, held = cfg["moe_intermediate_size"], cfg["experts_held"][1]
    return out + [("feed_forward.gate", (E, cfg["num_experts"]), "matrix"),
                  ("feed_forward.expert_bias", (cfg["num_experts"],), "bias"),
                  ("feed_forward.experts_in", (held, E, 2 * F), "experts_in"),
                  ("feed_forward.experts_down", (held, F, E), "experts_down")]


def _leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _make_leaf(key, name: str, shape, kind: str, cfg: dict):
    std = cfg["matrix_std"]
    normal = lambda n, s: std * jax.random.normal(  # noqa: E731
        _leaf_key(key, n), s, jnp.float32)
    if kind == "matrix":
        return normal(name, shape)
    if kind == "gain":
        return 1.0 + 0.02 * jax.random.normal(_leaf_key(key, name), shape,
                                              jnp.float32)
    if kind == "bias":
        b = 0.02 * jax.random.normal(_leaf_key(key, name), shape,
                                     jnp.float32)
        if cfg.get("bias_tiers"):
            b = b + _bias_tiers(_leaf_key(key, name + ".elsewhere"), cfg)
        return b
    if kind == "conv":
        bound = shape[0] ** -0.5
        return jax.random.uniform(_leaf_key(key, name), shape, jnp.float32,
                                  -bound, bound)
    # an expert's matrices by its index among ALL the router's experts
    first = cfg["experts_held"][0]
    stem = name.rsplit(".", 1)[0]
    held, rows, cols = shape
    if kind == "experts_in":
        return jnp.stack([jnp.concatenate(
            [normal(f"{stem}.experts.{first + e}.{w}", (rows, cols // 2))
             for w in ("w1", "w3")], axis=-1) for e in range(held)])
    if kind == "experts_down":
        return jnp.stack([normal(f"{stem}.experts.{first + e}.w2",
                                 (rows, cols)) for e in range(held)])
    raise ValueError(kind)


def _bias_tiers(key, cfg: dict):
    """[num_experts] float32: `held` on the held experts, `elsewhere` on
    `elsewhere_count` of the others, drawn from `key`; 0 on the rest. The
    steps between tiers must pass 1 (the width of `s`) with room for the
    N(0, 0.02) beside them, and a token must have a choice left for the
    held experts."""
    tiers, G = cfg["bias_tiers"], cfg["num_experts"]
    first, count = cfg["experts_held"]
    n = tiers["elsewhere_count"]
    if not (0 < n < cfg["num_experts_per_tok"] and n <= G - count
            and tiers["held"] >= 1.5
            and tiers["elsewhere"] - tiers["held"] >= 1.5):
        raise ValueError(f"expert_bias_tiers {tiers} cannot hold the choice")
    e = jnp.arange(G)
    here = (e >= first) & (e < first + count)
    lot = jnp.where(here, -1.0, jax.random.uniform(key, (G,)))
    pinned = jnp.zeros(G, bool).at[jax.lax.top_k(lot, n)[1]].set(True)
    return (tiers["held"] * here + tiers["elsewhere"] * pinned).astype(
        jnp.float32)


def init_layer(cfg: dict, seed: int, i: int) -> dict:
    """Layer i's leaves {leaf: array}, float32, in one jitted call."""
    specs = layer_specs(cfg, i)

    def make(key):
        return {leaf: _make_leaf(key, f"layers.{i}.{leaf}", shape, kind, cfg)
                for leaf, shape, kind in specs}

    return jax.jit(make)(seed_key(seed))


def init_weights(cfg: dict, seed: int) -> dict:
    """{"embed_tokens" [V, E], "embedding_norm" [E], "layers": [{leaf:
    array}]}. The same seed gives the same values on any call."""
    E, V = cfg["hidden_size"], cfg["padded_vocab"]

    def ends(key):
        return {"embed_tokens": _make_leaf(key, "embed_tokens", (V, E),
                                           "matrix", cfg),
                "embedding_norm": _make_leaf(key, "embedding_norm", (E,),
                                             "gain", cfg)}

    tree = jax.jit(ends)(seed_key(seed))
    tree["layers"] = [init_layer(cfg, seed, i)
                      for i in range(cfg["num_hidden_layers"])]
    return tree


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
    """An operand of a matrix product, rounded to `precision`, straight
    through for the backward pass."""
    if precision == "float32":
        return x
    return x + jax.lax.stop_gradient(_round_to(x, precision) - x)


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _q(a, precision), _q(b, precision),
                      precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _conv_mixer(p, h, seg, *, precision):
    """Three shifted adds, each masked by segment."""
    T = h.shape[1]
    bcu = _mm("bte,ef->btf", h, p["conv.in_proj"], precision)
    gate_b, gate_c, u = jnp.split(bcu, 3, axis=-1)
    v = gate_b * u
    taps = p["conv.conv"]
    K = taps.shape[0]
    c = jnp.zeros_like(v)
    for j in range(K):
        back = K - 1 - j                      # the source is t - back
        src = jnp.pad(v, ((0, 0), (back, 0), (0, 0)))[:, :T]
        src_seg = jnp.pad(seg, ((0, 0), (back, 0)),
                          constant_values=-1)[:, :T]
        c = c + jnp.where((src_seg == seg)[..., None], src, 0.0) * taps[j]
    return _mm("bte,ef->btf", gate_c * c, p["conv.out_proj"], precision)


def _rope_halves(x, pos, theta):
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos[..., None].astype(jnp.float32) * inv          # [B, T, D/2]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention_mixer(p, h, seg, pos, *, n_head, n_kv, eps, theta, precision):
    B, T, E = h.shape
    D = E // n_head
    q = _mm("bte,ef->btf", h, p["self_attn.q_proj"], precision)
    k = _mm("bte,ef->btf", h, p["self_attn.k_proj"], precision)
    v = _mm("bte,ef->btf", h, p["self_attn.v_proj"], precision)
    q = _rms(q.reshape(B, T, n_head, D), p["self_attn.q_layernorm"], eps)
    k = _rms(k.reshape(B, T, n_kv, D), p["self_attn.k_layernorm"], eps)
    v = v.reshape(B, T, n_kv, D)
    q, k = _rope_halves(q, pos, theta), _rope_halves(k, pos, theta)
    group = n_head // n_kv
    t = jnp.arange(T)
    causal = t[:, None] >= t[None, :]

    @jax.checkpoint
    def one(args):
        """One query head of one row: [T, T] scores."""
        q1, k1, v1, seg1 = args
        s = _mm("td,sd->ts", q1, k1, precision) / math.sqrt(D)
        allowed = causal & (seg1[:, None] == seg1[None, :])
        w = jax.nn.softmax(jnp.where(allowed, s, -1e30), axis=-1)
        return _mm("ts,sd->td", w, v1, precision)

    qs = q.transpose(0, 2, 1, 3).reshape(B * n_head, T, D)
    ks = jnp.repeat(k.transpose(0, 2, 1, 3), group, axis=1).reshape(
        B * n_head, T, D)
    vs = jnp.repeat(v.transpose(0, 2, 1, 3), group, axis=1).reshape(
        B * n_head, T, D)
    segs = jnp.repeat(seg, n_head, axis=0)
    a = jax.lax.map(one, (qs, ks, vs, segs))
    a = a.reshape(B, n_head, T, D).transpose(0, 2, 1, 3).reshape(B, T, E)
    return _mm("bte,ef->btf", a, p["self_attn.out_proj"], precision)


def _dense_ffn(p, h, *, precision):
    gate = _mm("bte,ef->btf", h, p["feed_forward.w1"], precision)
    up = _mm("bte,ef->btf", h, p["feed_forward.w3"], precision)
    return _mm("btf,fe->bte", jax.nn.silu(gate) * up, p["feed_forward.w2"],
               precision)


def route(p, flat, *, top_k, norm, scale, norm_eps, precision):
    """[N, E] -> (choice [N, k] int32 over ALL experts, weights [N, k])."""
    s = jax.nn.sigmoid(_mm("ne,eg->ng", flat, p["feed_forward.gate"],
                           precision))
    _, choice = jax.lax.top_k(s + p["feed_forward.expert_bias"], top_k)
    w = jnp.take_along_axis(s, choice, axis=-1)
    if norm:
        w = w / (jnp.sum(w, -1, keepdims=True) + norm_eps)
    return choice, w * scale


def _routed_ffn(p, h, *, held, top_k, norm, scale, norm_eps, precision):
    """A dense masked sum over the experts held: every row goes through
    every held expert, and counts by the weight it was routed there with
    (zero where it was not)."""
    B, T, E = h.shape
    flat = h.reshape(B * T, E)
    choice, w = route(p, flat, top_k=top_k, norm=norm, scale=scale,
                         norm_eps=norm_eps, precision=precision)
    first, count = held
    F = p["feed_forward.experts_down"].shape[1]

    @jax.checkpoint
    def one(args):
        e, w_in, w_down = args
        gate_e = jnp.sum(jnp.where(choice == first + e, w, 0.0), axis=-1)
        up = _mm("ne,ef->nf", flat, w_in, precision)
        act = jax.nn.silu(up[:, :F]) * up[:, F:]
        return gate_e[:, None] * _mm("nf,fe->ne", act, w_down, precision)

    parts = jax.lax.map(one, (jnp.arange(count),
                              p["feed_forward.experts_in"],
                              p["feed_forward.experts_down"]))
    return jnp.sum(parts, axis=0).reshape(B, T, E)


def layer(p, x, seg, pos, *, mixer: str, routed: bool, cfg_key: tuple,
          precision: str):
    """One layer. x [B, T, E] float32; seg, pos [B, T]."""
    c = dict(cfg_key)
    eps = c["norm_eps"]
    h = _rms(x, p["operator_norm"], eps)
    if mixer == "conv":
        x = x + _conv_mixer(p, h, seg, precision=precision)
    else:
        x = x + _attention_mixer(
            p, h, seg, pos, n_head=c["num_attention_heads"],
            n_kv=c["num_key_value_heads"], eps=eps, theta=c["rope_theta"],
            precision=precision)
    h = _rms(x, p["ffn_norm"], eps)
    if not routed:
        return x + _dense_ffn(p, h, precision=precision)
    return x + _routed_ffn(
        p, h, held=c["experts_held"], top_k=c["num_experts_per_tok"],
        norm=c["norm_topk_prob"], scale=c["routed_scaling_factor"],
        norm_eps=c["route_norm_eps"], precision=precision)


def _head(wte, norm_g, x, eps, precision):
    return _mm("bte,ve->btv", _rms(x, norm_g, eps), wte, precision)


def _masked_loss(logits, ids, loss_mask):
    """Shifted next-token cross-entropy, weighted by `loss_mask` at the
    label's position as the program's loss states it."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    m = loss_mask[:, 1:].astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)


_SCALARS = ("norm_eps", "rope_theta", "num_attention_heads",
            "num_key_value_heads", "experts_held", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor", "route_norm_eps")


class Reference:
    """The jitted pieces for one configuration and one precision: one
    program for each kind of layer (mixer x FFN), forward and backward."""

    def __init__(self, cfg: dict, precision: str = "float32"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}")
        self.cfg = cfg
        self.precision = precision
        key = tuple((k, tuple(cfg[k]) if isinstance(cfg[k], (list, tuple))
                     else cfg[k]) for k in _SCALARS)
        eps = cfg["norm_eps"]
        self._fwd, self._bwd = {}, {}
        for i, mixer in enumerate(cfg["layer_types"]):
            kind = (mixer, _routed(cfg, i))
            if kind in self._fwd:
                continue
            fn = functools.partial(layer, mixer=mixer, routed=kind[1],
                                   cfg_key=key, precision=precision)

            def bwd(p, x, seg, pos, dy, fn=fn):
                _, vjp = jax.vjp(lambda p_, x_: fn(p_, x_, seg, pos), p, x)
                return vjp(dy)

            self._fwd[kind], self._bwd[kind] = jax.jit(fn), jax.jit(bwd)
        self.head = jax.jit(functools.partial(_head, eps=eps,
                                              precision=precision))

        def head_loss(wte, norm_g, x, ids, loss_mask):
            return _masked_loss(_head(wte, norm_g, x, eps, precision), ids,
                                loss_mask)

        self.head_loss_grad = jax.jit(
            jax.value_and_grad(head_loss, argnums=(0, 1, 2)))
        self.embed_bwd = jax.jit(
            lambda wte, ids, dx: jnp.zeros_like(wte).at[ids].add(dx))

    def _kind(self, i: int) -> tuple:
        return self.cfg["layer_types"][i], _routed(self.cfg, i)

    @staticmethod
    def _rows(batch_or_ids, segment_ids=None, position_ids=None):
        ids = jnp.asarray(batch_or_ids, jnp.int32)
        B, T = ids.shape
        seg = (jnp.zeros((B, T), jnp.int32) if segment_ids is None
               else jnp.asarray(segment_ids, jnp.int32))
        pos = (jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
               if position_ids is None
               else jnp.asarray(position_ids, jnp.int32))
        return ids, seg, pos

    # -- inference ----------------------------------------------------------
    def logits(self, params, ids, *, segment_ids=None, position_ids=None):
        """[B, T, padded_vocab] float32 logits of a full forward pass."""
        ids, seg, pos = self._rows(ids, segment_ids, position_ids)
        x = params["embed_tokens"][ids]
        for i, p in enumerate(params["layers"]):
            x = self._fwd[self._kind(i)](p, x, seg, pos)
        return self.head(params["embed_tokens"], params["embedding_norm"], x)

    # -- training -----------------------------------------------------------
    def loss_and_grads(self, params, batch, on_leaf_grads):
        """Loss of one packed batch and its gradients, layer by layer: the
        forward pass keeps each layer's input, the backward pass recomputes
        one layer at a time and hands every finished gradient to
        `on_leaf_grads(where, grads)` (where = "embedding_norm",
        ("layers", i), "embed_tokens"). `embed_tokens` comes last: it is
        tied, and takes the head's and the lookup's gradient together."""
        ids, seg, pos = self._rows(batch["input_ids"], batch["segment_ids"],
                                   batch["position_ids"])
        mask = jnp.asarray(batch["loss_mask"], jnp.float32)
        xs = [params["embed_tokens"][ids]]
        for i, p in enumerate(params["layers"]):
            xs.append(self._fwd[self._kind(i)](p, xs[-1], seg, pos))
        loss, (dwte, dnorm, dx) = self.head_loss_grad(
            params["embed_tokens"], params["embedding_norm"], xs.pop(), ids,
            mask)
        on_leaf_grads("embedding_norm", dnorm)
        for i in reversed(range(len(params["layers"]))):
            dp, dx = self._bwd[self._kind(i)](params["layers"][i], xs.pop(),
                                              seg, pos, dx)
            on_leaf_grads(("layers", i), dp)
        on_leaf_grads("embed_tokens",
                      dwte + self.embed_bwd(params["embed_tokens"], ids, dx))
        return loss


# ---------------------------------------------------------------------------
# AdamW (Loshchilov & Hutter, decoupled decay on every PARAMETER)
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
    if isinstance(where, str):
        return {where: sub}
    return {f"layers.{where[1]}.{leaf}": x for leaf, x in sub.items()}


def flat_names(tree: dict) -> dict:
    """Reference-layout tree -> {name: leaf}."""
    out = {k: tree[k] for k in ("embed_tokens", "embedding_norm")}
    for i, blk in enumerate(tree["layers"]):
        out.update(_flat_where(("layers", i), blk))
    return out


def _parameters(tree: dict) -> dict:
    """A layer's leaves without its buffers."""
    return {k: v for k, v in tree.items() if k not in BUFFERS}


def train_reference(cfg: dict, seed: int, batches: list, *, lr: float,
                    weight_decay: float, b1: float = 0.9, b2: float = 0.999,
                    eps: float = 1e-8, precision: str = "float32",
                    params: dict | None = None,
                    keep_grads_up_to: int = 0) -> dict:
    """Follow the first `len(batches)` AdamW steps from the seed's weights
    (or from `params`, for the tests).

    Returns the loss of each step, the norm of every PARAMETER's first
    gradient, the norm of every leaf's change over all the steps (a
    buffer's is exactly zero), the latter two as {name: float}; the first
    gradient itself ("grads": {name: numpy array}) of every parameter of at
    most `keep_grads_up_to` elements (a router, the taps, a gain: small
    enough to hold, and each sums over every token of the step); and, given
    `params`, the parameters after the steps."""
    ref = Reference(cfg, precision)
    given = params
    params = (init_weights(cfg, seed) if given is None
              else jax.tree_util.tree_map(jnp.copy, given))
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    m, v = zeros(params), zeros(params)
    losses, grad_norms, kept = [], {}, {}
    hp = dict(lr=lr, b1=b1, b2=b2, eps=eps, wd=weight_decay)

    def first_grads(named: dict):
        grad_norms.update(_norms(named))
        kept.update({k: np.asarray(g) for k, g in named.items()
                     if g.size <= keep_grads_up_to})

    for step, batch in enumerate(batches, start=1):
        t = jnp.float32(step)

        def on_leaf_grads(where, g, step=step, t=t):
            if isinstance(where, str):
                if step == 1:
                    first_grads({where: g})
                params[where], m[where], v[where] = _adamw(
                    params[where], m[where], v[where], g, t, **hp)
                return
            i = where[1]
            g = _parameters(g)
            if step == 1:
                first_grads(_flat_where(where, g))
            new_p, new_m, new_v = _adamw(
                _parameters(params["layers"][i]), _parameters(m["layers"][i]),
                _parameters(v["layers"][i]), g, t, **hp)
            params["layers"][i].update(new_p)
            m["layers"][i].update(new_m)
            v["layers"][i].update(new_v)

        losses.append(float(ref.loss_and_grads(params, batch,
                                               on_leaf_grads)))
    del m, v
    change = flat_names(_diff_norms(
        params, init_weights(cfg, seed) if given is None else given))
    out = {"losses": losses,
           "grad_norms": {k: float(x) for k, x in grad_norms.items()},
           "change_norms": {k: float(x) for k, x in change.items()},
           "grads": kept}
    if given is not None:
        out["params"] = params
    return out
