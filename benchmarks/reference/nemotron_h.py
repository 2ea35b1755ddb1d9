"""A plain Nemotron-H-family decoder (`model_type: nemotron_h`), written
from the layer equations, for the benchmark's `correct` decision. It
imports nothing of the program.

`jax.numpy`, float32, every matrix product at `highest` precision, layer by
layer over the full sequence: no cache, no chunks, no kernels, no sorting.
A block is `x <- x + mixer(rmsnorm(x))` with ONE mixer, by the block's
letter in `hybrid_override_pattern`; then `norm_f` and the untied head.
No bias anywhere but the convolution's.

  M  Mamba-2. `[z | xBC | dt] = u W_in` (widths d_inner | d_inner + 2 G N |
     H); `xBC <- silu(conv(xBC) + bias)`, the causal depthwise convolution
     written as K shifted adds; split into x [H, P], B [G, N], C [G, N]
     (head h uses group h // (H / G)); `dt <- softplus(dt + dt_bias)`,
     `A = -exp(A_log)`; the recurrence as ONE `lax.scan` over time:
     `h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t`, `y_t = h_t C_t + D x_t`;
     the gated norm, gate first: `rmsnorm_groups(y * silu(z))` over G groups
     with one gain of d_inner; `W_out`.
  *  attention: H_q query heads over H_kv K/V heads, causal softmax scaled
     by head_dim^-0.5, NO rotary and no other position term.
  E  experts in a latent. `s = sigmoid(x W_r)` over ALL n_routed experts;
     the choice is the k largest of `s + b`; `w = s[choice] / (sum + 1e-20)
     * scale`. `x_l = x W_li`; expert e is `relu(x_l U_e)^2 V_e`; the routed
     part is `(sum_e w_e E_e(x_l)) W_lo`, the sum taken as a dense masked sum
     over the experts HELD (`experts_held = (first, count)`: the chip's share
     of an expert-parallel deployment; a chosen expert that is not held
     adds nothing, here as in the program); plus the shared expert on x
     itself, `relu(x U_s)^2 V_s`.

Departures from `modeling_nemotron_h.py` of the source repository, each
also under the configuration's `assumed`: `rope_theta` and
`partial_rotary_factor` stand in the published config and are not read
(the family's attention has no position term); the multi-token-prediction
module is a drafter's and is not built; `time_step_floor` clamps `dt` at
initialisation there and nowhere in the forward pass, so it is not read.

Weights are made leaf by leaf from the seed and the leaf's NAME, rounded to
bfloat16 and held in float32: the configuration's parameters ARE bfloat16.
Matrices N(0, 0.02), gains 1 + N(0, 0.02), the selection bias and the
convolution's bias N(0, 0.02), and the state-space layer's own
initialisers: the convolution U(-1/sqrt(K), 1/sqrt(K)), `A_log = log U(1,
16)`, `dt_bias` the inverse softplus of a step drawn log-uniformly from
[0.001, 0.1], `D = 1`, so that the seeded state neither dies nor blows up
over a long request. The scoring path (:func:`score_sequences`) makes,
uses and frees one layer at a time over all sequences.

`precision` puts the same mathematics through a lower precision for the
control: "bfloat16" rounds both operands of every matrix product (the
router's too) to bfloat16, "fp8" to float8_e4m3 with one scale per tensor;
both carry the recurrent state in bfloat16 (the step below the float32 the
configuration states for it), rounded after every position. Sums stay
float32.
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
BIAS_STD = 0.02


def model_cfg(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file. The
    file's `n_routed_experts` counts the experts HELD; the router's width
    is the published count beside it."""
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "hybrid_override_pattern", "num_attention_heads",
            "num_key_value_heads", "head_dim", "mamba_num_heads",
            "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
            "moe_intermediate_size", "moe_latent_size",
            "moe_shared_expert_intermediate_size", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor", "norm_eps")
    cfg = {k: config[k] for k in keys}
    cfg["n_routed_experts"] = config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])
    cfg["experts_held"] = tuple(config.get(
        "experts_held", (0, config["n_routed_experts"])))
    assumed = config.get("assumed", {})
    cfg["padded_vocab"] = assumed.get("padded_vocab", config["vocab_size"])
    # toy widths draw wider: std * sqrt(fan-in) is what a layer's output
    # scales with, and 0.02 * sqrt(4096) = 1.28 is what makes the state a
    # large part of a Mamba-2 layer's output at the published widths
    cfg["matrix_std"] = assumed.get("matrix_std", MATRIX_STD)
    return cfg


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
    """(leaf, shape, kind) of layer i. Matrices are stored [in, out]."""
    E = cfg["hidden_size"]
    kind = cfg["hybrid_override_pattern"][i]
    out = [("norm", (E,), "gain")]
    if kind == "M":
        H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
        G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
        d_inner, conv_dim = H * P, H * P + 2 * G * N
        return out + [("in_proj", (E, d_inner + conv_dim + H), "matrix"),
                      ("conv1d_weight", (K, conv_dim), "conv"),
                      ("conv1d_bias", (conv_dim,), "bias"),
                      ("A_log", (H,), "a_log"), ("D", (H,), "one"),
                      ("dt_bias", (H,), "dt_bias"),
                      ("mixer_norm", (d_inner,), "gain"),
                      ("out_proj", (d_inner, E), "matrix")]
    if kind == "*":
        Hq, Hkv, Dh = (cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"])
        return out + [("q_proj", (E, Hq * Dh), "matrix"),
                      ("k_proj", (E, Hkv * Dh), "matrix"),
                      ("v_proj", (E, Hkv * Dh), "matrix"),
                      ("o_proj", (Hq * Dh, E), "matrix")]
    L, F = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    S, held = (cfg["moe_shared_expert_intermediate_size"],
               cfg["experts_held"][1])
    return out + [("router", (E, cfg["n_routed_experts"]), "matrix"),
                  ("e_score_correction_bias", (cfg["n_routed_experts"],),
                   "bias"),
                  ("latent_in", (E, L), "matrix"),
                  ("experts_up", (held, L, F), "matrix"),
                  ("experts_down", (held, F, L), "matrix"),
                  ("latent_out", (L, E), "matrix"),
                  ("shared_up_proj", (E, S), "matrix"),
                  ("shared_down_proj", (S, E), "matrix")]


def top_specs(cfg: dict) -> list[tuple[str, tuple, str]]:
    V, E = cfg["padded_vocab"], cfg["hidden_size"]
    return [("embed_tokens", (V, E), "matrix"), ("norm_f", (E,), "gain"),
            ("lm_head", (V, E), "matrix")]


def _leaf(key, name: str, shape, kind: str, dtype, matrix_std: float):
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if kind in ("matrix", "gain", "bias"):
        x = jax.random.normal(key, shape, jnp.float32)
        x = {"matrix": matrix_std * x, "gain": 1.0 + MATRIX_STD * x,
             "bias": BIAS_STD * x}[kind]
    elif kind == "conv":
        bound = 1.0 / math.sqrt(shape[0])
        x = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    elif kind == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif kind == "one":
        x = jnp.ones(shape, jnp.float32)
    else:
        raise ValueError(kind)
    return x.astype(jnp.bfloat16).astype(dtype)


@functools.lru_cache(maxsize=None)
def _maker(specs: tuple, dtype, matrix_std: float):
    """One program for every layer of one kind: the layer's number is
    folded into the key as data, the leaf's name as a constant."""
    return jax.jit(lambda key, i: {
        name: _leaf(jax.random.fold_in(key, i), name, shape, kind, dtype,
                    matrix_std)
        for name, shape, kind in specs})


_TOP = 0x7FFFFFFF       # the "layer number" of the leaves outside the layers


def layer_weights(cfg: dict, seed: int, i: int, dtype=jnp.float32) -> dict:
    """Layer i's leaves, in ONE jitted call. `dtype=bfloat16` gives the
    same values without the float32 copy (they are bfloat16 numbers)."""
    return _maker(tuple(layer_specs(cfg, i)), jnp.dtype(dtype),
                  cfg.get("matrix_std", MATRIX_STD))(
        seed_key(seed), jnp.int32(i))


def top_weights(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    return _maker(tuple(top_specs(cfg)), jnp.dtype(dtype),
                  cfg.get("matrix_std", MATRIX_STD))(
        seed_key(seed), jnp.int32(_TOP))


def init_weights(cfg: dict, seed: int) -> dict:
    """The whole model (small sizes only)."""
    return dict(top_weights(cfg, seed), layers=[
        layer_weights(cfg, seed, i)
        for i in range(cfg["num_hidden_layers"])])


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
    return x if precision == "float32" else _round_to(x, precision)


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _q(a, precision), _q(b, precision),
                      precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _relu2(h, up, down, precision):
    u = _mm("...e,ef->...f", h, up, precision)
    return _mm("...f,fe->...e", jnp.square(jax.nn.relu(u)), down, precision)


def mamba(w, u, cfg: dict, precision: str):
    """u [B, T, E] (normed) -> the mixer's output [B, T, E]."""
    B, T, _ = u.shape
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    d_inner = H * P
    conv_dim = d_inner + 2 * G * N
    zxbcdt = _mm("bte,ef->btf", u, w["in_proj"], precision)
    z, xbc, dt = (zxbcdt[..., :d_inner],
                  zxbcdt[..., d_inner:d_inner + conv_dim],
                  zxbcdt[..., d_inner + conv_dim:])
    # the causal depthwise convolution as K shifted adds: tap k reaches
    # K - 1 - k rows back
    conv = jnp.broadcast_to(w["conv1d_bias"], xbc.shape)
    for k in range(K):
        back = K - 1 - k
        shifted = jnp.pad(xbc, ((0, 0), (back, 0), (0, 0)))[:, :T]
        conv = conv + shifted * w["conv1d_weight"][k]
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d_inner].reshape(B, T, H, P)
    b = jnp.repeat(xbc[..., d_inner:d_inner + G * N].reshape(B, T, G, N),
                   H // G, axis=2)                           # [B, T, H, N]
    c = jnp.repeat(xbc[..., d_inner + G * N:].reshape(B, T, G, N),
                   H // G, axis=2)
    dt = jax.nn.softplus(dt + w["dt_bias"])                  # [B, T, H]
    a = -jnp.exp(w["A_log"])

    def step(h, inp):
        x_t, b_t, c_t, dt_t = inp
        h = (h * jnp.exp(dt_t * a)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        if precision != "float32":       # the control's state: one step down
            h = _round_to(h, "bfloat16")
        return h, jnp.sum(h * c_t[:, :, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), jnp.float32), tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, b, c, dt)))
    y = jnp.moveaxis(y, 0, 1) + w["D"][:, None] * x          # [B, T, H, P]
    gated = (y.reshape(B, T, G, d_inner // G)
             * jax.nn.silu(z).reshape(B, T, G, d_inner // G))
    gated = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + cfg["norm_eps"])
    return _mm("btf,fe->bte", gated.reshape(B, T, d_inner) * w["mixer_norm"],
               w["out_proj"], precision)


def attention(w, h, cfg: dict, precision: str):
    B, T, _ = h.shape
    Hq, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    q = _mm("bte,ef->btf", h, w["q_proj"], precision).reshape(B, T, Hq, Dh)
    k = _mm("bte,ef->btf", h, w["k_proj"], precision).reshape(B, T, Hkv, Dh)
    v = _mm("bte,ef->btf", h, w["v_proj"], precision).reshape(B, T, Hkv, Dh)
    k, v = (jnp.repeat(x, Hq // Hkv, axis=2) for x in (k, v))
    s = _mm("bthd,bshd->bhts", q, k, precision) / math.sqrt(Dh)
    t = jnp.arange(T)
    p = jax.nn.softmax(
        jnp.where((t[:, None] >= t[None, :])[None, None], s, -1e30), axis=-1)
    a = _mm("bhts,bshd->bthd", p, v, precision)
    return _mm("btf,fe->bte", a.reshape(B, T, Hq * Dh), w["o_proj"],
               precision)


def route(w, h, cfg: dict, precision: str):
    """h [N, E] -> (dense weights [N, n_routed], zero where not chosen;
    margin [N] between the k-th and the next of s + b)."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm("ne,eg->ng", h, w["router"], precision))
    vals, idx = jax.lax.top_k(s + w["e_score_correction_bias"], k + 1)
    choice = idx[:, :k]
    picked = jnp.take_along_axis(s, choice, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    picked = picked * cfg["routed_scaling_factor"]
    rows = jnp.arange(h.shape[0])[:, None]
    dense = jnp.zeros_like(s).at[rows, choice].set(picked)
    return dense, vals[:, k - 1] - vals[:, k]


def experts(w, h, cfg: dict, precision: str, held=None):
    """The expert layer's output [B, T, E] and the routing margin [B, T].
    `held` overrides the configuration's share (the share test)."""
    B, T, E = h.shape
    first, count = cfg["experts_held"] if held is None else held
    flat = h.reshape(B * T, E)
    dense, margin = route(w, flat, cfg, precision)
    x_l = _mm("ne,el->nl", flat, w["latent_in"], precision)

    def one_expert(e, acc):
        u = _mm("nl,lf->nf", x_l, w["experts_up"][e], precision)
        y = _mm("nf,fl->nl", jnp.square(jax.nn.relu(u)),
                w["experts_down"][e], precision)
        return acc + jax.lax.dynamic_index_in_dim(
            dense, first + e, axis=1, keepdims=True) * y

    routed = jax.lax.fori_loop(0, count, one_expert, jnp.zeros_like(x_l))
    routed = _mm("nl,le->ne", routed, w["latent_out"], precision)
    shared = _relu2(h, w["shared_up_proj"], w["shared_down_proj"], precision)
    return routed.reshape(B, T, E) + shared, margin.reshape(B, T)


def layer(w, x, cfg: dict, precision: str):
    """One block. Returns (x, margin [B, T]); a layer with no router has
    margin +inf."""
    h = _rms(x, w["norm"], cfg["norm_eps"])
    no_margin = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
    if "in_proj" in w:
        return x + mamba(w, h, cfg, precision), no_margin
    if "q_proj" in w:
        return x + attention(w, h, cfg, precision), no_margin
    y, margin = experts(w, h, cfg, precision)
    return x + y, margin


def head(top, x, cfg: dict, precision: str):
    return _mm("bte,ve->btv", _rms(x, top["norm_f"], cfg["norm_eps"]),
               top["lm_head"], precision)


class Reference:
    """The jitted pieces for one configuration and one precision."""

    def __init__(self, cfg: dict, precision: str = "float32"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}")
        cfg = dict(cfg, experts_held=tuple(cfg["experts_held"]))
        self.cfg = cfg
        self.precision = precision
        self.layer = jax.jit(functools.partial(
            layer, cfg=cfg, precision=precision))
        self.head = jax.jit(functools.partial(
            head, cfg=cfg, precision=precision))

    def embed(self, top, ids):
        return top["embed_tokens"][jnp.asarray(ids, jnp.int32)]

    def logits(self, weights, ids, with_margin: bool = False):
        """Whole-model mode: [B, T, padded_vocab] float32 logits of a full
        forward pass (and the smallest routing margin over the layers,
        [B, T])."""
        x = self.embed(weights, ids)
        margin = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
        for w in weights["layers"]:
            x, m = self.layer(w, x)
            margin = jnp.minimum(margin, m)
        out = self.head(weights, x)
        return (out, margin) if with_margin else out

    def hidden_layerwise(self, seed: int, ids):
        """Layer-at-a-time mode: each layer's weights are made, used over
        every sequence (one at a time: the attention's scores are
        [H, T, T]) and freed. ids [B, T] -> (final hidden states
        [B, T, E], margin [B, T], the top weights)."""
        cfg = self.cfg
        top = top_weights(cfg, seed)
        x = self.embed(top, ids)
        xs = [x[b:b + 1] for b in range(x.shape[0])]
        margins = [jnp.full((1, x.shape[1]), jnp.inf, jnp.float32)] * len(xs)
        for i in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, seed, i)
            for b in range(len(xs)):
                xs[b], m = self.layer(w, xs[b])
                margins[b] = jnp.minimum(margins[b], m)
            del w
        return jnp.concatenate(xs), jnp.concatenate(margins), top


def _gaps_below_best(logits, tokens, vocab: int):
    """For every position t of one sequence: how far the logit of
    `tokens[t + 1]` lies below the best at t. logits [1, T, V'],
    tokens [T] -> [T - 1]."""
    rows = logits[0, :-1, :vocab]
    return jnp.max(rows, axis=-1) - jnp.take_along_axis(
        rows, tokens[1:, None], axis=-1)[:, 0]


def score_sequences(cfg: dict, seed: int, ids, precision: str = "float32"
                    ) -> dict:
    """The reference over padded sequences `ids` [B, T], layer at a time.
    For every position t < T - 1 of every sequence: the gap by which the
    reference logit of `ids[b, t + 1]` lies below the reference's best
    (`gaps` [B, T - 1]) and the smallest routing margin over the expert
    layers at t (`margins` [B, T - 1]). With a lower `precision` also
    `control_gaps`: the same reading for the tokens that precision's
    reference puts first."""
    vocab = cfg["vocab_size"]
    ids = jnp.asarray(ids, jnp.int32)
    ref = Reference(cfg)
    x, margin, top = ref.hidden_layerwise(seed, ids)
    gaps_fn = jax.jit(functools.partial(_gaps_below_best, vocab=vocab))
    first = jax.jit(lambda lg: jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.argmax(lg[0, :-1, :vocab], axis=-1).astype(jnp.int32)]))
    low = None
    if precision != "float32":
        low_ref = Reference(cfg, precision)
        low = (low_ref, low_ref.hidden_layerwise(seed, ids)[0])
    gaps, control = [], []
    for b in range(ids.shape[0]):
        logits = ref.head(top, x[b:b + 1])
        gaps.append(np.asarray(gaps_fn(logits, ids[b])))
        if low is not None:
            low_first = first(low[0].head(top, low[1][b:b + 1]))
            control.append(np.asarray(gaps_fn(logits, low_first)))
    out = {"gaps": np.stack(gaps), "margins": np.asarray(margin)[:, :-1]}
    if control:
        out["control_gaps"] = np.stack(control)
    return out
