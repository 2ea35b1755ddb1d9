"""A plain Solar-Open2-family decoder (`model_type: solar_open2`), written
from the layer equations, for the benchmark's `correct` decision. It
imports nothing of the program.

`jax.numpy`, float32, every matrix product at `highest` precision, layer by
layer over the full sequence: no cache, no chunked algorithm, no kernels,
no sorting. No bias anywhere. With `N(x; w) = x rsqrt(mean(x^2) + eps) w`
a block is `x <- x + Mixer(N(x))`, then `x <- x + FFN(N(x))`; after the
last block `N` and the untied head.

  delta-rule mixer (every layer not in `gqa_layers`; Kimi Delta Attention,
     arXiv:2510.26692). `[q~ | k~ | v~] = u W_qkv` (H heads of d each);
     `(q~, k~, v~) <- silu(conv(.))`, the causal depthwise convolution
     written as K shifted adds, no bias; `q = l2norm(q~) d^-0.5`, `k =
     l2norm(k~)` (`x rsqrt(sum(x^2) + 1e-6)`); `g = -exp(A_log_h)
     softplus(W_f2 (W_f1 u) + dt_bias)`, a VECTOR of d log-decays a head;
     `beta = 2 sigmoid(W_b u)` a head; the rule as ONE `lax.scan` over
     time, a head's state S [d, d] from zero:
     `S <- diag(exp(g)) S; r = (v - S^T k) beta; S <- S + k r^T; o = S^T q`;
     `o <- rmsnorm_d(o; w_o) sigmoid(W_g2 (W_g1 u))`; `W_out`.
  attention (layers in `gqa_layers`). Hq query and Hkv K/V heads of D, NO
     position term; scores `q . k D^-0.5`, causal softmax, a block of
     queries at a time, query head h reading K/V head `h // (Hq / Hkv)`;
     the heads' concatenated values times `sigmoid(u W_g)`; `W_o`.
  FFN, every layer. `s = sigmoid(u W_r)` over ALL n_routed experts; the
     choice is the k largest of `s + b`; `w = s[choice] / (sum + 1e-20) *
     scale`; the routed part is a dense masked sum over the experts HELD
     (`experts_held = (first, count)`: the chip's share of an
     expert-parallel deployment; a chosen expert that is not held adds
     nothing, here as in the program), each `W_down (silu(u W_gate) (u
     W_up))` of 1280; plus the one shared expert, ungated.

So that a 40k-token session fits the chip, a layer runs a BLOCK of rows at
a time (`ROW_BLOCK`): the recurrence carries its state and the
convolution its last K - 1 input rows from block to block, which is the
recurrence itself and no chunked form of it; the attention's keys and
values are made for the whole sequence first and each block of queries
reads them all; the FFN is row-wise.

Departures from the source repository's modeling file, which this
reference has not seen: every reading below is an inference from the key's
name and the family's lineage (the `kda_*` keys are Kimi Delta Attention's,
the expert keys DeepSeek-V3's), stands under the configuration's `assumed`,
and is taken by the program too. (1) Both gates are sigmoid and
elementwise (not taken: swish, a gate a head). (2) `kda_use_full_proj:
false` sends the decay's and the output gate's projections through a rank
of `kda_low_rank` = the head width (Kimi Linear's). (3) The output norm's
eps is `rms_norm_eps`; l2norm's is 1e-6. (4) The router scores by sigmoid
with a selection bias (the config has no `scoring_func`; the lineage's
`noaux_tc`; not taken: softmax), and the bias is balanced, not drawn
(below). (5) `W_qkv`'s columns are laid `q | k | v` whole and the three
convolutions are one over the three streams side by side. (6)
`intermediate_size` is read by no layer (`first_k_dense_replace` 0).

Weights are made leaf by leaf from the seed and the leaf's NAME, rounded to
bfloat16 and held in float32: the configuration's parameters ARE bfloat16.
Matrices N(0, 0.02), norms' gains 1 + N(0, 0.02), and the mixer's own
initialisers, the lineage's: the convolution U(-1/sqrt(K), 1/sqrt(K)),
`A_log = log U(1, 16)`, `dt_bias` the inverse softplus of a step drawn
log-uniformly from [0.001, 0.1], a channel.

The selection bias `b` is NOT drawn: it is BALANCED, as the buffer is in a
trained release of the lineage (`noaux_tc`: aux-loss-free load balancing
moves `b_e` against expert e's excess load until every expert gets its
share); benchmarks/reference/gigachat3_5.py says why a seed would else
decide how much work a step is. `b` of each layer is the rest point of
that rule on a calibration batch drawn from the seed, run through this
reference layer by layer, float32; the program is handed the same numbers.

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
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "fp8")
_HIGHEST = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0
MATRIX_STD = 0.02
GAIN_STD = 0.02
ROW_BLOCK = 2048         # rows a layer runs at a time
SCORE_BYTES = 1 << 30    # the attention's [Hq, block, T] float32 scores
# the selection bias is balanced on this many sequences of this length
BALANCE_BATCH = (16, 512)
BALANCE_STEPS = 600
BALANCE_RATE = 0.02


def model_cfg(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file. The
    file's `n_routed_experts` counts the experts HELD; the router's width
    is the published count beside it."""
    keys = ("vocab_size", "hidden_size", "num_hidden_layers", "gqa_layers",
            "moe_intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rms_norm_eps",
            "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor")
    cfg = {k: config[k] for k in keys}
    cfg["gqa_layers"] = tuple(cfg["gqa_layers"])
    linear = dict(config["linear_attn_config"])
    cfg["linear_heads"] = linear["num_heads"]
    cfg["linear_head_dim"] = linear["head_dim"]
    cfg["conv_kernel"] = linear["short_conv_kernel_size"]
    cfg["n_routed_experts"] = config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])
    cfg["experts_held"] = tuple(config.get(
        "experts_held", (0, config["n_routed_experts"])))
    assumed = config.get("assumed", {})
    cfg["padded_vocab"] = assumed.get("padded_vocab", config["vocab_size"])
    cfg["kda_low_rank"] = assumed.get("kda_low_rank", linear["head_dim"])
    # toy widths draw wider: std * sqrt(fan-in) is what a layer's output
    # scales with, and 0.02 * sqrt(4096) = 1.28 is what the published
    # widths give the gates and the decay's argument
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
    out = [("mixer_norm", (E,), "gain"), ("ffn_norm", (E,), "gain")]
    if i in cfg["gqa_layers"]:
        Hq, Hkv, D = (cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
        out += [("q_proj", (E, Hq * D), "matrix"),
                ("k_proj", (E, Hkv * D), "matrix"),
                ("v_proj", (E, Hkv * D), "matrix"),
                ("g_proj", (E, Hq * D), "matrix"),
                ("o_proj", (Hq * D, E), "matrix")]
    else:
        H, d, K, r = (cfg["linear_heads"], cfg["linear_head_dim"],
                      cfg["conv_kernel"], cfg["kda_low_rank"])
        out += [("in_proj_qkv", (E, 3 * H * d), "matrix"),
                ("conv1d_weight", (K, 3 * H * d), "conv"),
                ("f_a_proj", (E, r), "matrix"),
                ("f_b_proj", (r, H * d), "matrix"),
                ("A_log", (H,), "a_log"), ("dt_bias", (H * d,), "dt_bias"),
                ("b_proj", (E, H), "matrix"),
                ("g_a_proj", (E, r), "matrix"),
                ("g_b_proj", (r, H * d), "matrix"),
                ("o_norm", (d,), "gain"),
                ("out_proj", (H * d, E), "matrix")]
    F, G, held = (cfg["moe_intermediate_size"], cfg["n_routed_experts"],
                  cfg["experts_held"][1])
    return out + [("router", (E, G), "matrix"),
                  ("e_score_correction_bias", (G,), "zero"),
                  ("experts_gate_up", (held, E, 2 * F), "matrix"),
                  ("experts_down", (held, F, E), "matrix"),
                  ("shared_gate_proj", (E, F), "matrix"),
                  ("shared_up_proj", (E, F), "matrix"),
                  ("shared_down_proj", (F, E), "matrix")]


def top_specs(cfg: dict) -> list[tuple[str, tuple, str]]:
    V, E = cfg["padded_vocab"], cfg["hidden_size"]
    return [("embed_tokens", (V, E), "matrix"), ("norm", (E,), "gain"),
            ("lm_head", (V, E), "matrix")]


def _leaf(key, name: str, shape, kind: str, dtype, matrix_std: float):
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if kind == "matrix":
        x = matrix_std * jax.random.normal(key, shape, jnp.float32)
    elif kind == "gain":
        x = 1.0 + GAIN_STD * jax.random.normal(key, shape, jnp.float32)
    elif kind == "zero":
        x = jnp.zeros(shape, jnp.float32)
    elif kind == "conv":
        bound = 1.0 / math.sqrt(shape[0])
        x = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    elif kind == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
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


def _seeded_layer(cfg: dict, seed: int, i: int, dtype) -> dict:
    return _maker(tuple(layer_specs(cfg, i)), jnp.dtype(dtype),
                  cfg.get("matrix_std", MATRIX_STD))(
        seed_key(seed), jnp.int32(i))


def layer_weights(cfg: dict, seed: int, i: int, dtype=jnp.float32) -> dict:
    """Layer i's leaves, in ONE jitted call. `dtype=bfloat16` gives the
    same values without the float32 copy (they are bfloat16 numbers). The
    selection bias is not drawn: it is BALANCED
    (:func:`_balanced_biases`), float32 whatever `dtype`."""
    w = _seeded_layer(cfg, seed, i, dtype)
    w["e_score_correction_bias"] = _balanced_biases(
        _cfg_key(cfg), int(seed))[i]
    return w


def top_weights(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    return _maker(tuple(top_specs(cfg)), jnp.dtype(dtype),
                  cfg.get("matrix_std", MATRIX_STD))(
        seed_key(seed), jnp.int32(_TOP))


def balance_bias(scores, k: int, steps: int = BALANCE_STEPS,
                 rate: float = BALANCE_RATE):
    """The selection bias that spreads `scores` [N, G] evenly: DeepSeek-V3's
    aux-loss-free rule run to rest on one batch. Each step takes the k
    largest of `s + b` a row and moves `b_e` against expert e's excess load
    (in units of the mean load), with a rate that falls to zero."""
    N, G = scores.shape
    mean_load = N * k / G

    def step(b, t):
        _, idx = jax.lax.top_k(scores + b, k)
        load = jnp.zeros((G,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        return b - rate * (1.0 - t / steps) * (load / mean_load - 1.0), None

    b, _ = jax.lax.scan(step, jnp.zeros((G,), jnp.float32),
                        jnp.arange(steps, dtype=jnp.float32))
    return b


def _cfg_key(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()))


_balancing_s = [0.0]


def balancing_seconds() -> float:
    """Wall seconds this process has spent balancing selection biases: the
    reference's own forward, which a driver keeps out of `setup_s` as it
    keeps the reference's scoring out."""
    return _balancing_s[0]


@functools.lru_cache(maxsize=4)
def _balanced_biases(cfg_key: tuple, seed: int) -> dict:
    """{layer: bias [G] float32}: the reference's own forward over a
    calibration batch drawn from the seed, layer by layer, each layer's
    bias balanced on the scores it sees there (the layers before it
    already balanced). One layer's float32 weights at a time."""
    t0 = time.perf_counter()
    cfg = dict(cfg_key)
    rows, length = BALANCE_BATCH
    ids = np.random.default_rng([int(seed), 0xBA1A]).integers(
        0, cfg["vocab_size"], (rows, length))
    ref = Reference(cfg)
    xs = [x[None] for x in top_weights(cfg, seed)["embed_tokens"][
        jnp.asarray(ids, jnp.int32)]]
    balance = jax.jit(functools.partial(
        balance_bias, k=cfg["num_experts_per_tok"]))
    out = {}
    for i in range(cfg["num_hidden_layers"]):
        w = _seeded_layer(cfg, seed, i, jnp.float32)
        xs = [ref.mixer_half(w, x) for x in xs]
        out[i] = w["e_score_correction_bias"] = balance(jnp.concatenate(
            [ref.router_scores(w, x) for x in xs]))
        xs = [ref.ffn_half(w, x)[0] for x in xs]
        # one layer's float32 weights at a time ON THE DEVICE too
        jax.block_until_ready(xs)
        del w
    del xs
    _balancing_s[0] += time.perf_counter() - t0
    return out


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


def norm(x, w, cfg: dict):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + cfg["rms_norm_eps"]) * w


def _swiglu(h, gate, up, down, precision):
    return _mm("...f,fe->...e",
               jax.nn.silu(_mm("...e,ef->...f", h, gate, precision))
               * _mm("...e,ef->...f", h, up, precision), down, precision)


def delta_block(w, u, carry, cfg: dict, precision: str):
    """One block of rows of the delta-rule mixer. u [T, E] (normed);
    carry = (S [H, d, d], the K - 1 rows of `u W_qkv` before the block
    [K - 1, 3 H d]) -> (the mixer's output [T, E], the carry after)."""
    T = u.shape[0]
    H, d, K = cfg["linear_heads"], cfg["linear_head_dim"], cfg["conv_kernel"]
    S, before = carry
    qkv = _mm("te,ef->tf", u, w["in_proj_qkv"], precision)
    # the causal depthwise convolution as K shifted adds: tap k reaches
    # K - 1 - k rows back, into the block before where it must
    rows = jnp.concatenate([before, qkv])
    conv = sum(rows[k:k + T] * w["conv1d_weight"][k] for k in range(K))
    act = jax.nn.silu(conv)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = l2(act[:, :H * d].reshape(T, H, d)) / math.sqrt(d)
    k = l2(act[:, H * d:2 * H * d].reshape(T, H, d))
    v = act[:, 2 * H * d:].reshape(T, H, d)
    f = _mm("tr,rf->tf", _mm("te,er->tr", u, w["f_a_proj"], precision),
            w["f_b_proj"], precision)
    g = (-jnp.exp(w["A_log"])[:, None]
         * jax.nn.softplus(f + w["dt_bias"]).reshape(T, H, d))
    beta = 2.0 * jax.nn.sigmoid(_mm("te,eh->th", u, w["b_proj"], precision))

    def step(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp          # [H, d] x4, [H]
        S = S * jnp.exp(g_t)[:, :, None]       # diag(exp(g)) S
        read = jnp.sum(S * k_t[:, :, None], axis=-2)          # S^T k
        r = (v_t - read) * b_t[:, None]
        S = S + k_t[:, :, None] * r[:, None, :]
        if precision != "float32":       # the control's state: one step down
            S = _round_to(S, "bfloat16")
        return S, jnp.sum(S * q_t[:, :, None], axis=-2)       # S^T q

    S, o = jax.lax.scan(step, S, (q, k, v, g, beta))          # o [T, H, d]
    o = (o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                           + cfg["rms_norm_eps"]) * w["o_norm"])
    gate = _mm("tr,rf->tf", _mm("te,er->tr", u, w["g_a_proj"], precision),
               w["g_b_proj"], precision)
    o = o * jax.nn.sigmoid(gate).reshape(T, H, d)
    return (_mm("tf,fe->te", o.reshape(T, H * d), w["out_proj"], precision),
            (S, rows[T:]))


def attention_keys(w, u, cfg: dict, precision: str):
    """u [T, E] -> this block's keys and values [T, Hkv, D]."""
    Hkv, D = cfg["num_key_value_heads"], cfg["head_dim"]
    return (_mm("te,ef->tf", u, w["k_proj"], precision).reshape(-1, Hkv, D),
            _mm("te,ef->tf", u, w["v_proj"], precision).reshape(-1, Hkv, D))


def attention_block(w, u, k, v, lo, cfg: dict, precision: str):
    """One block of queries against the whole sequence's keys and values.
    u [Tq, E] (normed) at rows lo .. lo + Tq; k, v [T, Hkv, D]."""
    Hq, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    Tq, T = u.shape[0], k.shape[0]
    q = _mm("te,ef->tf", u, w["q_proj"], precision).reshape(
        Tq, Hkv, Hq // Hkv, D)
    s = _mm("thgd,shd->hgts", q, k, precision) * D ** -0.5
    seen = (lo + jnp.arange(Tq))[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
    a = _mm("hgts,shd->thgd", p, v, precision).reshape(Tq, Hq * D)
    a = a * jax.nn.sigmoid(_mm("te,ef->tf", u, w["g_proj"], precision))
    return _mm("tf,fe->te", a, w["o_proj"], precision)


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
    """The expert layer's output [N, E] and the routing margin [N]. `held`
    overrides the configuration's share (the share test: the stacks in
    `w` are then that share's)."""
    F = cfg["moe_intermediate_size"]
    first, count = cfg["experts_held"] if held is None else held
    dense, margin = route(w, h, cfg, precision)

    def one_expert(e, acc):
        gate_up = w["experts_gate_up"][e]
        y = _swiglu(h, gate_up[:, :F], gate_up[:, F:], w["experts_down"][e],
                    precision)
        return acc + jax.lax.dynamic_index_in_dim(
            dense, first + e, axis=1, keepdims=True) * y

    routed = jax.lax.fori_loop(0, count, one_expert, jnp.zeros_like(h))
    shared = _swiglu(h, w["shared_gate_proj"], w["shared_up_proj"],
                     w["shared_down_proj"], precision)
    return routed + shared, margin


def head(top, x, cfg: dict, precision: str):
    return _mm("te,ve->tv", norm(x, top["norm"], cfg), top["lm_head"],
               precision)


def _blocks(T: int, size: int):
    return [(lo, min(lo + size, T)) for lo in range(0, T, size)]


class Reference:
    """The jitted pieces for one configuration and one precision. A
    sequence is `x` [1, T, E]; a layer runs it a block of rows at a time
    (the module's docstring)."""

    def __init__(self, cfg: dict, precision: str = "float32"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}")
        cfg = dict(cfg, experts_held=tuple(cfg["experts_held"]),
                   gqa_layers=tuple(cfg["gqa_layers"]))
        self.cfg, self.precision = cfg, precision
        kw = dict(cfg=cfg, precision=precision)
        self._norm = jax.jit(functools.partial(norm, cfg=cfg))
        self._delta = jax.jit(functools.partial(delta_block, **kw))
        self._keys = jax.jit(functools.partial(attention_keys, **kw))
        self._attend = jax.jit(functools.partial(attention_block, **kw))
        self._experts = jax.jit(functools.partial(experts, **kw))
        self._scores = jax.jit(lambda w, h: jax.nn.sigmoid(
            _mm("ne,eg->ng", h, w["router"], "float32")))
        self.head = jax.jit(functools.partial(head, **kw))

    def embed(self, top, ids):
        return top["embed_tokens"][jnp.asarray(ids, jnp.int32)]

    def mixer_half(self, w, x):
        """x [1, T, E] -> x + Mixer(N(x))."""
        cfg = self.cfg
        T = x.shape[1]
        u = self._norm(x[0], w["mixer_norm"])
        if "q_proj" in w:
            kv = [self._keys(w, u[lo:hi]) for lo, hi in _blocks(T, ROW_BLOCK)]
            k, v = (jnp.concatenate(half) for half in zip(*kv))
            size = max(16, min(ROW_BLOCK, SCORE_BYTES // (
                4 * cfg["num_attention_heads"] * T) // 16 * 16))
            y = [self._attend(w, u[lo:hi], k, v, lo)
                 for lo, hi in _blocks(T, size)]
        else:
            H, d, K = (cfg["linear_heads"], cfg["linear_head_dim"],
                       cfg["conv_kernel"])
            carry = (jnp.zeros((H, d, d), jnp.float32),
                     jnp.zeros((K - 1, 3 * H * d), jnp.float32))
            y = []
            for lo, hi in _blocks(T, ROW_BLOCK):
                out, carry = self._delta(w, u[lo:hi], carry)
                y.append(out)
        return x + jnp.concatenate(y)[None]

    def router_scores(self, w, x):
        """What the layer's router sees for x [1, T, E] (after the mixer's
        half): sigmoid scores [T, n_routed], float32."""
        return self._scores(w, self._norm(x[0], w["ffn_norm"]))

    def ffn_half(self, w, x):
        """x [1, T, E] -> (x + FFN(N(x)), the routing margin [1, T])."""
        u = self._norm(x[0], w["ffn_norm"])
        got = [self._experts(w, u[lo:hi])
               for lo, hi in _blocks(x.shape[1], ROW_BLOCK)]
        y, margin = (jnp.concatenate(part) for part in zip(*got))
        return x + y[None], margin[None]

    def layer(self, w, x):
        return self.ffn_half(w, self.mixer_half(w, x))

    def logits(self, weights, ids, with_margin: bool = False):
        """Whole-model mode (small sizes): [B, T, padded_vocab] float32
        logits of a full forward pass (and the smallest routing margin
        over the layers, [B, T])."""
        outs, margins = [], []
        for row in np.asarray(ids):
            x = self.embed(weights, row[None])
            margin = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
            for w in weights["layers"]:
                x, m = self.layer(w, x)
                margin = jnp.minimum(margin, m)
            outs.append(self.head(weights, x[0]))
            margins.append(margin[0])
        out = jnp.stack(outs)
        return (out, jnp.stack(margins)) if with_margin else out

    def hidden_layerwise(self, seed: int, seqs: list):
        """Layer-at-a-time mode: each layer's weights are made, used over
        every sequence (one at a time, each at its own length) and freed.
        seqs: lists of ids -> ([final hidden states [T, E]], [margin
        [T]], the top weights)."""
        cfg = self.cfg
        top = top_weights(cfg, seed)
        xs = [self.embed(top, np.asarray(s)[None]) for s in seqs]
        margins = [jnp.full((x.shape[1],), jnp.inf, jnp.float32) for x in xs]
        for i in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, seed, i)
            for b in range(len(xs)):
                xs[b], m = self.layer(w, xs[b])
                margins[b] = jnp.minimum(margins[b], m[0])
            jax.block_until_ready(xs)
            del w
        return [x[0] for x in xs], margins, top


def _gaps_below_best(logits, nxt, vocab: int):
    """How far the logit of `nxt[t]` lies below the best of row t.
    logits [T, V'], nxt [T] -> [T]."""
    rows = logits[:, :vocab]
    return jnp.max(rows, axis=-1) - jnp.take_along_axis(
        rows, nxt[:, None], axis=-1)[:, 0]


def score_sequences(cfg: dict, seed: int, seqs: list, spans: list,
                    precision: str = "float32") -> dict:
    """The reference over the sequences `seqs` (lists of ids, each the
    WHOLE text of a session up to and including a served answer), layer
    at a time. For every position t of `spans[b] = (lo, hi)` (the rows
    whose next token was SERVED): the gap by which the reference logit of
    `seqs[b][t + 1]` lies below the reference's best (`gaps`) and the
    smallest routing margin over the layers at t (`margins`), each one
    flat array over all sequences. The head runs on those rows only. With
    a lower `precision` also `control_gaps`: the same reading for the
    tokens that precision's reference puts first."""
    vocab = cfg["vocab_size"]
    ref = Reference(cfg)
    xs, margins, top = ref.hidden_layerwise(seed, seqs)
    gaps_fn = jax.jit(functools.partial(_gaps_below_best, vocab=vocab))
    low = None
    if precision != "float32":
        low_ref = Reference(cfg, precision)
        low = (low_ref, low_ref.hidden_layerwise(seed, seqs)[0])
    gaps, margin, control = [], [], []
    for b, (lo, hi) in enumerate(spans):
        nxt = jnp.asarray(seqs[b][lo + 1:hi + 1], jnp.int32)
        logits = ref.head(top, xs[b][lo:hi])
        gaps.append(np.asarray(gaps_fn(logits, nxt)))
        margin.append(np.asarray(margins[b][lo:hi]))
        if low is not None:
            first = jnp.argmax(low[0].head(top, low[1][b][lo:hi])[:, :vocab],
                               axis=-1).astype(jnp.int32)
            control.append(np.asarray(gaps_fn(logits, first)))
    out = {"gaps": np.concatenate(gaps), "margins": np.concatenate(margin)}
    if control:
        out["control_gaps"] = np.concatenate(control)
    return out
