"""A plain GigaChat-3.5-family decoder (`model_type: gigachat3_5`), written
from the layer equations, for the benchmark's `correct` decision. It
imports nothing of the program.

`jax.numpy`, float32, every matrix product at `highest` precision, layer by
layer over the full sequence: no cache, no chunks, no kernels, no sorting.
No bias anywhere. With `N(x; w) = x rsqrt(mean(x^2) + eps) (2 sigmoid(w))`
a block is `x <- x + N(Mixer(N(x)))`, then `x <- x + N(FFN(N(x)))` (four
norms, each its own `w`); after the last block `N` and the untied head.

  linear mixer (every layer not in `full_attention_layers`). `[q | k | v |
     z] = u W_qkvz` (q, k: Hk heads of dk; v, z: Hv heads of dv), `[b | a] =
     u W_ba`; `(q, k, v) <- silu(conv(concat(q, k, v)))`, the causal
     depthwise convolution written as K shifted adds, no bias; `q <-
     l2norm(q) / sqrt(dk)`, `k <- l2norm(k)` (`x rsqrt(sum(x^2) + 1e-6)`),
     key head h // (Hv / Hk) serving value head h; `beta = sigmoid(b)`, `g =
     -exp(A_log) softplus(a + dt_bias)`; the gated delta rule as ONE
     `lax.scan` over time, a value head's state S [dk, dv] from zero:
     `S <- exp(g) S; r = (v - S^T k) beta; S <- S + k r^T; o = S^T q`;
     `o <- rmsnorm_dv(o) (2 sigmoid(w_o)) (2 sigmoid(z))`; `W_out`.
  latent attention (layers in `full_attention_layers`). `c_q = N(u W_qa)`,
     `q = c_q W_qb` -> a head `[q_n | q_r]`; `[c_kv | k_r] = u W_kva`, `c =
     N(c_kv)`, `[k_n | v] = c W_kvb` a head (EXPANDED: nothing absorbed);
     rotary on the interleaved pairs of `q_r` and `k_r` with YaRN's blended
     frequencies; scores `(q_n . k_n + q_r . k_r) (Dn + Dr)^-0.5 m^2`, `m =
     0.1 mscale_all_dim ln(factor) + 1`; causal softmax, a block of queries
     at a time; the heads' concatenated values times `sigmoid(u W_g)`;
     `W_o`.
  FFN. Dense (the first `first_k_dense_replace` layers): `W_down (silu(min(
     u W_gate, L)) clip(u W_up, -L, L))`, `L = swiglu_limit`. Routed: `s =
     sigmoid(u W_r)` over ALL n_routed experts; the choice is the k largest
     of `s + b`; `w = s[choice] / (sum + 1e-20) * scale`; the routed part
     is a dense masked sum over the experts HELD (`experts_held = (first,
     count)`: the chip's share of an expert-parallel deployment; a chosen
     expert that is not held adds nothing, here as in the program), each
     the clamped SwiGLU of 2048; plus the one shared expert, ungated.

Departures from the source repository's modeling file, which this
reference has not seen: every reading below is an inference from the key's
name and the family's lineage (`GigaChat3` is `deepseek_v3`-typed; the
`linear_*` keys are Qwen3-Next's gated-delta-net keys), stands under the
configuration's `assumed`, and is taken by the program too. (1) The norm's
gain is `layernorm_gating_weight * sigmoid(w)`, 1 at `w = 0` (not taken:
`1 + w`). (2) The linear mixer's output gate is
`linear_sigmoid_gate_scale * sigmoid(z)` where Qwen3-Next has `silu(z)`,
and its norm's gain is the block norm's kind. (3) `gated_attention` is
Qwen3-Next's output gate, elementwise on the heads' concatenated values,
taken from the block's normed input (not taken: a head-wise gate, a gate
from the query's latent). (4) The router scores by sigmoid with a selection
bias (the config has no `scoring_func`; the lineage's `noaux_tc`), and the
bias is balanced, not drawn (below). (5)
`swiglu_limit` clamps the gate from above and the up half on both sides.
(6) `W_qkvz`'s columns are laid `q | k | v | z` whole. (7) The
multi-token-prediction modules are a drafter's and are not built.

Weights are made leaf by leaf from the seed and the leaf's NAME, rounded to
bfloat16 and held in float32: the configuration's parameters ARE bfloat16.
Matrices N(0, 0.02), every norm's `w` N(0, 0.02), and the linear mixer's
own initialisers, Qwen3-Next's: the convolution U(-1/sqrt(K), 1/sqrt(K)),
`A_log = log U(1, 16)`, `dt_bias` the inverse softplus of a step drawn
log-uniformly from [0.001, 0.1]. The scoring path (:func:`score_sequences`)
makes, uses and frees one layer at a time over all sequences.

The selection bias `b` is NOT drawn: it is BALANCED, as the buffer is in a
trained release of the lineage (`noaux_tc`: aux-loss-free load balancing
moves `b_e` against expert e's excess load until every expert gets its
share). With seeded matrices a third of a normed hidden state's energy
lies in ONE direction common to all tokens, the router's logits carry a
per-expert offset of 0.95 beside a per-token spread of 1.4, a quarter of
the experts is never chosen, and the share of the routed rows that falls
to the 16 held here is the seed's lot (5.2% to 9.1% of a window, 2.5% to
15% of a layer: measured, PERF.md section 6, PR 38): a seed would then
decide how much work a step is. So `b` of each routed layer is the rest
point of that rule on a calibration batch drawn from the seed
(`BALANCE_BATCH` sequences of uniform ids, the traffic's kind), run
through this reference layer by layer, float32 and not rounded (the
program holds the buffer in float32); the program is handed the same
numbers.

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
BIAS_STD = 0.02
QUERY_BLOCK = 512        # the attention's scores are [H, block, T]
# the selection bias is balanced on this many sequences of this length
BALANCE_BATCH = (16, 512)
BALANCE_STEPS = 600
BALANCE_RATE = 0.02


def model_cfg(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file. The
    file's `n_routed_experts` counts the experts HELD; the router's width
    is the published count beside it."""
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "first_k_dense_replace", "full_attention_layers",
            "intermediate_size", "moe_intermediate_size",
            "num_attention_heads", "kv_lora_rank", "q_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_theta", "rope_scaling", "rms_norm_eps",
            "layernorm_gating_weight", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_sigmoid_gate_scale", "linear_attn_o_norm_eps",
            "swiglu_limit", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor")
    cfg = {k: config[k] for k in keys}
    cfg["full_attention_layers"] = tuple(cfg["full_attention_layers"])
    cfg["rope_scaling"] = tuple(sorted(dict(cfg["rope_scaling"]).items()))
    cfg["n_routed_experts"] = config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])
    cfg["experts_held"] = tuple(config.get(
        "experts_held", (0, config["n_routed_experts"])))
    assumed = config.get("assumed", {})
    cfg["padded_vocab"] = assumed.get("padded_vocab", config["vocab_size"])
    # toy widths draw wider: std * sqrt(fan-in) is what a layer's output
    # scales with, and 0.02 * sqrt(7168) = 1.69 is what the published
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
    out = [(n, (E,), "bias") for n in ("pre_mixer_norm", "post_mixer_norm",
                                       "pre_ffn_norm", "post_ffn_norm")]
    if i in cfg["full_attention_layers"]:
        H, C, Rq = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                    cfg["q_lora_rank"])
        Dn, Dr, Dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
        out += [("q_a_proj", (E, Rq), "matrix"), ("q_a_norm", (Rq,), "bias"),
                ("q_b_proj", (Rq, H * (Dn + Dr)), "matrix"),
                ("kv_a_proj_with_mqa", (E, C + Dr), "matrix"),
                ("kv_a_norm", (C,), "bias"),
                ("kv_b_proj", (C, H * (Dn + Dv)), "matrix"),
                ("o_gate_proj", (E, H * Dv), "matrix"),
                ("o_proj", (H * Dv, E), "matrix")]
    else:
        Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
        dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
        K, conv_dim = cfg["linear_conv_kernel_dim"], 2 * Hk * dk + Hv * dv
        out += [("in_proj_qkvz", (E, conv_dim + Hv * dv), "matrix"),
                ("in_proj_ba", (E, 2 * Hv), "matrix"),
                ("conv1d_weight", (K, conv_dim), "conv"),
                ("A_log", (Hv,), "a_log"), ("dt_bias", (Hv,), "dt_bias"),
                ("o_norm", (dv,), "bias"),
                ("out_proj", (Hv * dv, E), "matrix")]
    if i < cfg["first_k_dense_replace"]:
        F = cfg["intermediate_size"]
        return out + [("gate_proj", (E, F), "matrix"),
                      ("up_proj", (E, F), "matrix"),
                      ("down_proj", (F, E), "matrix")]
    F, G, held = (cfg["moe_intermediate_size"], cfg["n_routed_experts"],
                  cfg["experts_held"][1])
    return out + [("router", (E, G), "matrix"),
                  ("e_score_correction_bias", (G,), "bias"),
                  ("experts_gate_up", (held, E, 2 * F), "matrix"),
                  ("experts_down", (held, F, E), "matrix"),
                  ("shared_gate_proj", (E, F), "matrix"),
                  ("shared_up_proj", (E, F), "matrix"),
                  ("shared_down_proj", (F, E), "matrix")]


def top_specs(cfg: dict) -> list[tuple[str, tuple, str]]:
    V, E = cfg["padded_vocab"], cfg["hidden_size"]
    return [("embed_tokens", (V, E), "matrix"), ("norm", (E,), "bias"),
            ("lm_head", (V, E), "matrix")]


def _leaf(key, name: str, shape, kind: str, dtype, matrix_std: float):
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if kind in ("matrix", "bias"):
        x = jax.random.normal(key, shape, jnp.float32)
        x = {"matrix": matrix_std, "bias": BIAS_STD}[kind] * x
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
    same values without the float32 copy (they are bfloat16 numbers). A
    routed layer's selection bias is not drawn: it is BALANCED
    (:func:`_balanced_biases`), float32 whatever `dtype`."""
    w = _seeded_layer(cfg, seed, i, dtype)
    if "router" in w:
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
    """{layer: bias [G] float32} for the routed layers: the reference's own
    forward over a calibration batch drawn from the seed, layer by layer,
    each routed layer's bias balanced on the scores it sees there (the
    layers before it already balanced). One layer's float32 weights at a
    time."""
    t0 = time.perf_counter()
    cfg = dict(cfg_key)
    rows, length = BALANCE_BATCH
    ids = np.random.default_rng([int(seed), 0xBA1A]).integers(
        0, cfg["vocab_size"], (rows, length))
    x = top_weights(cfg, seed)["embed_tokens"][jnp.asarray(ids, jnp.int32)]
    run = jax.jit(functools.partial(layer, cfg=cfg, precision="float32"))
    score = jax.jit(functools.partial(_router_scores, cfg=cfg))
    balance = jax.jit(functools.partial(
        balance_bias, k=cfg["num_experts_per_tok"]))
    out = {}
    for i in range(cfg["num_hidden_layers"]):
        w = _seeded_layer(cfg, seed, i, jnp.float32)
        if "router" in w:
            out[i] = balance(score(w, x))
            w["e_score_correction_bias"] = out[i]
        x, _ = run(w, x)
        # one layer's float32 weights at a time ON THE DEVICE too: the next
        # layer's are not made while this one's are still in use
        jax.block_until_ready(x)
        del w
    # nothing of the forward but the biases ([G] float32 a routed layer)
    # stays on the device when the program's weights are made
    del x
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


def _unit(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def norm(x, w, cfg: dict):
    """N(x; w): the gain is a scaled sigmoid of the parameter."""
    return (_unit(x, cfg["rms_norm_eps"])
            * (cfg["layernorm_gating_weight"] * jax.nn.sigmoid(w)))


def _swiglu(h, gate, up, down, limit, precision):
    g = jnp.minimum(_mm("...e,ef->...f", h, gate, precision), limit)
    u = jnp.clip(_mm("...e,ef->...f", h, up, precision), -limit, limit)
    return _mm("...f,fe->...e", jax.nn.silu(g) * u, down, precision)


def delta_mixer(w, u, cfg: dict, precision: str):
    """u [B, T, E] (normed) -> the linear mixer's output [B, T, E]."""
    B, T, _ = u.shape
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    K = cfg["linear_conv_kernel_dim"]
    conv_dim = 2 * Hk * dk + Hv * dv
    qkvz = _mm("bte,ef->btf", u, w["in_proj_qkvz"], precision)
    ba = _mm("bte,ef->btf", u, w["in_proj_ba"], precision)
    qkv, z = qkvz[..., :conv_dim], qkvz[..., conv_dim:]
    # the causal depthwise convolution as K shifted adds: tap k reaches
    # K - 1 - k rows back
    conv = jnp.zeros_like(qkv)
    for k in range(K):
        back = K - 1 - k
        shifted = jnp.pad(qkv, ((0, 0), (back, 0), (0, 0)))[:, :T]
        conv = conv + shifted * w["conv1d_weight"][k]
    qkv = jax.nn.silu(conv)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = l2(qkv[..., :Hk * dk].reshape(B, T, Hk, dk)) / math.sqrt(dk)
    k = l2(qkv[..., Hk * dk:2 * Hk * dk].reshape(B, T, Hk, dk))
    q, k = (jnp.repeat(x, Hv // Hk, axis=2) for x in (q, k))  # [B,T,Hv,dk]
    v = qkv[..., 2 * Hk * dk:].reshape(B, T, Hv, dv)
    beta = jax.nn.sigmoid(ba[..., :Hv])                       # [B, T, Hv]
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[..., Hv:] + w["dt_bias"])

    def step(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        S = S * jnp.exp(g_t)[..., None, None]
        read = jnp.sum(S * k_t[..., :, None], axis=-2)        # S^T k
        r = (v_t - read) * b_t[..., None]
        S = S + k_t[..., :, None] * r[..., None, :]
        if precision != "float32":       # the control's state: one step down
            S = _round_to(S, "bfloat16")
        return S, jnp.sum(S * q_t[..., :, None], axis=-2)     # S^T q

    _, o = jax.lax.scan(step, jnp.zeros((B, Hv, dk, dv), jnp.float32), tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)                                 # [B, T, Hv, dv]
    o = (_unit(o, cfg["linear_attn_o_norm_eps"])
         * (cfg["layernorm_gating_weight"] * jax.nn.sigmoid(w["o_norm"]))
         * (cfg["linear_sigmoid_gate_scale"]
            * jax.nn.sigmoid(z.reshape(B, T, Hv, dv))))
    return _mm("btf,fe->bte", o.reshape(B, T, Hv * dv), w["out_proj"],
               precision)


def _yarn_inv_freq(dim: int, theta: float, scaling: dict):
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]

    def correction(turns):
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction(scaling["beta_slow"])), dim - 1)
    plain = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return jnp.asarray(plain / factor * ramp + plain * (1 - ramp),
                       jnp.float32)


def _rope(x, pos, inv):
    """Rotary on the interleaved pairs (2i, 2i+1) of the last axis.
    x [B, T, H, D], pos [B, T], inv [D/2]."""
    ang = pos[..., None].astype(jnp.float32) * inv            # [B, T, D/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def latent_attention(w, u, cfg: dict, precision: str):
    """u [B, T, E] (normed) -> the attention mixer's output [B, T, E]."""
    B, T, _ = u.shape
    H, C = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    Dn, Dr, Dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    yarn = dict(cfg["rope_scaling"])
    inv = _yarn_inv_freq(Dr, cfg["rope_theta"], yarn)
    m = 0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"]) + 1.0
    scale = (Dn + Dr) ** -0.5 * m * m
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    c_q = norm(_mm("bte,ef->btf", u, w["q_a_proj"], precision),
               w["q_a_norm"], cfg)
    q = _mm("btr,rf->btf", c_q, w["q_b_proj"], precision).reshape(
        B, T, H, Dn + Dr)
    kv_a = _mm("bte,ef->btf", u, w["kv_a_proj_with_mqa"], precision)
    c = norm(kv_a[..., :C], w["kv_a_norm"], cfg)
    q_rope = _rope(q[..., Dn:], pos, inv)
    k_r = _rope(kv_a[..., None, C:], pos, inv)[:, :, 0]       # [B, T, Dr]
    kv = _mm("btc,cf->btf", c, w["kv_b_proj"], precision).reshape(
        B, T, H, Dn + Dv)
    t = jnp.arange(T)
    blocks = []
    for lo in range(0, T, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, T)
        s = (_mm("bthd,bshd->bhts", q[:, lo:hi, :, :Dn], kv[..., :Dn],
                 precision)
             + _mm("bthd,bsd->bhts", q_rope[:, lo:hi], k_r, precision)
             ) * scale
        p = jax.nn.softmax(jnp.where(
            (t[lo:hi, None] >= t[None, :])[None, None], s, -1e30), axis=-1)
        blocks.append(_mm("bhts,bshd->bthd", p, kv[..., Dn:], precision))
    a = jnp.concatenate(blocks, axis=1).reshape(B, T, H * Dv)
    a = a * jax.nn.sigmoid(_mm("bte,ef->btf", u, w["o_gate_proj"],
                               precision))
    return _mm("btf,fe->bte", a, w["o_proj"], precision)


def _router_scores(w, x, cfg: dict):
    """What a routed layer's router sees for the residual stream x
    [B, T, E]: sigmoid scores [B * T, n_routed], float32."""
    u = norm(x, w["pre_mixer_norm"], cfg)
    mixer = latent_attention if "kv_b_proj" in w else delta_mixer
    x = x + norm(mixer(w, u, cfg, "float32"), w["post_mixer_norm"], cfg)
    h = norm(x, w["pre_ffn_norm"], cfg).reshape(-1, x.shape[-1])
    return jax.nn.sigmoid(_mm("ne,eg->ng", h, w["router"], "float32"))


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
    `held` overrides the configuration's share (the share test: the
    stacks in `w` are then that share's)."""
    B, T, E = h.shape
    F, limit = cfg["moe_intermediate_size"], cfg["swiglu_limit"]
    first, count = cfg["experts_held"] if held is None else held
    flat = h.reshape(B * T, E)
    dense, margin = route(w, flat, cfg, precision)

    def one_expert(e, acc):
        gate_up = w["experts_gate_up"][e]
        y = _swiglu(flat, gate_up[:, :F], gate_up[:, F:],
                    w["experts_down"][e], limit, precision)
        return acc + jax.lax.dynamic_index_in_dim(
            dense, first + e, axis=1, keepdims=True) * y

    routed = jax.lax.fori_loop(0, count, one_expert, jnp.zeros_like(flat))
    shared = _swiglu(h, w["shared_gate_proj"], w["shared_up_proj"],
                     w["shared_down_proj"], limit, precision)
    return routed.reshape(B, T, E) + shared, margin.reshape(B, T)


def layer(w, x, cfg: dict, precision: str):
    """One block. Returns (x, margin [B, T]); a layer with no router has
    margin +inf."""
    u = norm(x, w["pre_mixer_norm"], cfg)
    mixer = latent_attention if "kv_b_proj" in w else delta_mixer
    x = x + norm(mixer(w, u, cfg, precision), w["post_mixer_norm"], cfg)
    u = norm(x, w["pre_ffn_norm"], cfg)
    if "router" in w:
        y, margin = experts(w, u, cfg, precision)
    else:
        y = _swiglu(u, w["gate_proj"], w["up_proj"], w["down_proj"],
                    cfg["swiglu_limit"], precision)
        margin = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
    return x + norm(y, w["post_ffn_norm"], cfg), margin


def head(top, x, cfg: dict, precision: str):
    return _mm("bte,ve->btv", norm(x, top["norm"], cfg), top["lm_head"],
               precision)


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
        every sequence (one at a time) and freed. ids [B, T] -> (final
        hidden states [B, T, E], margin [B, T], the top weights)."""
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
