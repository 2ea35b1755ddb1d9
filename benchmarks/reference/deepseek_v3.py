"""A plain DeepSeek-V3-family decoder (`model_type: deepseek_v3`), written
from the layer equations, for the benchmark's `correct` decision. It
imports nothing of the program.

`jax.numpy`, float32, every matrix product at `highest` precision, the
EXPANDED attention over the full sequence (no cache, no absorption), the
routed experts as a dense masked sum over ALL experts (every expert's
SwiGLU for every row, times the row's weight for that expert, which is
zero where the router did not choose it), layer by layer.

Per layer, x the residual stream, h = RMSNorm(x), no biases:
  q = h W_q -> per head [q_nope | q_rope];  [c | k_r] = h W_kv_a;
  c = RMSNorm(c); RoPE (interleaved pairs (2i, 2i+1)) on q_rope and on
  k_r (ONE vector for all heads); [k_nope | v] per head = c W_kv_b;
  scores (q_nope . k_nope + q_rope . k_r) / sqrt(qk_head_dim), causal
  softmax, x += concat(P v) W_o.
  Dense FFN (the first `first_k_dense_replace` layers):
  x += W_down(silu(W_gate h) * W_up h).
  Routed FFN: s = sigmoid(h W_r); choice = the k largest of s + b;
  w = s[choice] / sum(s[choice]) * routed_scaling_factor;
  x += sum_e w_e E_e(h) + S(h), S one SwiGLU of n_shared_experts * F.

Weights are made leaf by leaf from the seed and the leaf's NAME, rounded
to bfloat16 and held in float32: the configuration's parameters ARE
bfloat16. `b` (`e_score_correction_bias`) is drawn small and non-zero, so
that a program that leaves it out of the choice, or adds it to the
weights, fails. At 8 layers the float32 weights are 20 GB, so the scoring
path (:func:`score_sequences`) makes, uses and frees one layer at a time
over all sequences; :func:`init_weights` / :meth:`Reference.logits` hold
the whole model and are for small sizes.

`precision` puts the same mathematics through a lower precision for the
control: "bfloat16" rounds both operands of every matrix product (the
router's too) to bfloat16, "fp8" to float8_e4m3 with one scale per
tensor. Sums stay float32.
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
    """The sizes the reference needs, from a configuration file."""
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "intermediate_size",
            "moe_intermediate_size", "n_routed_experts", "n_shared_experts",
            "num_experts_per_tok", "first_k_dense_replace",
            "norm_topk_prob", "routed_scaling_factor", "rms_norm_eps",
            "rope_theta")
    cfg = {k: config[k] for k in keys}
    cfg["padded_vocab"] = config.get("assumed", {}).get(
        "padded_vocab", config["vocab_size"])
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
    E, H, C = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    Dn, Dr, Dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    out = [("input_layernorm", (E,), "gain"),
           ("q_proj", (E, H * (Dn + Dr)), "matrix"),
           ("kv_a_proj_with_mqa", (E, C + Dr), "matrix"),
           ("kv_a_layernorm", (C,), "gain"),
           ("kv_b_proj", (C, H * (Dn + Dv)), "matrix"),
           ("o_proj", (H * Dv, E), "matrix"),
           ("post_attention_layernorm", (E,), "gain")]
    if i < cfg["first_k_dense_replace"]:
        I = cfg["intermediate_size"]
        return out + [("gate_proj", (E, I), "matrix"),
                      ("up_proj", (E, I), "matrix"),
                      ("down_proj", (I, E), "matrix")]
    G, F = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    S = cfg["n_shared_experts"] * F
    return out + [("router", (E, G), "matrix"),
                  ("e_score_correction_bias", (G,), "bias"),
                  ("experts_gate_up", (G, E, 2 * F), "matrix"),
                  ("experts_down", (G, F, E), "matrix"),
                  ("shared_gate_proj", (E, S), "matrix"),
                  ("shared_up_proj", (E, S), "matrix"),
                  ("shared_down_proj", (S, E), "matrix")]


def top_specs(cfg: dict) -> list[tuple[str, tuple, str]]:
    V, E = cfg["padded_vocab"], cfg["hidden_size"]
    return [("embed_tokens", (V, E), "matrix"), ("norm", (E,), "gain"),
            ("lm_head", (V, E), "matrix")]


def _leaf(key, name: str, shape, kind: str, dtype):
    """Matrices N(0, 0.02); gains 1 + N(0, 0.02); the selection bias
    N(0, 0.02): about twice the usual gap between the 6th and the 7th of
    128 sigmoid scores, so it moves many choices. Every leaf is rounded
    to bfloat16."""
    x = jax.random.normal(
        jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF),
        shape, jnp.float32)
    x = {"matrix": MATRIX_STD * x, "gain": 1.0 + MATRIX_STD * x,
         "bias": BIAS_STD * x}[kind]
    return x.astype(jnp.bfloat16).astype(dtype)


@functools.lru_cache(maxsize=None)
def _maker(specs: tuple, dtype):
    """One program for every layer of one kind: the layer's number is
    folded into the key as data, the leaf's name as a constant."""
    return jax.jit(lambda key, i: {
        name: _leaf(jax.random.fold_in(key, i), name, shape, kind, dtype)
        for name, shape, kind in specs})


_TOP = 0x7FFFFFFF       # the "layer number" of the leaves outside the layers


def layer_weights(cfg: dict, seed: int, i: int, dtype=jnp.float32) -> dict:
    """Layer i's leaves, in ONE jitted call. `dtype=bfloat16` gives the
    same values without the float32 copy (they are bfloat16 numbers)."""
    return _maker(tuple(layer_specs(cfg, i)), jnp.dtype(dtype))(
        seed_key(seed), jnp.int32(i))


def top_weights(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    return _maker(tuple(top_specs(cfg)), jnp.dtype(dtype))(
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


def _rope(x, pos, theta: float):
    """RoPE on the interleaved pairs (2i, 2i+1) of the last axis.
    x [B, T, H, D], pos [B, T]."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos[..., None].astype(jnp.float32) * inv           # [B, T, D/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(h, gate, up, down, precision):
    g = _mm("...e,ef->...f", h, gate, precision)
    u = _mm("...e,ef->...f", h, up, precision)
    return _mm("...f,fe->...e", jax.nn.silu(g) * u, down, precision)


def attention(w, x, pos, cfg: dict, precision: str):
    B, T, E = x.shape
    H, C = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    Dn, Dr, Dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rms(x, w["input_layernorm"], eps)
    q = _mm("bte,ef->btf", h, w["q_proj"], precision).reshape(
        B, T, H, Dn + Dr)
    kv_a = _mm("bte,ef->btf", h, w["kv_a_proj_with_mqa"], precision)
    c = _rms(kv_a[..., :C], w["kv_a_layernorm"], eps)
    q_rope = _rope(q[..., Dn:], pos, theta)
    k_r = _rope(kv_a[..., None, C:], pos, theta)             # [B, T, 1, Dr]
    kv = _mm("btc,cf->btf", c, w["kv_b_proj"], precision).reshape(
        B, T, H, Dn + Dv)
    s = (_mm("bthd,bshd->bhts", q[..., :Dn], kv[..., :Dn], precision)
         + _mm("bthd,bsd->bhts", q_rope, k_r[:, :, 0], precision)
         ) / math.sqrt(Dn + Dr)
    t = jnp.arange(T)
    p = jax.nn.softmax(
        jnp.where((t[:, None] >= t[None, :])[None, None], s, -1e30), axis=-1)
    a = _mm("bhts,bshd->bthd", p, kv[..., Dn:], precision)
    return x + _mm("btf,fe->bte", a.reshape(B, T, H * Dv), w["o_proj"],
                   precision)


def route(w, h, cfg: dict, precision: str):
    """h [N, E] -> (dense weights [N, G], zero where not chosen; margin
    [N] between the k-th and the next of s + b)."""
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


def ffn(w, x, cfg: dict, precision: str):
    """The layer's second half. Returns (x, margin [B, T]); a dense layer
    has no router and its margin is +inf."""
    B, T, E = x.shape
    h = _rms(x, w["post_attention_layernorm"], cfg["rms_norm_eps"])
    if "router" not in w:
        return (x + _swiglu(h, w["gate_proj"], w["up_proj"], w["down_proj"],
                            precision),
                jnp.full((B, T), jnp.inf, jnp.float32))
    F = cfg["moe_intermediate_size"]
    flat = h.reshape(B * T, E)
    dense, margin = route(w, flat, cfg, precision)

    def one_expert(e, acc):
        gu = _mm("ne,ef->nf", flat, w["experts_gate_up"][e], precision)
        y = _mm("nf,fe->ne", jax.nn.silu(gu[:, :F]) * gu[:, F:],
                w["experts_down"][e], precision)
        return acc + dense[:, e, None] * y

    routed = jax.lax.fori_loop(0, cfg["n_routed_experts"], one_expert,
                               jnp.zeros_like(flat))
    shared = _swiglu(h, w["shared_gate_proj"], w["shared_up_proj"],
                     w["shared_down_proj"], precision)
    return x + routed.reshape(B, T, E) + shared, margin.reshape(B, T)


def layer(w, x, pos, cfg: dict, precision: str):
    return ffn(w, attention(w, x, pos, cfg, precision), cfg, precision)


def head(top, x, cfg: dict, precision: str):
    return _mm("bte,ve->btv", _rms(x, top["norm"], cfg["rms_norm_eps"]),
               top["lm_head"], precision)


class Reference:
    """The jitted pieces for one configuration and one precision."""

    def __init__(self, cfg: dict, precision: str = "float32"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}")
        self.cfg = cfg
        self.precision = precision
        self.layer = jax.jit(functools.partial(
            layer, cfg=cfg, precision=precision))
        self.head = jax.jit(functools.partial(
            head, cfg=cfg, precision=precision))

    def embed(self, top, ids):
        ids = jnp.asarray(ids, jnp.int32)
        B, T = ids.shape
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        return top["embed_tokens"][ids], pos

    def logits(self, weights, ids, with_margin: bool = False):
        """Whole-model mode: [B, T, padded_vocab] float32 logits of a full
        forward pass (and the smallest routing margin over the layers,
        [B, T])."""
        x, pos = self.embed(weights, ids)
        margin = jnp.full(x.shape[:2], jnp.inf, jnp.float32)
        for w in weights["layers"]:
            x, m = self.layer(w, x, pos)
            margin = jnp.minimum(margin, m)
        out = self.head(weights, x)
        return (out, margin) if with_margin else out

    def hidden_layerwise(self, seed: int, ids):
        """Layer-at-a-time mode: each layer's weights are made, used over
        every sequence (one at a time: the expanded attention's scores
        are [H, T, T]) and freed. ids [B, T] -> (final hidden states
        [B, T, E], margin [B, T], the top weights)."""
        cfg = self.cfg
        top = top_weights(cfg, seed)
        x, pos = self.embed(top, ids)
        xs = [x[b:b + 1] for b in range(x.shape[0])]
        margins = [jnp.full((1, x.shape[1]), jnp.inf, jnp.float32)] * len(xs)
        for i in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, seed, i)
            for b in range(len(xs)):
                xs[b], m = self.layer(w, xs[b], pos[:1])
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
    (`gaps` [B, T - 1]) and the smallest routing margin over the layers
    at t (`margins` [B, T - 1]). With a lower `precision` also
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
