"""A plain AFMoE-family decoder (`model_type: afmoe`, the Trinity-Mini row),
written from the layer equations, for the benchmark's `correct` decision. It
imports nothing of the program and nothing of the other references.

`jax.numpy`, float32, every matrix product at `highest` precision, layer by
layer over the full sequence: no cache, no pages, no kernels, no sorting,
no batching. No bias anywhere. With `N(x; w) = x rsqrt(mean(x^2) + eps) w`:

  lookup     `h0 = E[ids] * sqrt(hidden_size)` (`mup_enabled`).
  block      FOUR norms: `x <- x + N_post_attn(Attn(N_in(x)))`, then
             `x <- x + N_post_mlp(FFN(N_pre_mlp(x)))`.
  attention  every layer, with `u` the block's normed input: `q = N_d(u W_q)`
             and `k = N_d(u W_k)` a head (one gain of `head_dim` for all
             heads), `v = u W_v`; Hq query heads read K/V head `h // (Hq /
             Hkv)`. In a `"sliding_attention"` layer ONLY: the rotation
             (whole head, pairs `(i, i + d/2)`, `theta^(-2i/d)`, after the
             norm) and the window, written as a MASK on the scores:
             position i sees j with `i - sliding_window < j <= i`. In a
             `"full_attention"` layer no position term and plain causal.
             Scale `d^-0.5`; the heads' concatenated values times
             `sigmoid(u W_g)`, elementwise; `W_o`. A block of queries at a
             time against every key, so that 33,792 positions fit.
  FFN        layers `< num_dense_layers`: `W_down (silu(u W_gate) (u
             W_up))` of `intermediate_size`. Every other: `s = sigmoid(u
             W_r)` over ALL experts; the k largest of `s + b`; `w =
             s[choice] / (sum + 1e-20) * route_scale`; a dense masked sum
             over the experts HELD, each SwiGLU of `moe_intermediate_size`
             (a loop over experts); plus the one shared expert on every
             row.
  head       `N(x)`, the untied head, float32 logits.

Departures from the source repository's modeling file, which this reference
has not seen: each reading below is an inference from the key's name,
stands under the configuration's `assumed`, and is taken by the program
too. (1) `mup_enabled` multiplies the lookup by `sqrt(hidden_size)` and is
read nowhere else. (2) The window counts the token itself (2,048 keys in
all; not taken: 2,048 keys BEFORE it). (3) The rotation pairs lane i with
lane i + d/2 (not taken: adjacent lanes) and follows the norm. (4) The gate
is sigmoid, elementwise over the heads' concatenated values, from the
block's normed input. (5) `expert_bias` moves the choice only; `n_group =
topk_group = 1` means no group limit. (6) `load_balance_coeff` and
`use_grouped_mm` are read by no layer.

Weights are made leaf by leaf from the seed and the leaf's NAME, rounded to
bfloat16 and held in float32: the configuration's parameters ARE bfloat16.
Matrices N(0, `matrix_std`), gains 1 + N(0, 0.02). The selection bias is
NOT drawn: it is BALANCED on a calibration batch by the aux-loss-free rule
(`balance_bias`), as the buffer is in a trained release: a seeded router at
8 of 128 would else decide how much work a step is. The program is handed
the same numbers.

`precision` puts the same mathematics through a lower precision for the
control: "bfloat16" rounds both operands of every matrix product (the
router's too) to bfloat16, "fp8" to float8_e4m3 with one scale per tensor.
Sums stay float32.
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
ROW_BLOCK = 2048         # rows a row-wise part runs at a time
SCORE_BYTES = 1 << 30    # the attention's [Hq, block, T] float32 scores
SLIDING = "sliding_attention"
# the selection bias is balanced on this many sequences of this length
BALANCE_BATCH = (16, 512)
BALANCE_STEPS = 600
BALANCE_RATE = 0.02


def model_cfg(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file."""
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers", "num_dense_layers",
            "layer_types", "sliding_window", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
            "num_experts", "num_experts_per_tok", "route_norm",
            "route_scale")
    cfg = {k: config[k] for k in keys}
    cfg["layer_types"] = tuple(cfg["layer_types"])
    cfg["experts_held"] = tuple(config.get(
        "experts_held", (0, config["num_experts"])))
    assumed = config.get("assumed", {})
    cfg["padded_vocab"] = assumed.get("padded_vocab", config["vocab_size"])
    # toy widths draw wider: std * sqrt(fan-in) is what a layer's output
    # scales with
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
    E, D = cfg["hidden_size"], cfg["head_dim"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    out = [(n, (E,), "gain") for n in (
        "input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm",
        "post_mlp_layernorm")]
    out += [("q_proj", (E, Hq * D), "matrix"),
            ("k_proj", (E, Hkv * D), "matrix"),
            ("v_proj", (E, Hkv * D), "matrix"),
            ("g_proj", (E, Hq * D), "matrix"),
            ("o_proj", (Hq * D, E), "matrix"),
            ("q_norm", (D,), "gain"), ("k_norm", (D,), "gain")]
    if i < cfg["num_dense_layers"]:
        F = cfg["intermediate_size"]
        return out + [("gate_proj", (E, F), "matrix"),
                      ("up_proj", (E, F), "matrix"),
                      ("down_proj", (F, E), "matrix")]
    F, G, held = (cfg["moe_intermediate_size"], cfg["num_experts"],
                  cfg["experts_held"][1])
    return out + [("router", (E, G), "matrix"),
                  ("expert_bias", (G,), "zero"),
                  ("experts_gate_up", (held, E, 2 * F), "matrix"),
                  ("experts_down", (held, F, E), "matrix"),
                  ("shared_gate_proj", (E, F), "matrix"),
                  ("shared_up_proj", (E, F), "matrix"),
                  ("shared_down_proj", (F, E), "matrix")]


def top_specs(cfg: dict) -> list[tuple[str, tuple, str]]:
    V, E = cfg["padded_vocab"], cfg["hidden_size"]
    return [("embed_tokens", (V, E), "matrix"), ("norm", (E,), "gain"),
            ("lm_head", (V, E), "matrix")]


def count_parameters(cfg: dict) -> dict:
    """Leaves' sizes, part by part: {"layer_<i>": n, ..., "top": n,
    "total": n} (the selection bias, a buffer, counted with its layer)."""
    out = {f"layer_{i}": sum(math.prod(s) for _, s, _ in layer_specs(cfg, i))
           for i in range(cfg["num_hidden_layers"])}
    out["top"] = sum(math.prod(s) for _, s, _ in top_specs(cfg))
    out["total"] = sum(out.values())
    return out


def _leaf(key, name: str, shape, kind: str, dtype, matrix_std: float):
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if kind == "matrix":
        x = matrix_std * jax.random.normal(key, shape, jnp.float32)
    elif kind == "gain":
        x = 1.0 + GAIN_STD * jax.random.normal(key, shape, jnp.float32)
    elif kind == "zero":
        x = jnp.zeros(shape, jnp.float32)
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
    same values without the float32 copy (they are bfloat16 numbers). An
    expert layer's selection bias is not drawn: it is BALANCED
    (:func:`_balanced_biases`), float32 whatever `dtype`."""
    w = _seeded_layer(cfg, seed, i, dtype)
    if "expert_bias" in w:
        w["expert_bias"] = _balanced_biases(_cfg_key(cfg), int(seed))[i]
    return w


def top_weights(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    return _maker(tuple(top_specs(cfg)), jnp.dtype(dtype),
                  cfg.get("matrix_std", MATRIX_STD))(
        seed_key(seed), jnp.int32(_TOP))


def balance_bias(scores, k: int, steps: int = BALANCE_STEPS,
                 rate: float = BALANCE_RATE):
    """The selection bias that spreads `scores` [N, G] evenly: the
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
    """{expert layer: bias [G] float32}: the reference's own forward over a
    calibration batch drawn from the seed, layer by layer, each layer's
    bias balanced on the scores it sees there (the layers before it
    already balanced). One layer's float32 weights at a time."""
    t0 = time.perf_counter()
    cfg = dict(cfg_key)
    rows, length = BALANCE_BATCH
    ids = np.random.default_rng([int(seed), 0xBA1A]).integers(
        0, cfg["vocab_size"], (rows, length))
    ref = Reference(cfg)
    top = top_weights(cfg, seed)
    xs = [ref.embed(top, row) for row in ids]
    del top
    balance = jax.jit(functools.partial(
        balance_bias, k=cfg["num_experts_per_tok"]))
    out = {}
    for i in range(cfg["num_hidden_layers"]):
        w = _seeded_layer(cfg, seed, i, jnp.float32)
        xs = [ref.mixer_half(w, x, i) for x in xs]
        if "router" in w:
            out[i] = w["expert_bias"] = balance(jnp.concatenate(
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


def rotate(x, pos, theta: float):
    """x [T, H, d] at positions pos [T]: lane i turns with lane i + d/2 by
    `pos * theta^(-2i/d)`."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def attention_keys(w, u, lo, cfg: dict, precision: str, sliding: bool):
    """u [T, E] (normed) at rows lo .. lo + T -> this block's keys (normed,
    and rotated in a sliding layer) and values [T, Hkv, D]."""
    Hkv, D = cfg["num_key_value_heads"], cfg["head_dim"]
    k = _mm("te,ef->tf", u, w["k_proj"], precision).reshape(-1, Hkv, D)
    k = norm(k, w["k_norm"], cfg)
    if sliding:
        k = rotate(k, lo + jnp.arange(u.shape[0]), cfg["rope_theta"])
    return k, _mm("te,ef->tf", u, w["v_proj"], precision).reshape(-1, Hkv, D)


def attention_block(w, u, k, v, lo, cfg: dict, precision: str,
                    sliding: bool):
    """One block of queries against the whole sequence's keys and values.
    u [Tq, E] (normed) at rows lo .. lo + Tq; k, v [T, Hkv, D]."""
    Hq, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    Tq, T = u.shape[0], k.shape[0]
    rows = lo + jnp.arange(Tq)
    q = _mm("te,ef->tf", u, w["q_proj"], precision).reshape(Tq, Hq, D)
    q = norm(q, w["q_norm"], cfg)
    if sliding:
        q = rotate(q, rows, cfg["rope_theta"])
    q = q.reshape(Tq, Hkv, Hq // Hkv, D)
    s = _mm("thgd,shd->hgts", q, k, precision) * D ** -0.5
    cols = jnp.arange(T)[None, :]
    seen = rows[:, None] >= cols
    if sliding:
        seen = seen & (cols > rows[:, None] - cfg["sliding_window"])
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
    a = _mm("hgts,shd->thgd", p, v, precision).reshape(Tq, Hq * D)
    a = a * jax.nn.sigmoid(_mm("te,ef->tf", u, w["g_proj"], precision))
    return _mm("tf,fe->te", a, w["o_proj"], precision)


def route(w, h, cfg: dict, precision: str):
    """h [N, E] -> (dense weights [N, num_experts], zero where not chosen;
    margin [N] between the k-th and the next of s + b)."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm("ne,eg->ng", h, w["router"], precision))
    vals, idx = jax.lax.top_k(s + w["expert_bias"], k + 1)
    choice = idx[:, :k]
    picked = jnp.take_along_axis(s, choice, axis=-1)
    if cfg["route_norm"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    picked = picked * cfg["route_scale"]
    rows = jnp.arange(h.shape[0])[:, None]
    dense = jnp.zeros_like(s).at[rows, choice].set(picked)
    return dense, vals[:, k - 1] - vals[:, k]


def ffn(w, h, cfg: dict, precision: str, held=None):
    """A layer's FFN over rows h [N, E] -> (output [N, E], the routing
    margin [N]; +inf in a dense layer). `held` overrides the
    configuration's share (the share test: the stacks in `w` are then that
    share's)."""
    if "router" not in w:
        return (_swiglu(h, w["gate_proj"], w["up_proj"], w["down_proj"],
                        precision),
                jnp.full((h.shape[0],), jnp.inf, jnp.float32))
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
    sequence is `x` [T, E]; a layer runs it a block of rows at a time."""

    def __init__(self, cfg: dict, precision: str = "float32"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}")
        cfg = dict(cfg, experts_held=tuple(cfg["experts_held"]),
                   layer_types=tuple(cfg["layer_types"]))
        self.cfg, self.precision = cfg, precision
        kw = dict(cfg=cfg, precision=precision)
        self._norm = jax.jit(functools.partial(norm, cfg=cfg))
        self._keys = jax.jit(functools.partial(attention_keys, **kw),
                             static_argnames="sliding")
        self._attend = jax.jit(functools.partial(attention_block, **kw),
                               static_argnames="sliding")
        self._ffn = jax.jit(functools.partial(ffn, **kw))
        self._scores = jax.jit(lambda w, h: jax.nn.sigmoid(
            _mm("ne,eg->ng", h, w["router"], "float32")))
        self.head = jax.jit(functools.partial(head, **kw))

    def embed(self, top, ids):
        return (top["embed_tokens"][jnp.asarray(ids, jnp.int32)]
                * math.sqrt(self.cfg["hidden_size"]))

    def mixer_half(self, w, x, i: int):
        """x [T, E] -> x + N_post_attn(Attn(N_in(x))) of layer i."""
        cfg = self.cfg
        T = x.shape[0]
        sliding = cfg["layer_types"][i] == SLIDING
        u = self._norm(x, w["input_layernorm"])
        kv = [self._keys(w, u[lo:hi], lo, sliding=sliding)
              for lo, hi in _blocks(T, ROW_BLOCK)]
        k, v = (jnp.concatenate(half) for half in zip(*kv))
        size = max(16, min(ROW_BLOCK, SCORE_BYTES // (
            4 * cfg["num_attention_heads"] * T) // 16 * 16))
        y = jnp.concatenate([
            self._attend(w, u[lo:hi], k, v, lo, sliding=sliding)
            for lo, hi in _blocks(T, size)])
        return x + self._norm(y, w["post_attention_layernorm"])

    def router_scores(self, w, x):
        """What the layer's router sees for x [T, E] (after the mixer's
        half): sigmoid scores [T, num_experts], float32."""
        return self._scores(w, self._norm(x, w["pre_mlp_layernorm"]))

    def ffn_half(self, w, x):
        """x [T, E] -> (x + N_post_mlp(FFN(N_pre_mlp(x))), margin [T])."""
        u = self._norm(x, w["pre_mlp_layernorm"])
        got = [self._ffn(w, u[lo:hi])
               for lo, hi in _blocks(x.shape[0], ROW_BLOCK)]
        y, margin = (jnp.concatenate(part) for part in zip(*got))
        return x + self._norm(y, w["post_mlp_layernorm"]), margin

    def layer(self, w, x, i: int):
        return self.ffn_half(w, self.mixer_half(w, x, i))

    def logits(self, weights, ids, with_margin: bool = False):
        """Whole-model mode (small sizes): [B, T, padded_vocab] float32
        logits of a full forward pass (and the smallest routing margin
        over the layers, [B, T])."""
        outs, margins = [], []
        for row in np.asarray(ids):
            x = self.embed(weights, row)
            margin = jnp.full(x.shape[:1], jnp.inf, jnp.float32)
            for i, w in enumerate(weights["layers"]):
                x, m = self.layer(w, x, i)
                margin = jnp.minimum(margin, m)
            outs.append(self.head(weights, x))
            margins.append(margin)
        out = jnp.stack(outs)
        return (out, jnp.stack(margins)) if with_margin else out

    def hidden_layerwise(self, seed: int, seqs: list):
        """Layer-at-a-time mode: each layer's weights are made, used over
        every sequence (one at a time, each at its own length) and freed.
        seqs: lists of ids -> ([final hidden states [T, E]], [margin
        [T]], the top weights)."""
        cfg = self.cfg
        top = top_weights(cfg, seed)
        xs = [self.embed(top, np.asarray(s)) for s in seqs]
        margins = [jnp.full((x.shape[0],), jnp.inf, jnp.float32) for x in xs]
        for i in range(cfg["num_hidden_layers"]):
            w = layer_weights(cfg, seed, i)
            for b in range(len(xs)):
                xs[b], m = self.layer(w, xs[b], i)
                margins[b] = jnp.minimum(margins[b], m)
            jax.block_until_ready(xs)
            del w
        return xs, margins, top


def _gaps_below_best(logits, nxt, vocab: int):
    """How far the logit of `nxt[t]` lies below the best of row t.
    logits [T, V'], nxt [T] -> [T]."""
    rows = logits[:, :vocab]
    return jnp.max(rows, axis=-1) - jnp.take_along_axis(
        rows, nxt[:, None], axis=-1)[:, 0]


def score_sequences(cfg: dict, seed: int, seqs: list, spans: list,
                    precision: str = "float32") -> dict:
    """The reference over the sequences `seqs` (lists of ids, each a
    prompt and its served answer), layer at a time. For every position t
    of `spans[b] = (lo, hi)` (the rows whose next token was SERVED): the
    gap by which the reference logit of `seqs[b][t + 1]` lies below the
    reference's best (`gaps`) and the smallest routing margin over the
    layers at t (`margins`), each one flat array over all sequences. The
    head runs on those rows only. With a lower `precision` also
    `control_gaps`: the same reading for the tokens that precision's
    reference puts first."""
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
