"""What every model family HAS, once: the base its config inherits (the
statements the engine reads), the norm, the bias-free dense layer, the
rotation, the gated FFN bodies, the routed half, and the serving model's
shell. A family file keeps what the family IS: its published config keys,
its mixers, which layer is of which kind, its presets
(docs/architecture.md, "adding a family").

Every helper here that declares a parameter or a sub-module does so under
the CALLING block's scope and under the name the caller gives, so a tree
is the same whether a family calls the helper or spells it out.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Any, Callable, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import delta_rule, moe, ssm
from ..ops.attention import causal_attention
from ..ops.embed import embed_lookup
from ..ops.mla_attention import latent_attention
from ..ops.paged_attention import paged_attention


def pad_vocab(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


@dataclasses.dataclass(frozen=True)
class FamilyConfig:
    """The base of every model config: the program's own keys that every
    family has, and what a family may state to the rest of the program,
    with the value that stands where it states nothing. A reader takes the
    attribute plainly: a misspelt statement fails, it does not fall back.

    - ``layer_caches``: per layer what it keeps for a served sequence,
      ``"kv"`` two rows a TOKEN (pages) for ever, ``"kv_window"`` the same
      rows only while the token is one of the ``sliding_window`` newest
      (pages of a second group, given back behind the window), ``"ssm"`` a
      state with its convolution tail a SLOT, None nothing; None: ``"kv"``
      in every layer (engine/kv_pool.py; the shell below deals the caches
      by it).
    - ``cache_row_widths``: the widths of a ``"kv"`` layer's two rows where
      they are no K/V pair of heads; None: ``n_kv_head or n_head`` heads of
      ``head_dim``, twice (engine/kv_pool.py, engine/speculative.py).
    - ``state_name``, ``ssm_state_shape``, ``ssm_tail_shape``: an ``"ssm"``
      layer's state, its name in the registry and in the device trace
      (engine/kv_pool.py, ``slot_state_layer`` below).
    - ``cast_first``: the last path component of the leaves EVERY use of
      which in the serving forward casts to the compute dtype first, so
      that the serving tree holds them rounded once a revision
      (``rounds_first(path)``; engine/serve_weights.py).
    - ``serving_head``: ``(leaf the serving tree adds, leaf it is rounded
      from)`` for a tied head whose table the lookup reads unrounded
      (engine/serve_weights.py).
    - ``is_buffer``: ``(path) -> bool`` for leaves that are no parameters
      (the optimizer neither moves nor decays them); None: there are none
      and the optimizer is the plain one (neurons/common.py,
      engine/train.py).
    - ``experts_held``: ``(first, count)`` of the router's experts whose
      stacks this chip holds; None: all (``routed_ffn`` below).
    - ``norm(name)``: the family's norm layer (its blocks, the shell).
    """
    dtype: str = "bfloat16"           # activations and products
    param_dtype: str = "bfloat16"     # storage
    logits_dtype: str = "float32"
    vocab_multiple: int = 128         # the vocabulary's rows, in lane tiles
    remat: bool = False
    scan_blocks: bool = False

    layer_caches = None
    cache_row_widths = None
    state_name = "ssm"
    cast_first = ()
    serving_head = (None, None)
    is_buffer = None
    experts_held = None
    route_norm_eps = 1e-20      # under the chosen scores' sum (ops/moe.route)

    @property
    def padded_vocab(self) -> int:
        return pad_vocab(self.vocab_size, self.vocab_multiple)

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    # the K/V heads of a ``"kv"`` layer, under the names engine/kv_pool.py
    # reads
    @property
    def n_head(self) -> int:
        return self.num_attention_heads

    @property
    def n_kv_head(self) -> int:
        return self.num_key_value_heads

    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def storage_dtype(self):
        return jnp.dtype(self.param_dtype)

    def rounds_first(self, path: tuple[str, ...]) -> bool:
        return path[-1] in self.cast_first

    def norm(self, name: str) -> nn.Module:
        return RMSNorm(self.rms_norm_eps, "float32", name=name)

    def refuse(self, unsupported: dict[str, Any], why: str) -> None:
        """Raise for the keys of ``unsupported`` whose value is true: a
        family writes ONE reading of each published key."""
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"{type(self).__name__}: {', '.join(bad)} not "
                             f"supported ({why})")


def held_outside(held: tuple[int, int], experts: int) -> bool:
    first, count = held
    return not (0 <= first and count >= 1 and first + count <= experts)


def rotary_embedding(x: jax.Array, position_ids: jax.Array,
                     theta: float, *, interleaved: bool = False,
                     inv_freq: jax.Array | None = None) -> jax.Array:
    """Apply RoPE to [B, T, H, D] given positions [B, T]. Pair i is the
    lanes ``(i, i + D/2)`` (Llama's halves) or, ``interleaved``, the
    lanes ``(2i, 2i + 1)`` (DeepSeek-V3's ``rope_interleave``).
    ``inv_freq`` [D/2] stands in for ``theta``'s plain frequencies (a
    family whose ``rope_scaling`` blends them)."""
    D = x.shape[-1]
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32)
                                    / D))
    angles = position_ids[..., None].astype(jnp.float32) * inv_freq  # [B,T,D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = x32[..., 0::2], x32[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                        axis=-1).reshape(x.shape)
        return out.astype(x.dtype)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_inv_freq(dim: int, theta: float, scaling: dict) -> jax.Array:
    """YaRN's blended rotary frequencies [dim / 2] (DeepSeek-V3's
    ``DeepseekV3YarnRotaryEmbedding``): pairs that turn more than
    ``beta_fast`` times over the original context keep their frequency,
    those that turn fewer than ``beta_slow`` times are divided by
    ``factor``, a linear ramp between."""
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]

    def correction(turns):
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction(scaling["beta_slow"])), dim - 1)
    plain = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


class RMSNorm(nn.Module):
    eps: float
    param_dtype: str

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(), ("embed",)),
            (x.shape[-1],), jnp.dtype(self.param_dtype))
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                                   + self.eps)
        return (norm * scale).astype(x.dtype)


def _normal(*axes):
    return nn.with_logical_partitioning(nn.initializers.normal(0.02), axes)


def dense(features: int, name: str, axes: tuple, cfg) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=cfg.compute_dtype(),
                    param_dtype=cfg.storage_dtype(),
                    kernel_init=_normal(*axes), name=name)


def a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


def conv_init(key, shape, dtype):
    """PyTorch's default for a depthwise fan-in of K taps: U(-1/sqrt(K),
    1/sqrt(K))."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def dt_bias_init(key, shape, dtype):
    """The inverse softplus of a step drawn log-uniformly from
    [0.001, 0.1] (Mamba-2's ``time_step_min`` / ``time_step_max``)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _gated(h, width: int, names: tuple[str, str, str], cfg, act: Callable):
    gate = dense(width, names[0], ("embed", "mlp"), cfg)(h)
    up = dense(width, names[1], ("embed", "mlp"), cfg)(h)
    return dense(cfg.hidden_size, names[2], ("mlp", "embed"), cfg)(
        act(gate, up))


def swiglu(h, width: int, names: tuple[str, str, str], cfg,
           limit: float | None = None):
    """``W_down(silu(W_gate h) * W_up h)``, ``names`` the three matrices':
    the product in float32 and, with ``limit`` (a family's
    ``swiglu_limit``), clamped (``ops.moe.clamped_swiglu``)."""
    return _gated(h, width, names, cfg, lambda gate, up: moe.clamped_swiglu(
        gate, up, limit).astype(gate.dtype))


def plain_swiglu(h, width: int, names: tuple[str, str, str], cfg):
    """The same with the product in the compute dtype, as the releases of
    kanana-2 and LFM2 spell it: it rounds otherwise than ``swiglu``."""
    return _gated(h, width, names, cfg, lambda gate, up: nn.silu(gate) * up)


SHARED_SWIGLU = ("shared_gate_proj", "shared_up_proj", "shared_down_proj")


def relu2(h, width: int, names: tuple[str, str], cfg):
    """``W_down relu(W_up h)^2``, the square in float32."""
    up = dense(width, names[0], ("embed", "mlp"), cfg)(h)
    act = jnp.square(nn.relu(up.astype(jnp.float32))).astype(up.dtype)
    return dense(cfg.hidden_size, names[1], ("mlp", "embed"), cfg)(act)


def grouped_query_attention(module: nn.Module, h, step: Step, cfg,
                            impl: str, gate: Callable | None = None, *,
                            qk_norm: bool = False,
                            rope_theta: float | None = None,
                            window: int | None = None):
    """``cfg.n_head`` query heads over ``cfg.n_kv_head`` K/V heads of
    ``cfg.head_dim``, scale ``head_dim^-0.5``, causal; caches one K/V pair
    of heads a token, in pages. With ``gate``: ``W_o [softmax(q k^T) v *
    gate(W_g h)]``, elementwise over the heads' concatenated values,
    float32. What a layer has beside that is an argument's value:
    ``qk_norm`` the family's norm over each head of q and of k (``q_norm``,
    ``k_norm``: one gain of ``head_dim`` for all heads); ``rope_theta`` a
    rotation of the whole head in Llama's halves, after the norm (None: NO
    position term); ``window`` position ``i`` sees ``j`` with ``i - window
    < j <= i`` (None: every ``j <= i``). The rows are cached as attended:
    normed and rotated."""
    B, T, E = h.shape
    Hq, Hkv, Dh = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q = dense(Hq * Dh, "q_proj", ("embed", "qkv"), cfg)(h)
    k = dense(Hkv * Dh, "k_proj", ("embed", "qkv"), cfg)(h)
    v = dense(Hkv * Dh, "v_proj", ("embed", "qkv"), cfg)(h)
    if gate is not None:
        z = dense(Hq * Dh, "g_proj", ("embed", "qkv"), cfg)(h)
    q = q.reshape(B, T, Hq, Dh)
    k, v = k.reshape(B, T, Hkv, Dh), v.reshape(B, T, Hkv, Dh)
    if qk_norm:
        q, k = cfg.norm("q_norm")(q), cfg.norm("k_norm")(k)
    if rope_theta is not None:
        q = rotary_embedding(q, step.position_ids, rope_theta)
        k = rotary_embedding(k, step.position_ids, rope_theta)
    windowed = {} if window is None else {"window": window}
    if step.sow_kv:
        module.sow("intermediates", "kv_cache", (k, v))
    if step.kv_pages is not None:
        attn = paged_attention(q, *step.kv_pages, step.page_tables,
                               step.kv_lens, k, v, **windowed)
    else:
        rep = Hq // Hkv
        attn = causal_attention(
            q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
            attention_mask=step.attention_mask,
            segment_ids=step.segment_ids, impl=impl, **windowed)
    attn = attn.reshape(B, T, Hq * Dh)
    if gate is not None:
        attn = (attn.astype(jnp.float32) * gate(z)).astype(
            cfg.compute_dtype())
    return dense(E, "o_proj", ("qkv", "embed"), cfg)(attn)


def latent_attention_layer(module: nn.Module, h, q, step: Step, cfg,
                           norm_name: str, scale: float):
    """DeepSeek-V3's latent attention behind the family's query ``q`` [B,
    T, H, nope + rope]: ``[c_kv | k_r] = h W_kva``, ``c =
    cfg.norm(norm_name)(c_kv)``; rotary (``rope_interleave``; YaRN's
    blended frequencies where the config states ``rope_scaling``) on the
    query's rope part and on ``k_r``, ONE vector for all heads; ``[k_nope |
    v]`` a head ``= c W_kvb``. Caches ``c`` and ``k_r`` a token
    (``cache_row_widths``); attends in the expanded form without a cache
    and in the absorbed form over the paged one
    (``ops.mla_attention.latent_attention``). -> [B, T, H, v_head_dim]."""
    H, C = cfg.num_attention_heads, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = q[..., :Dn], q[..., Dn:]
    kv_a = dense(C + Dr, "kv_a_proj_with_mqa", ("embed", None), cfg)(h)
    c = cfg.norm(norm_name)(kv_a[..., :C])
    rope = functools.partial(
        rotary_embedding, position_ids=step.position_ids,
        theta=cfg.rope_theta, interleaved=cfg.rope_interleave,
        inv_freq=None if cfg.rope_scaling is None else yarn_inv_freq(
            Dr, cfg.rope_theta, dict(cfg.rope_scaling)))
    q_rope, k_r = rope(q_rope), rope(kv_a[..., None, C:])[:, :, 0]
    if step.sow_kv:
        # the whole cache of this layer: the normed latent and the one
        # shared rotary key (kv_pool's pair: c first, k_r second)
        module.sow("intermediates", "kv_cache", (c, k_r))
    w_kv_b = module.param("kv_b_proj", _normal(None, "qkv"),
                          (C, H * (Dn + Dv)), cfg.storage_dtype())
    return latent_attention(
        q_nope, q_rope, c, k_r,
        w_kv_b.astype(cfg.compute_dtype()).reshape(C, H, Dn + Dv), scale,
        kv_pages=step.kv_pages, page_tables=step.page_tables,
        kv_lens=step.kv_lens, attention_mask=step.attention_mask,
        segment_ids=step.segment_ids, impl=cfg.attention_impl)


def routed_ffn(module: nn.Module, h, cfg, *, experts: int, width: int,
               live=None, sow: bool = False,
               bias: str = "e_score_correction_bias",
               first: str = "experts_gate_up", latent: int | None = None,
               router_dtype=jnp.float32, **counted) -> tuple[jax.Array, dict]:
    """The routed half of an FFN, under ``module``'s scope: the router's
    kernel over all ``experts`` and its selection bias ``bias`` (a buffer in
    every release: it moves the choice, never the weights), the two stacks
    of the experts held here (``cfg.experts_held``; ``first`` [G, D, width]
    and ``experts_down`` [G, F, D], whose widths say which body an expert
    is: ops/moe.py), the choice, the grouped products over the held rows.
    ``latent``: the experts work in that width ``D``, between ``latent_in``
    and ``latent_out``; else on ``h`` itself. ``live`` [B, T] marks the rows
    that are no padding; ``counted`` goes to ``moe.routed_experts``
    (``swiglu_limit``, ``router_experts``, ``count_fullest``). -> (the rows
    [B * T, E], for the caller to shape where it adds them; what the layer
    counted), the counters sown under ``serve_stats`` too where ``sow``."""
    B, T, E = h.shape
    F, D = cfg.moe_intermediate_size, latent or E
    held, cdt = cfg.experts_held, cfg.compute_dtype()
    G = experts if held is None else held[1]
    normal = nn.initializers.normal(0.02)
    w_router = module.param("router", normal, (E, experts), router_dtype)
    b_router = module.param(bias, nn.initializers.zeros_init(), (experts,),
                            jnp.float32)
    w_in = module.param(first, normal, (G, D, width), cfg.storage_dtype())
    w_down = module.param("experts_down", normal, (G, F, D),
                          cfg.storage_dtype())
    x = flat = h.reshape(B * T, E)
    choice, weights = moe.route(
        flat, w_router, b_router, cfg.num_experts_per_tok,
        cfg.routed_scaling_factor, cfg.norm_topk_prob, cfg.route_norm_eps)
    if latent:
        with jax.named_scope("moe.latent_in"):
            x = dense(latent, "latent_in", ("embed", None), cfg)(flat)
    out, stats = moe.routed_experts(
        x, choice, weights, w_in.astype(cdt), w_down.astype(cdt), held=held,
        live=None if live is None else live.reshape(B * T), **counted)
    if sow:
        module.sow("intermediates", "serve_stats", stats)
    if latent:
        with jax.named_scope("moe.latent_out"):
            out = dense(E, "latent_out", (None, "embed"), cfg)(out)
    return out, stats


def slot_state_layer(module: nn.Module, x, conv_w, conv_b, step: Step, cfg,
                     prefill: Callable, decode: Callable):
    """What a layer with a per-slot state (``"ssm"`` in
    ``cfg.layer_caches``) does with its caches, under the names
    engine/kv_pool.py reads: ``x`` [B, T, C] goes through the causal
    depthwise convolution (``conv_w`` [K, C], ``conv_b`` or None) and the
    family's recurrence. Without pools the whole of ``x``:
    ``prefill(conv, s0) -> (y, state)`` from ``step.ssm_init`` or zero, the
    state after the last live position and the convolution's tail sown
    under ``ssm_cache`` where ``step.sow_kv``. With pools one token:
    ``decode(conv, states) -> (y [B, ..], states)`` on the rows
    ``step.slots``, the moved pools sown back and the live slots counted
    (``<cfg.state_name>_slot_steps``). -> y [B, T, ..]."""
    name = cfg.state_name
    if step.ssm_pools is None:
        with jax.named_scope(f"{name}.prefill"):
            s0, tail0 = ((None, None) if step.ssm_init is None
                         else step.ssm_init)
            # `tail0` is named only when there is one: the fault injectors
            # of benchmarks/tools swap in a `causal_conv1d` of the older
            # signature
            conv, tail = ssm.causal_conv1d(
                x, conv_w, conv_b, step.live_len,
                **({} if tail0 is None else {"tail0": tail0}))
            y, state = prefill(conv, s0)
        if step.sow_kv:
            # the whole of what this layer keeps for the sequence
            module.sow("intermediates", "ssm_cache", (state, tail))
        return y
    with jax.named_scope(f"{name}.decode"):
        states, tails = step.ssm_pools
        conv, tails = ssm.conv_decode_update(tails, step.slots, x[:, 0],
                                             conv_w, conv_b)
        y, states = decode(conv, states)
        y = y[:, None]
    module.sow("intermediates", "ssm_cache", (states, tails))
    module.sow("intermediates", "serve_stats", {
        f"{name}_slot_steps": jnp.sum(step.kv_lens > 0).astype(jnp.int32)})
    return y


def delta_rule_layer(module: nn.Module, qkv, conv_w, split: Callable, g,
                     beta, step: Step, cfg):
    """:func:`slot_state_layer` with the gated delta rule
    (ops/delta_rule.py) as the recurrence: ``split(conv) -> (q, k, v)`` a
    head, ``g`` the log-decay (a head or a key channel) and ``beta`` [B, T,
    H]. -> o [B, T, H, dv], float32."""
    def prefill(conv, s0):
        q, k, v = split(conv)
        return delta_rule.delta_rule_prefill(
            q, k, v, g, beta, step.live_len, s0, chunk=cfg.chunk_size)

    def decode(conv, states):
        q, k, v = split(conv)
        # a bucket's padding rows (no sequence: length 0) cost no
        # arithmetic and leave the row they name as it was
        return delta_rule.gdn_decode_update(
            states, step.slots, q, k, v, g[:, 0], beta[:, 0],
            step.kv_lens > 0)

    return slot_state_layer(module, qkv, conv_w, None, step, cfg, prefill,
                            decode)


def embed_table(module: nn.Module, cfg, name: str = "embed_tokens"):
    """The [padded_vocab, hidden] table under ``name``: the lookup's, the
    untied head's."""
    return module.param(name, _normal("vocab", "embed"),
                        (cfg.padded_vocab, cfg.hidden_size),
                        cfg.storage_dtype())


def logits(x, table, cfg):
    """[B, T, padded_vocab] over ``table``'s rows, accumulated in float32."""
    out = jnp.einsum("bte,ve->btv", x, table.astype(cfg.compute_dtype()),
                     preferred_element_type=jnp.float32)
    return out.astype(jnp.dtype(cfg.logits_dtype))


def default_positions(position_ids, B: int, T: int):
    if position_ids is None:
        return jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    return position_ids


class Step(NamedTuple):
    """What the shell hands a layer beside the residual stream. The serving
    hooks are gpt2.GPT2.__call__'s: ``sow_kv`` sows the layer's fresh cache
    (``kv_cache`` rows a token, ``ssm_cache`` a state and tail),
    ``kv_pages`` / ``page_tables`` / ``kv_lens`` attend over the paged
    cache (a ``"kv_window"`` layer is handed its own group's: the narrow
    table, and the lengths counted from that table's first row); and a
    per-slot layer's: ``ssm_pools`` its ``(states, tails)``,
    of which ``slots`` [B] are the rows this step moves on by one token
    (sown back under ``ssm_cache``). Without pools such a layer runs the
    whole of the input, from zero or from ``ssm_init`` (the ``(state,
    tail)`` an earlier part of the same sequence left), and sows the state
    after the last live position."""
    attention_mask: Any = None
    segment_ids: Any = None
    position_ids: Any = None
    live: Any = None        # [B, T] bool: no bucket's padding or empty slot
    live_len: Any = None    # [B]: how many positions of a row are live
    kv_lens: Any = None
    sow_kv: bool = False
    page_tables: Any = None
    slots: Any = None
    kv_pages: Any = None        # this layer's own, dealt by the shell
    ssm_pools: Any = None
    ssm_init: Any = None


class Decoder(nn.Module):
    """``init_params`` for a family's model."""
    cfg: Any

    def init_params(self, rng, *, seq_len: int = 8):
        """Raw (unboxed) param pytree; logical axis metadata is recovered
        separately via parallel.sharding.logical_param_specs."""
        dummy = jnp.zeros((1, seq_len), jnp.int32)
        return nn.meta.unbox(self.init(rng, dummy)["params"])


class ServedDecoder(Decoder):
    """The shell of a served family: the lookup, the live rows, each
    layer's caches dealt in the order ``cfg.layer_caches`` gives, the final
    norm (``final_norm`` its name), the untied head. A family supplies
    ``block(i)``, layer ``i``'s module, called as ``block(x, step)``, and
    may wrap ``embed`` and ``head`` (a multiplier on the lookup, a scope's
    name)."""
    final_norm = "norm"

    def block(self, i: int) -> nn.Module:
        raise NotImplementedError

    def embed(self, table, input_ids):
        return embed_lookup(table, input_ids).astype(
            self.cfg.compute_dtype())

    def head(self, x, table):
        return logits(x, table, self.cfg)

    @nn.compact
    def __call__(self, input_ids, *, attention_mask=None, segment_ids=None,
                 position_ids=None, deterministic: bool = True,
                 return_hidden: bool = False, kv_lens=None,
                 sow_kv: bool = False, kv_pages=None, page_tables=None,
                 ssm_pools=None, slots=None, ssm_init=None,
                 window_pages=None, window_tables=None, window_starts=None):
        """``kv_pages`` one pair for each ``"kv"`` layer, ``ssm_pools`` /
        ``ssm_init`` one for each ``"ssm"`` layer, ``window_pages`` one
        pair for each ``"kv_window"`` layer, in layer order (:class:`Step`
        says what each is). ``window_tables`` [B, pages] is the window
        group's own table and ``window_starts`` [B] the position of its
        first row (engine/kv_pool.py: the shifted table)."""
        del deterministic
        cfg = self.cfg
        B, T = input_ids.shape
        wte = embed_table(self, cfg)
        position_ids = default_positions(position_ids, B, T)
        if attention_mask is not None:
            live = attention_mask.astype(bool)
        elif kv_lens is not None:
            live = jnp.broadcast_to(kv_lens[:, None] > 0, (B, T))
        else:
            live = None
        live_len = (jnp.full((B,), T, jnp.int32) if attention_mask is None
                    else jnp.sum(attention_mask.astype(jnp.int32), axis=1))
        step = Step(attention_mask, segment_ids, position_ids, live,
                    live_len, kv_lens, sow_kv, page_tables, slots)
        x = self.embed(wte, input_ids)
        pages, pools, inits, window = (
            itertools.repeat(None) if dealt is None else iter(dealt)
            for dealt in (kv_pages, ssm_pools, ssm_init, window_pages))
        caches = cfg.layer_caches or ("kv",) * cfg.num_hidden_layers
        # a window layer attends its own group: the narrow table, the
        # lengths counted from its first row
        in_window = {} if window_pages is None else dict(
            page_tables=window_tables, kv_lens=kv_lens - window_starts)
        for i, kind in enumerate(caches):
            if kind == "kv_window":
                x = self.block(i)(x, step._replace(kv_pages=next(window),
                                                   **in_window))
                continue
            x = self.block(i)(x, step._replace(
                kv_pages=next(pages) if kind == "kv" else None,
                ssm_pools=next(pools) if kind == "ssm" else None,
                ssm_init=next(inits) if kind == "ssm" else None))
        x = cfg.norm(self.final_norm)(x)
        if return_hidden:
            return x
        return self.head(x, embed_table(self, cfg, "lm_head"))


def make_model(model: type[Decoder], presets: dict) -> Callable:
    """A family's ``make_model(preset name or config) -> (model, config)``."""
    def make(preset_or_cfg):
        cfg = (presets[preset_or_cfg] if isinstance(preset_or_cfg, str)
               else preset_or_cfg)
        return model(cfg), cfg
    return make
