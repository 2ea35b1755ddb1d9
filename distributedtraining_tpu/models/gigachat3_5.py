"""GigaChat-3.5-family decoder (``model_type: gigachat3_5``): gated
delta-rule layers beside latent attention, routed + shared experts of which
a chip holds its share, on the serving path.

The configuration carries the published keys under their published names
(ai-sage/GigaChat3.5-432B-A28B's ``config.json`` is the row the presets are
cut from). No bias anywhere. The readings marked (assumed) are inferences
from a key's name and the family's lineage; each stands in the benchmark
configuration's ``assumed`` and in benchmarks/reference/gigachat3_5.py,
which takes the SAME reading.

* Norm ``N(x; w)`` (``norm_type: ZeroCenteredGatedNorm``,
  ``layernorm_gating_weight`` 2), float32: ``x rsqrt(mean(x^2) + eps) (2
  sigmoid(w))``: the gain is a scaled sigmoid of the parameter, 1 at ``w =
  0`` (assumed).
* Block (``layernorm_type: pre_post``): ``x <- x + N(Mixer(N(x)))``, then
  ``x <- x + N(FFN(N(x)))``: four norms a layer.
* Linear mixer (``linear_attention_type: GigaChat35GatedDeltaNet``; every
  layer not in ``full_attention_layers``; ops/delta_rule.py): ``[q | k | v
  | z] = u W_qkvz`` (``q``, ``k``: ``linear_num_key_heads`` heads of
  ``linear_key_head_dim``; ``v``, ``z``: ``linear_num_value_heads`` heads
  of ``linear_value_head_dim``), ``[b | a] = u W_ba``; ``(q, k, v) <-
  silu(causal depthwise conv1d(concat(q, k, v)))``, no bias; ``q <-
  l2norm(q) / sqrt(dk)``, ``k <- l2norm(k)``; ``beta = sigmoid(b)``, ``g =
  -exp(A_log) softplus(a + dt_bias)``; the gated delta rule; the output
  ``rmsnorm(o) (2 sigmoid(w_o)) (2 sigmoid(z))`` a head (assumed), then
  ``W_out``. What the layer keeps for a sequence is NOT per token: one
  float32 state ``[value heads, dk, dv]`` and the last ``conv - 1`` rows of
  ``concat(q, k, v)`` before the convolution (``ssm_state_shape``,
  ``ssm_tail_shape``). The matrix's columns are laid ``q | k | v | z``
  whole, not a key head's group at a time as the lineage's checkpoints lay
  them: with seeded weights the layout is the program's own.
* Latent attention (layers in ``full_attention_layers``):
  ``c_q = N(u W_qa)``, ``q = c_q W_qb`` -> per head ``[q_nope | q_rope]``;
  ``[c_kv | k_r] = u W_kva``, ``c = N(c_kv)``; interleaved rotary with
  YaRN's blended frequencies on ``q_rope`` and ``k_r``; softmax scale
  ``qk_head_dim^-0.5 m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``;
  both forms are ``ops.mla_attention.latent_attention``, shared with
  models/deepseek_v3.py; ``gated_attention``: the heads' concatenated
  values times ``sigmoid(u W_g)`` before ``o_proj`` (assumed). Caches
  ``c`` and ``k_r`` a token (``cache_row_widths``).
* Dense FFN (the first ``first_k_dense_replace`` layers): SwiGLU of
  ``intermediate_size``. Routed FFN (the rest): sigmoid router with a
  selection bias over ALL ``n_routed_experts`` (assumed: the lineage's
  ``noaux_tc``), ``num_experts_per_tok`` chosen, normalised, scaled;
  experts and the one shared expert SwiGLU of ``moe_intermediate_size``.
  Every SwiGLU is clamped by ``swiglu_limit`` (``ops.moe.clamped_swiglu``).
  ``experts_held = (first, count)`` says which of the router's experts
  THIS chip holds: the layer routes over all of them and computes the rows
  routed to its own; the rest is another chip's and is left out.

What each layer caches is stated per layer (``layer_caches``): ``"ssm"`` a
per-slot state with its convolution tail, ``"kv"`` the latent page pair;
engine/kv_pool.py builds both from it and from the shapes stated here, in
one engine. The published multi-token-prediction modules
(``num_nextn_predict_layers``) are a drafter's and are not built.

Serving only: no backward pass is written for the chunked delta rule, and
the fleet plane does not know this family (ROADMAP M2, M5).
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import delta_rule, moe, ssm
from ..ops.embed import embed_lookup
from ..ops.mla_attention import latent_attention
from .gpt2 import pad_vocab
from .llama import _dense, rotary_embedding
from .nemotron_h import _a_log_init, _conv_init, _dt_bias_init

_YARN = (("beta_fast", 32), ("beta_slow", 1), ("factor", 8), ("mscale", 1),
         ("mscale_all_dim", 1), ("original_max_position_embeddings", 32768),
         ("type", "yarn"))


@dataclasses.dataclass(frozen=True)
class GigaChat35Config:
    # the published keys, under their published names
    vocab_size: int = 128256
    max_position_embeddings: int = 262144
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 64
    num_key_value_heads: int = 64
    n_shared_experts: int = 1
    n_routed_experts: int = 256
    routed_scaling_factor: float = 2.5
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    qk_nope_head_dim: int = 128
    qk_head_dim: int = 192
    n_group: int = 1
    topk_group: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 3
    norm_topk_prob: bool = True
    rope_interleave: bool = True
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 100000.0
    rope_scaling: tuple = _YARN          # the published group, as pairs
    attention_bias: bool = False
    norm_type: str = "ZeroCenteredGatedNorm"
    layernorm_type: str = "pre_post"
    layernorm_gating_weight: float = 2.0
    gated_attention: bool = True
    use_shared_expert_sigmoid: bool = False
    use_mla_scaling_factor: bool = True
    linear_attention_type: str = "GigaChat35GatedDeltaNet"
    full_attention_layers: tuple[int, ...] = tuple(range(3, 40, 4))
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    linear_num_key_heads: int = 32
    linear_num_value_heads: int = 64
    linear_gating_type: str = "gated_rmsnorm_sigmoid_zero_centered"
    linear_sigmoid_gate_scale: float = 2.0
    linear_attn_o_norm_eps: float = 1e-6
    swiglu_limit: float = 10.0
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 2
    # the program's own
    experts_held: tuple[int, int] = (0, 256)   # (first, count) on this chip
    chunk_size: int = delta_rule.CHUNK
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    logits_dtype: str = "float32"
    attention_impl: str = "dense"
    vocab_multiple: int = 128
    remat: bool = False
    scan_blocks: bool = False

    def __post_init__(self):
        first, count = self.experts_held
        yarn = dict(self.rope_scaling)
        unsupported = {
            "full_attention_layers": not all(
                0 <= i < self.num_hidden_layers
                for i in self.full_attention_layers),
            "first_k_dense_replace": not (
                0 <= self.first_k_dense_replace <= self.num_hidden_layers),
            "n_group/topk_group": (self.n_group, self.topk_group) != (1, 1),
            "hidden_act": self.hidden_act != "silu",
            "attention_bias": self.attention_bias,
            "rope_scaling": (yarn.get("type") != "yarn"
                             or yarn["mscale"] != yarn["mscale_all_dim"]),
            "norm_type": self.norm_type != "ZeroCenteredGatedNorm",
            "layernorm_type": self.layernorm_type != "pre_post",
            "gated_attention": not self.gated_attention,
            "use_shared_expert_sigmoid": self.use_shared_expert_sigmoid,
            "use_mla_scaling_factor": not self.use_mla_scaling_factor,
            "linear_attention_type":
                self.linear_attention_type != "GigaChat35GatedDeltaNet",
            "linear_gating_type": (self.linear_gating_type
                                   != "gated_rmsnorm_sigmoid_zero_centered"),
            "linear_num_key_heads": (self.linear_num_value_heads
                                     % self.linear_num_key_heads != 0),
            "n_shared_experts": self.n_shared_experts != 1,
            "experts_held": not (0 <= first and count >= 1
                                 and first + count <= self.n_routed_experts),
            "tie_word_embeddings": self.tie_word_embeddings,
            "qk_head_dim": self.qk_head_dim != (self.qk_nope_head_dim
                                                + self.qk_rope_head_dim),
            "scan_blocks": self.scan_blocks,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"GigaChat35Config: {', '.join(bad)} not "
                             "supported (this block writes one reading of "
                             "each key: see the module's docstring)")

    @property
    def padded_vocab(self) -> int:
        return pad_vocab(self.vocab_size, self.vocab_multiple)

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def cache_row_widths(self) -> tuple[int, int]:
        """What a latent-attention layer caches a token
        (engine/kv_pool.row_widths)."""
        return self.kv_lora_rank, self.qk_rope_head_dim

    @property
    def layer_caches(self) -> tuple[str, ...]:
        """What each layer keeps for a sequence (engine/kv_pool.py):
        ``"kv"`` the latent pair a TOKEN in the full-attention layers,
        ``"ssm"`` a fixed-size state a SLOT in every other."""
        return tuple("kv" if i in self.full_attention_layers else "ssm"
                     for i in range(self.num_hidden_layers))

    # what a per-slot layer keeps, under the names kv_pool.make_state_pool
    # reads, and the name its gauge and counter carry (serve.gdn.*)
    state_name = "gdn"

    @property
    def ssm_state_shape(self) -> tuple[int, int, int]:
        return (self.linear_num_value_heads, self.linear_key_head_dim,
                self.linear_value_head_dim)

    @property
    def conv_dim(self) -> int:
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def ssm_tail_shape(self) -> tuple[int, int]:
        return self.linear_conv_kernel_dim - 1, self.conv_dim

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim^-0.5 m^2`` with YaRN's ``m = 0.1 mscale_all_dim
        ln(factor) + 1`` (DeepSeek-V3's rule; ``use_mla_scaling_factor``)."""
        yarn = dict(self.rope_scaling)
        m = 0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"]) + 1.0
        return self.qk_head_dim ** -0.5 * m * m

    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def storage_dtype(self):
        return jnp.dtype(self.param_dtype)

    def rounds_first(self, path: tuple[str, ...]) -> bool:
        """See ``GPT2Config.rounds_first``. Cast before every use: the
        ``nn.Dense`` kernels, ``kv_b_proj``, the experts' two stacks, the
        head; the lookup's rows straight after the gather. Not ``A_log``,
        ``dt_bias``, the convolution (they enter the float32 recurrence),
        a norm's parameter, the router or its selection bias (float32
        scores): those leaves are float32 in the tree and stay so."""
        return path[-1] in _CAST_FIRST


_CAST_FIRST = ("kernel", "kv_b_proj", "experts_gate_up", "experts_down",
               "lm_head", "embed_tokens")

PRESETS: dict[str, GigaChat35Config] = {
    # the published sizes: 432B parameters, never built on one chip
    "gigachat3.5-432b-a28b": GigaChat35Config(),
    # one chip's share of a stated deployment: published layer 0 (linear
    # mixer, dense FFN: the three leading dense layers count once) and
    # layers 3-6 (latent, linear, linear, linear: one whole period), with
    # experts 0..15 of the 256 that sixteen chips share and ids 0..16,031
    # of the vocabulary; the final norm and the head too (stage 1 of a
    # pipeline, head held here so that it yields logits).
    # benchmarks/configs/gigachat3.5-432b-a28b-l5-e16-v16k.json
    "gigachat3.5-432b-a28b-l5-e16-v16k": GigaChat35Config(
        num_hidden_layers=5, first_k_dense_replace=1,
        full_attention_layers=(1,), vocab_size=16032,
        num_nextn_predict_layers=0, experts_held=(0, 16)),
    # the same five layers at toy widths, all 8 experts, float32, for the
    # CPU. The state stays [., 128, 128]: the decode kernel's tiles
    "tiny-gigachat": GigaChat35Config(
        vocab_size=512, max_position_embeddings=256, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=4,
        n_routed_experts=8, kv_lora_rank=32, q_lora_rank=24,
        qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
        qk_head_dim=24, num_experts_per_tok=3, first_k_dense_replace=1,
        rope_scaling=tuple(dict(
            _YARN, original_max_position_embeddings=32).items()),
        full_attention_layers=(1,), linear_num_key_heads=2,
        linear_num_value_heads=4, num_nextn_predict_layers=0,
        experts_held=(0, 8), chunk_size=16, param_dtype="float32",
        dtype="float32"),
}


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


def zero_centred_gain(w, weight: float):
    return weight * jax.nn.sigmoid(w.astype(jnp.float32))


def output_gate(z, scale: float):
    """A mixer's output gate, float32: ``scale sigmoid(z)``. The linear
    mixer's (``linear_sigmoid_gate_scale``, 1 at zero) and the attention's
    (``gated_attention``, scale 1)."""
    return scale * jax.nn.sigmoid(z.astype(jnp.float32))


class ZeroCentredNorm(nn.Module):
    """``x rsqrt(mean(x^2) + eps) (weight sigmoid(w))``, float32 inside."""
    eps: float
    weight: float

    @nn.compact
    def __call__(self, x):
        w = self.param("w", nn.initializers.zeros_init(), (x.shape[-1],),
                       jnp.float32)
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (norm * zero_centred_gain(w, self.weight)).astype(x.dtype)


def _norm(cfg, name: str) -> ZeroCentredNorm:
    return ZeroCentredNorm(cfg.rms_norm_eps, cfg.layernorm_gating_weight,
                           name=name)


def _swiglu(h, width: int, names: tuple[str, str, str], cfg):
    gate = _dense(width, names[0], ("embed", "mlp"), cfg)(h)
    up = _dense(width, names[1], ("embed", "mlp"), cfg)(h)
    act = moe.clamped_swiglu(gate, up, cfg.swiglu_limit).astype(gate.dtype)
    return _dense(cfg.hidden_size, names[2], ("mlp", "embed"), cfg)(act)


class GigaChat35Block(nn.Module):
    cfg: GigaChat35Config
    full_attention: bool
    routed: bool

    @nn.compact
    def __call__(self, x, attention_mask, segment_ids, position_ids, live,
                 live_len, kv_lens=None, sow_kv=False, kv_pages=None,
                 page_tables=None, ssm_pools=None, slots=None,
                 ssm_init=None):
        cfg = self.cfg
        h = _norm(cfg, "pre_mixer_norm")(x)
        if self.full_attention:
            y = self._latent(h, attention_mask, segment_ids, position_ids,
                             kv_lens, sow_kv, kv_pages, page_tables)
        else:
            y = self._delta(h, live_len, kv_lens, sow_kv, ssm_pools, slots,
                            ssm_init)
        x = x + _norm(cfg, "post_mixer_norm")(y)
        h = _norm(cfg, "pre_ffn_norm")(x)
        if self.routed:
            y = self._experts(h, live, sow_kv)
        else:
            y = _swiglu(h, cfg.intermediate_size,
                        ("gate_proj", "up_proj", "down_proj"), cfg)
        return x + _norm(cfg, "post_ffn_norm")(y)

    def _delta(self, u, live_len, kv_lens, sow_kv, ssm_pools, slots,
               ssm_init=None):
        cfg = self.cfg
        B, T, E = u.shape
        Hv, dk, dv = cfg.ssm_state_shape
        Hk, K = cfg.linear_num_key_heads, cfg.linear_conv_kernel_dim
        conv_dim = cfg.conv_dim
        cdt, f32 = cfg.compute_dtype(), jnp.float32
        qkvz = _dense(conv_dim + Hv * dv, "in_proj_qkvz", ("embed", "mlp"),
                      cfg)(u)
        ba = _dense(2 * Hv, "in_proj_ba", ("embed", None), cfg)(u)
        qkv, z = qkvz[..., :conv_dim], qkvz[..., conv_dim:]
        conv_w = self.param("conv1d_weight", _conv_init, (K, conv_dim), f32)
        a_log = self.param("A_log", _a_log_init, (Hv,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (Hv,), f32)
        beta = jax.nn.sigmoid(ba[..., :Hv].astype(f32))
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., Hv:].astype(f32)
                                              + dt_bias)

        def split(act):
            """silu(conv) -> q, k [.., Hk, dk] (q scaled, k of unit
            length, float32) and v [.., Hv, dv]."""
            act = jax.nn.silu(act)
            lead = act.shape[:-1]
            q = act[..., :Hk * dk].reshape(*lead, Hk, dk)
            k = act[..., Hk * dk:2 * Hk * dk].reshape(*lead, Hk, dk)

            def unit(a):
                return a * jax.lax.rsqrt(
                    jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

            return (unit(q) * dk ** -0.5, unit(k),
                    act[..., 2 * Hk * dk:].reshape(*lead, Hv, dv))

        if ssm_pools is None:
            with jax.named_scope("gdn.prefill"):
                # from zero, or from what the sequence's earlier part left
                s0, tail0 = (None, None) if ssm_init is None else ssm_init
                # `tail0` is named only when there is one: the fault
                # injectors of benchmarks/tools swap in a
                # `causal_conv1d` of the older signature
                conv, tail = ssm.causal_conv1d(
                    qkv, conv_w, None, live_len,
                    **({} if tail0 is None else {"tail0": tail0}))
                q, k, v = split(conv)
                o, state = delta_rule.delta_rule_prefill(
                    q, k, v, g, beta, live_len, s0, chunk=cfg.chunk_size)
            if sow_kv:
                # the whole of what this layer keeps for the sequence
                self.sow("intermediates", "ssm_cache", (state, tail))
        else:
            with jax.named_scope("gdn.decode"):
                states, tails = ssm_pools
                conv, tails = ssm.conv_decode_update(
                    tails, slots, qkv[:, 0], conv_w, None)
                q, k, v = split(conv)
                # a bucket's padding rows (no sequence: length 0) cost
                # no arithmetic and leave the row they name as it was
                o, states = delta_rule.gdn_decode_update(
                    states, slots, q, k, v, g[:, 0], beta[:, 0],
                    kv_lens > 0)
                o = o[:, None]
            self.sow("intermediates", "ssm_cache", (states, tails))
            self.sow("intermediates", "serve_stats", {
                "gdn_slot_steps": jnp.sum(kv_lens > 0).astype(jnp.int32)})
        # the gated norm a head: the norm's gain and the gate are both
        # scaled sigmoids, 1 at zero
        w_o = self.param("o_norm", nn.initializers.zeros_init(), (dv,), f32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.linear_attn_o_norm_eps)
        o = (o * zero_centred_gain(w_o, cfg.layernorm_gating_weight)
             * output_gate(z.reshape(B, T, Hv, dv),
                           cfg.linear_sigmoid_gate_scale))
        return _dense(E, "out_proj", ("mlp", "embed"), cfg)(
            o.reshape(B, T, Hv * dv).astype(cdt))

    def _latent(self, h, attention_mask, segment_ids, position_ids, kv_lens,
                sow_kv, kv_pages, page_tables):
        cfg = self.cfg
        B, T, E = h.shape
        H, C = cfg.num_attention_heads, cfg.kv_lora_rank
        Dn, Dr, Dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        cdt = cfg.compute_dtype()
        c_q = _norm(cfg, "q_a_norm")(
            _dense(cfg.q_lora_rank, "q_a_proj", ("embed", None), cfg)(h))
        q = _dense(H * (Dn + Dr), "q_b_proj", (None, "qkv"), cfg)(c_q)
        q = q.reshape(B, T, H, Dn + Dr)
        q_nope, q_rope = q[..., :Dn], q[..., Dn:]
        kv_a = _dense(C + Dr, "kv_a_proj_with_mqa", ("embed", None), cfg)(h)
        c = _norm(cfg, "kv_a_norm")(kv_a[..., :C])
        inv_freq = yarn_inv_freq(Dr, cfg.rope_theta, dict(cfg.rope_scaling))
        q_rope = rotary_embedding(q_rope, position_ids, cfg.rope_theta,
                                  interleaved=cfg.rope_interleave,
                                  inv_freq=inv_freq)
        k_r = rotary_embedding(kv_a[..., None, C:], position_ids,
                               cfg.rope_theta,
                               interleaved=cfg.rope_interleave,
                               inv_freq=inv_freq)[:, :, 0]
        if sow_kv:
            # the whole cache of this layer: the normed latent and the
            # one shared rotary key (kv_pool's pair: c first, k_r second)
            self.sow("intermediates", "kv_cache", (c, k_r))
        w_kv_b = self.param(
            "kv_b_proj",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         (None, "qkv")),
            (C, H * (Dn + Dv)), cfg.storage_dtype())
        attn = latent_attention(
            q_nope, q_rope, c, k_r,
            w_kv_b.astype(cdt).reshape(C, H, Dn + Dv), cfg.softmax_scale,
            kv_pages=kv_pages, page_tables=page_tables, kv_lens=kv_lens,
            attention_mask=attention_mask, segment_ids=segment_ids,
            impl=cfg.attention_impl)
        gate = _dense(H * Dv, "o_gate_proj", ("embed", "qkv"), cfg)(h)
        attn = (attn.reshape(B, T, H * Dv).astype(jnp.float32)
                * output_gate(gate, 1.0)).astype(cdt)
        return _dense(E, "o_proj", ("qkv", "embed"), cfg)(attn)

    def _experts(self, h, live, sow_kv):
        cfg = self.cfg
        B, T, E = h.shape
        cdt = cfg.compute_dtype()
        G, F, held = (cfg.n_routed_experts, cfg.moe_intermediate_size,
                      cfg.experts_held)
        normal = nn.initializers.normal(0.02)
        w_router = self.param("router", normal, (E, G), jnp.float32)
        # a buffer in the release: it moves the choice, never the weights
        bias = self.param("e_score_correction_bias",
                          nn.initializers.zeros_init(), (G,), jnp.float32)
        w_gate_up = self.param("experts_gate_up", normal,
                               (held[1], E, 2 * F), cfg.storage_dtype())
        w_down = self.param("experts_down", normal, (held[1], F, E),
                            cfg.storage_dtype())
        flat = h.reshape(B * T, E)
        choice, weights = moe.route(
            flat, w_router, bias, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.norm_topk_prob)
        routed, stats = moe.routed_experts(
            flat, choice, weights, w_gate_up.astype(cdt),
            w_down.astype(cdt), held=held,
            live=None if live is None else live.reshape(B * T),
            swiglu_limit=cfg.swiglu_limit)
        if sow_kv:
            self.sow("intermediates", "serve_stats", stats)
        with jax.named_scope("moe.shared"):
            shared = _swiglu(
                h, cfg.n_shared_experts * F,
                ("shared_gate_proj", "shared_up_proj", "shared_down_proj"),
                cfg)
        return routed.reshape(B, T, E) + shared


class GigaChat35(nn.Module):
    cfg: GigaChat35Config

    @nn.compact
    def __call__(self, input_ids, *, attention_mask=None, segment_ids=None,
                 position_ids=None, deterministic: bool = True,
                 return_hidden: bool = False, kv_lens=None,
                 sow_kv: bool = False, kv_pages=None, page_tables=None,
                 ssm_pools=None, slots=None, ssm_init=None):
        """The serving hooks are nemotron_h.NemotronH.__call__'s:
        ``kv_pages`` one pair for each full-attention layer, in layer
        order (here the LATENT pair), ``ssm_pools`` one ``(states,
        tails)`` pair for each linear layer, ``slots`` [B] the pools' rows
        this step moves on by one token; the moved pools are sown back
        under ``ssm_cache``. Without them a linear layer starts from a
        zero state, or from ``ssm_init`` (one ``(state, tail)`` pair a
        linear layer: what the sequence's earlier part left), and sows the
        state after the last live position (``attention_mask`` says which
        are live)."""
        del deterministic
        cfg = self.cfg
        B, T = input_ids.shape
        wte = self.param(
            "embed_tokens",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         ("vocab", "embed")),
            (cfg.padded_vocab, cfg.hidden_size), cfg.storage_dtype())
        if position_ids is None:
            position_ids = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        # the rows a routed layer counts and a linear layer feeds on: not
        # a prefill bucket's padding, not a decode bucket's empty slots
        if attention_mask is not None:
            live = attention_mask.astype(bool)
        elif kv_lens is not None:
            live = jnp.broadcast_to(kv_lens[:, None] > 0, (B, T))
        else:
            live = None
        live_len = (jnp.full((B,), T, jnp.int32) if attention_mask is None
                    else jnp.sum(attention_mask.astype(jnp.int32), axis=1))
        x = embed_lookup(wte, input_ids).astype(cfg.compute_dtype())
        n_kv = n_ssm = 0
        for i in range(cfg.num_hidden_layers):
            full = i in cfg.full_attention_layers
            pages = pools = init = None
            if full and kv_pages is not None:
                pages, n_kv = kv_pages[n_kv], n_kv + 1
            if not full:
                if ssm_pools is not None:
                    pools = ssm_pools[n_ssm]
                if ssm_init is not None:
                    init = ssm_init[n_ssm]
                n_ssm += 1
            x = GigaChat35Block(cfg, full, i >= cfg.first_k_dense_replace,
                                name=f"layer_{i}")(
                x, attention_mask, segment_ids, position_ids, live,
                live_len, kv_lens, sow_kv, pages, page_tables, pools, slots,
                init)
        x = _norm(cfg, "norm")(x)
        if return_hidden:
            return x
        lm_head = self.param(
            "lm_head",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         ("vocab", "embed")),
            (cfg.padded_vocab, cfg.hidden_size), cfg.storage_dtype())
        logits = jnp.einsum("bte,ve->btv", x,
                            lm_head.astype(cfg.compute_dtype()),
                            preferred_element_type=jnp.float32)
        return logits.astype(jnp.dtype(cfg.logits_dtype))

    def init_params(self, rng, *, seq_len: int = 8):
        dummy = jnp.zeros((1, seq_len), jnp.int32)
        return nn.meta.unbox(self.init(rng, dummy)["params"])


def make_model(preset_or_cfg) -> tuple[GigaChat35, GigaChat35Config]:
    cfg = (PRESETS[preset_or_cfg] if isinstance(preset_or_cfg, str)
           else preset_or_cfg)
    return GigaChat35(cfg), cfg
