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
  behind the query it is ``family.latent_attention_layer``, shared with
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

from ..ops import delta_rule
from . import family
from .family import dense

_YARN = (("beta_fast", 32), ("beta_slow", 1), ("factor", 8), ("mscale", 1),
         ("mscale_all_dim", 1), ("original_max_position_embeddings", 32768),
         ("type", "yarn"))


@dataclasses.dataclass(frozen=True)
class GigaChat35Config(family.FamilyConfig):
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
    # the program's own, beside family.FamilyConfig's
    experts_held: tuple[int, int] = (0, 256)   # (first, count) on this chip
    chunk_size: int = delta_rule.CHUNK
    attention_impl: str = "dense"

    def __post_init__(self):
        yarn = dict(self.rope_scaling)
        self.refuse({
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
            "experts_held": family.held_outside(self.experts_held,
                                                self.n_routed_experts),
            "tie_word_embeddings": self.tie_word_embeddings,
            "qk_head_dim": self.qk_head_dim != (self.qk_nope_head_dim
                                                + self.qk_rope_head_dim),
            "scan_blocks": self.scan_blocks,
        }, "this block writes one reading of each key: see the module's "
           "docstring")

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

    # cast before every use: the ``nn.Dense`` kernels, ``kv_b_proj``, the
    # experts' two stacks, the head; the lookup's rows straight after the
    # gather. Not ``A_log``, ``dt_bias``, the convolution (they enter the
    # float32 recurrence), a norm's parameter, the router or its selection
    # bias (float32 scores): those leaves are float32 in the tree and stay so
    cast_first = ("kernel", "kv_b_proj", "experts_gate_up", "experts_down",
                  "lm_head", "embed_tokens")

    def norm(self, name: str) -> nn.Module:
        return ZeroCentredNorm(self.rms_norm_eps,
                               self.layernorm_gating_weight, name=name)


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


class GigaChat35Block(nn.Module):
    cfg: GigaChat35Config
    full_attention: bool
    routed: bool

    @nn.compact
    def __call__(self, x, step: family.Step):
        cfg = self.cfg
        h = cfg.norm("pre_mixer_norm")(x)
        y = (self._latent if self.full_attention else self._delta)(h, step)
        x = x + cfg.norm("post_mixer_norm")(y)
        h = cfg.norm("pre_ffn_norm")(x)
        if self.routed:
            y = self._experts(h, step)
        else:
            y = family.swiglu(h, cfg.intermediate_size,
                              ("gate_proj", "up_proj", "down_proj"), cfg,
                              cfg.swiglu_limit)
        return x + cfg.norm("post_ffn_norm")(y)

    def _delta(self, u, step):
        cfg = self.cfg
        B, T, E = u.shape
        Hv, dk, dv = cfg.ssm_state_shape
        Hk, K = cfg.linear_num_key_heads, cfg.linear_conv_kernel_dim
        conv_dim = cfg.conv_dim
        cdt, f32 = cfg.compute_dtype(), jnp.float32
        qkvz = dense(conv_dim + Hv * dv, "in_proj_qkvz", ("embed", "mlp"),
                      cfg)(u)
        ba = dense(2 * Hv, "in_proj_ba", ("embed", None), cfg)(u)
        qkv, z = qkvz[..., :conv_dim], qkvz[..., conv_dim:]
        conv_w = self.param("conv1d_weight", family.conv_init,
                            (K, conv_dim), f32)
        a_log = self.param("A_log", family.a_log_init, (Hv,), f32)
        dt_bias = self.param("dt_bias", family.dt_bias_init, (Hv,), f32)
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

        o = family.delta_rule_layer(self, qkv, conv_w, split, g, beta, step,
                                    cfg)
        # the gated norm a head: the norm's gain and the gate are both
        # scaled sigmoids, 1 at zero
        w_o = self.param("o_norm", nn.initializers.zeros_init(), (dv,), f32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.linear_attn_o_norm_eps)
        o = (o * zero_centred_gain(w_o, cfg.layernorm_gating_weight)
             * output_gate(z.reshape(B, T, Hv, dv),
                           cfg.linear_sigmoid_gate_scale))
        return dense(E, "out_proj", ("mlp", "embed"), cfg)(
            o.reshape(B, T, Hv * dv).astype(cdt))

    def _latent(self, h, step):
        cfg = self.cfg
        B, T, E = h.shape
        H, Dv = cfg.num_attention_heads, cfg.v_head_dim
        c_q = cfg.norm("q_a_norm")(
            dense(cfg.q_lora_rank, "q_a_proj", ("embed", None), cfg)(h))
        q = dense(H * cfg.qk_head_dim, "q_b_proj", (None, "qkv"), cfg)(c_q)
        attn = family.latent_attention_layer(
            self, h, q.reshape(B, T, H, cfg.qk_head_dim), step, cfg,
            "kv_a_norm", cfg.softmax_scale)
        gate = dense(H * Dv, "o_gate_proj", ("embed", "qkv"), cfg)(h)
        attn = (attn.reshape(B, T, H * Dv).astype(jnp.float32)
                * output_gate(gate, 1.0)).astype(cfg.compute_dtype())
        return dense(E, "o_proj", ("qkv", "embed"), cfg)(attn)

    def _experts(self, h, step):
        cfg = self.cfg
        F, limit = cfg.moe_intermediate_size, cfg.swiglu_limit
        routed, _ = family.routed_ffn(
            self, h, cfg, experts=cfg.n_routed_experts, width=2 * F,
            live=step.live, sow=step.sow_kv, swiglu_limit=limit)
        with jax.named_scope("moe.shared"):
            shared = family.swiglu(h, cfg.n_shared_experts * F,
                                   family.SHARED_SWIGLU, cfg, limit)
        return routed.reshape(h.shape) + shared


class GigaChat35(family.ServedDecoder):
    """``kv_pages`` holds the LATENT pair of each full-attention layer."""
    cfg: GigaChat35Config

    def block(self, i: int) -> GigaChat35Block:
        cfg = self.cfg
        return GigaChat35Block(cfg, i in cfg.full_attention_layers,
                               i >= cfg.first_k_dense_replace,
                               name=f"layer_{i}")


make_model = family.make_model(GigaChat35, PRESETS)
