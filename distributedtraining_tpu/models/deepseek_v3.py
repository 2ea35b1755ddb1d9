"""DeepSeek-V3-family decoder (``model_type: deepseek_v3``): latent
attention (MLA) and routed + shared experts, on the serving path.

The configuration carries the published keys under their published names
(kakaocorp/kanana-2-30b-a3b-instruct-2601's ``config.json`` is the row the
presets are cut from). Per layer, with x the residual stream and
h = RMSNorm(x) (no biases anywhere):

* Latent attention. ``q = h W_q`` -> per head ``[q_nope | q_rope]``;
  ``[c | k_r] = h W_kv_a``; ``c = RMSNorm(c)``; RoPE (interleaved pairs)
  on ``q_rope`` and on ``k_r``, which is ONE vector shared by all heads;
  ``[k_nope | v]`` per head ``= c W_kv_b``; scores
  ``(q_nope . k_nope + q_rope . k_r) / sqrt(qk_head_dim)``, causal
  softmax, ``x += concat(P v) W_o``. What a layer caches per token is
  ``c`` after its norm and ``k_r`` after RoPE, nothing else
  (``cache_row_widths``; engine/kv_pool.py builds the pool from it).
  Without a cache the block attends in the EXPANDED form above through
  ``ops.attention.causal_attention`` (q/k of 192, v of 128: the dense
  product takes the two widths as they are, nothing is padded). Over the
  paged cache it attends in the ABSORBED form: ``q' = q_nope W_uk^T``,
  scores ``(q' . c + q_rope . k_r)``, ``o = (P c) W_uv``, with
  ``W_uk``/``W_uv`` the two halves of ``W_kv_b``. The two forms are one
  function (``ops.mla_attention.latent_attention``), and the layer
  around it is ``family.latent_attention_layer``, models/gigachat3_5.py's
  too (tests/test_deepseek_v3.py).
* Dense FFN (the first ``first_k_dense_replace`` layers): SwiGLU of
  ``intermediate_size``.
* Routed FFN (the rest): sigmoid router with a selection bias
  (``noaux_tc``, one group), ``num_experts_per_tok`` experts of
  ``moe_intermediate_size`` (ops/moe.py: dropless, sorted, grouped
  products), beside ONE SwiGLU of ``n_shared_experts *
  moe_intermediate_size`` (the shared experts).

Serving only: the fleet plane (wire v2, screening, merge of expert
leaves) does not know this family yet (ROADMAP M2).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax

from . import family
from .family import dense


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config(family.FamilyConfig):
    # the published keys, under their published names
    vocab_size: int = 128256
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 64                 # published; MLA does not use it
    kv_lora_rank: int = 512
    q_lora_rank: int | None = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    qk_head_dim: int = 192
    v_head_dim: int = 128
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_group: int = 1
    topk_group: int = 1
    topk_method: str = "noaux_tc"
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    hidden_act: str = "silu"
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    rope_interleave: bool = True
    rope_scaling: None = None
    max_position_embeddings: int = 32768
    tie_word_embeddings: bool = False
    # the program's own, beside family.FamilyConfig's
    attention_impl: str = "dense"

    def __post_init__(self):
        self.refuse({
            "q_lora_rank": self.q_lora_rank is not None,
            "n_group/topk_group": (self.n_group, self.topk_group) != (1, 1),
            "scoring_func": self.scoring_func != "sigmoid",
            "topk_method": self.topk_method != "noaux_tc",
            "moe_layer_freq": self.moe_layer_freq != 1,
            "hidden_act": self.hidden_act != "silu",
            "attention_bias": self.attention_bias,
            "rope_scaling": self.rope_scaling is not None,
            "tie_word_embeddings": self.tie_word_embeddings,
            "qk_head_dim": self.qk_head_dim != (self.qk_nope_head_dim
                                                + self.qk_rope_head_dim),
            "scan_blocks": self.scan_blocks,
        }, "this block takes the query from one full-rank matrix, plain "
           "rotary frequencies and one sigmoid-scored group; a config that "
           "states q_lora_rank and rope_scaling is models/gigachat3_5.py's")

    @property
    def cache_row_widths(self) -> tuple[int, int]:
        """What one layer caches a token (engine/kv_pool.row_widths)."""
        return self.kv_lora_rank, self.qk_rope_head_dim

    # cast before every use: the ``nn.Dense`` kernels, ``kv_b_proj``, the
    # experts' two stacks and the head; the lookup's rows straight after
    # the gather. Not the router and its selection bias (float32 scores,
    # ops/moe.route), not an RMSNorm's scale
    cast_first = ("kernel", "kv_b_proj", "experts_gate_up", "experts_down",
                  "lm_head", "embed_tokens")

    def norm(self, name: str) -> nn.Module:
        return family.RMSNorm(self.rms_norm_eps, self.param_dtype, name=name)


PRESETS: dict[str, DeepseekV3Config] = {
    # the published sizes: 30.7B parameters, never built on one chip
    "kanana-2-30b-a3b": DeepseekV3Config(),
    # depth cut to what one v5e chip holds: the embedding, the leading
    # dense layer, 7 expert layers with all 128 experts, the final norm
    # and the head (stage 1 of an 8-stage pipeline, head held here too)
    "kanana-2-30b-a3b-l8": DeepseekV3Config(num_hidden_layers=8),
    "tiny-kanana": DeepseekV3Config(
        vocab_size=512, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, head_dim=16,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        qk_head_dim=24, v_head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=1,
        num_experts_per_tok=3, max_position_embeddings=256,
        param_dtype="float32", dtype="float32"),
}


class DeepseekV3Block(nn.Module):
    cfg: DeepseekV3Config
    routed: bool

    @nn.compact
    def __call__(self, x, step: family.Step):
        cfg = self.cfg
        B, T, E = x.shape
        H, D = cfg.num_attention_heads, cfg.qk_head_dim

        h = cfg.norm("input_layernorm")(x)
        q = dense(H * D, "q_proj", ("embed", "qkv"), cfg)(h)
        attn = family.latent_attention_layer(
            self, h, q.reshape(B, T, H, D), step, cfg, "kv_a_layernorm",
            D ** -0.5)
        x = x + dense(E, "o_proj", ("qkv", "embed"), cfg)(
            attn.reshape(B, T, -1))

        h = cfg.norm("post_attention_layernorm")(x)
        if not self.routed:
            return x + family.plain_swiglu(
                h, cfg.intermediate_size,
                ("gate_proj", "up_proj", "down_proj"), cfg)
        F = cfg.moe_intermediate_size
        routed, _ = family.routed_ffn(
            self, h, cfg, experts=cfg.n_routed_experts, width=2 * F,
            live=step.live, sow=step.sow_kv,
            router_dtype=cfg.storage_dtype())
        with jax.named_scope("moe.shared"):
            shared = family.plain_swiglu(h, cfg.n_shared_experts * F,
                                         family.SHARED_SWIGLU, cfg)
        return x + routed.reshape(x.shape) + shared


class DeepseekV3(family.ServedDecoder):
    cfg: DeepseekV3Config

    def block(self, i: int) -> DeepseekV3Block:
        return DeepseekV3Block(self.cfg, i >= self.cfg.first_k_dense_replace,
                               name=f"layer_{i}")


make_model = family.make_model(DeepseekV3, PRESETS)
