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
  function (``ops.mla_attention.latent_attention``, which
  models/gigachat3_5.py calls too; tests/test_deepseek_v3.py).
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
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import moe
from ..ops.embed import embed_lookup
from ..ops.mla_attention import latent_attention
from .gpt2 import pad_vocab
from .llama import RMSNorm, _dense, rotary_embedding


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
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
    # the program's own
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    logits_dtype: str = "float32"
    attention_impl: str = "dense"
    vocab_multiple: int = 128
    remat: bool = False
    scan_blocks: bool = False

    def __post_init__(self):
        unsupported = {
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
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"DeepseekV3Config: {', '.join(bad)} not "
                             "supported (this block takes the query from "
                             "one full-rank matrix, plain rotary "
                             "frequencies and one sigmoid-scored group; a "
                             "config that states q_lora_rank and "
                             "rope_scaling is models/gigachat3_5.py's)")

    @property
    def padded_vocab(self) -> int:
        return pad_vocab(self.vocab_size, self.vocab_multiple)

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def cache_row_widths(self) -> tuple[int, int]:
        """What one layer caches a token (engine/kv_pool.row_widths)."""
        return self.kv_lora_rank, self.qk_rope_head_dim

    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def storage_dtype(self):
        return jnp.dtype(self.param_dtype)

    def rounds_first(self, path: tuple[str, ...]) -> bool:
        """See ``GPT2Config.rounds_first``. Cast before every use: the
        ``nn.Dense`` kernels, ``kv_b_proj``, the experts' two stacks and
        the head; the lookup's rows straight after the gather. Not the
        router and its selection bias (float32 scores, ops/moe.route),
        not an RMSNorm's scale."""
        return path[-1] in _CAST_FIRST


_CAST_FIRST = ("kernel", "kv_b_proj", "experts_gate_up", "experts_down",
               "lm_head", "embed_tokens")

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


def _swiglu(h, width: int, names: tuple[str, str, str], cfg):
    gate = _dense(width, names[0], ("embed", "mlp"), cfg)(h)
    up = _dense(width, names[1], ("embed", "mlp"), cfg)(h)
    return _dense(cfg.hidden_size, names[2], ("mlp", "embed"), cfg)(
        nn.silu(gate) * up)


class DeepseekV3Block(nn.Module):
    cfg: DeepseekV3Config
    routed: bool

    @nn.compact
    def __call__(self, x, attention_mask, segment_ids, position_ids,
                 kv_lens=None, sow_kv=False, kv_pages=None,
                 page_tables=None, live=None):
        cfg = self.cfg
        B, T, E = x.shape
        H, C = cfg.num_attention_heads, cfg.kv_lora_rank
        Dn, Dr, Dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        cdt = cfg.compute_dtype()
        norm = functools.partial(_norm, cfg)

        h = norm("input_layernorm")(x)
        q = _dense(H * (Dn + Dr), "q_proj", ("embed", "qkv"), cfg)(h)
        q = q.reshape(B, T, H, Dn + Dr)
        q_nope, q_rope = q[..., :Dn], q[..., Dn:]
        kv_a = _dense(C + Dr, "kv_a_proj_with_mqa", ("embed", None), cfg)(h)
        c = norm("kv_a_layernorm")(kv_a[..., :C])
        q_rope = rotary_embedding(q_rope, position_ids, cfg.rope_theta,
                                  interleaved=cfg.rope_interleave)
        k_r = rotary_embedding(kv_a[..., None, C:], position_ids,
                               cfg.rope_theta,
                               interleaved=cfg.rope_interleave)[:, :, 0]
        if sow_kv:
            # the whole cache of this layer: the normed latent and the
            # one shared rotary key (kv_pool's pair: c first, k_r second)
            self.sow("intermediates", "kv_cache", (c, k_r))
        w_kv_b = self.param(
            "kv_b_proj",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         (None, "qkv")),
            (C, H * (Dn + Dv)), cfg.storage_dtype())
        w_kv_b = w_kv_b.astype(cdt).reshape(C, H, Dn + Dv)
        attn = latent_attention(
            q_nope, q_rope, c, k_r, w_kv_b, (Dn + Dr) ** -0.5,
            kv_pages=kv_pages, page_tables=page_tables, kv_lens=kv_lens,
            attention_mask=attention_mask, segment_ids=segment_ids,
            impl=cfg.attention_impl)
        x = x + _dense(E, "o_proj", ("qkv", "embed"), cfg)(
            attn.reshape(B, T, H * Dv))

        h = norm("post_attention_layernorm")(x)
        if not self.routed:
            return x + _swiglu(h, cfg.intermediate_size,
                               ("gate_proj", "up_proj", "down_proj"), cfg)
        G, F = cfg.n_routed_experts, cfg.moe_intermediate_size
        w_router = self.param(
            "router", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("embed", None)),
            (E, G), cfg.storage_dtype())
        # a buffer in the release: it moves the choice, never the weights
        bias = self.param("e_score_correction_bias",
                          nn.initializers.zeros_init(), (G,), jnp.float32)
        w_gate_up = self.param(
            "experts_gate_up", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), (None, "embed", "mlp")),
            (G, E, 2 * F), cfg.storage_dtype())
        w_down = self.param(
            "experts_down", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), (None, "mlp", "embed")),
            (G, F, E), cfg.storage_dtype())
        flat = h.reshape(B * T, E)
        choice, weights = moe.route(
            flat, w_router, bias, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.norm_topk_prob)
        routed, stats = moe.routed_experts(
            flat, choice, weights, w_gate_up.astype(cdt),
            w_down.astype(cdt),
            live=None if live is None else live.reshape(B * T))
        if sow_kv:
            self.sow("intermediates", "serve_stats", stats)
        with jax.named_scope("moe.shared"):
            shared = _swiglu(
                h, cfg.n_shared_experts * F,
                ("shared_gate_proj", "shared_up_proj", "shared_down_proj"),
                cfg)
        return x + routed.reshape(B, T, E) + shared


def _norm(cfg, name: str) -> RMSNorm:
    return RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name=name)


class DeepseekV3(nn.Module):
    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, input_ids, *, attention_mask=None, segment_ids=None,
                 position_ids=None, deterministic: bool = True,
                 return_hidden: bool = False, kv_lens=None,
                 sow_kv: bool = False, kv_pages=None, page_tables=None):
        """The serving hooks are gpt2.GPT2.__call__'s: ``sow_kv`` sows
        each layer's fresh cache rows, ``kv_pages``/``page_tables``/
        ``kv_lens`` attend over the paged cache."""
        cfg = self.cfg
        B, T = input_ids.shape
        wte = self.param(
            "embed_tokens",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         ("vocab", "embed")),
            (cfg.padded_vocab, cfg.hidden_size), cfg.storage_dtype())
        if position_ids is None:
            position_ids = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        # the rows a routed layer counts: not a prefill bucket's padding,
        # not a decode bucket's empty slots (a live slot holds >= 1 token)
        if attention_mask is not None:
            live = attention_mask.astype(bool)
        elif kv_lens is not None:
            live = jnp.broadcast_to(kv_lens[:, None] > 0, (B, T))
        else:
            live = None
        x = embed_lookup(wte, input_ids).astype(cfg.compute_dtype())
        for i in range(cfg.num_hidden_layers):
            x = DeepseekV3Block(cfg, i >= cfg.first_k_dense_replace,
                                name=f"layer_{i}")(
                x, attention_mask, segment_ids, position_ids, kv_lens,
                sow_kv, kv_pages[i] if kv_pages is not None else None,
                page_tables, live)
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


def make_model(preset_or_cfg) -> tuple[DeepseekV3, DeepseekV3Config]:
    cfg = (PRESETS[preset_or_cfg] if isinstance(preset_or_cfg, str)
           else preset_or_cfg)
    return DeepseekV3(cfg), cfg
