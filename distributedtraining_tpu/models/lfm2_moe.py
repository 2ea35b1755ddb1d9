"""LFM2-MoE-family decoder (``model_type: lfm2_moe``): gated short
convolutions beside grouped-query attention, routed experts of which a chip
holds its share, on the TRAINING path.

The configuration carries the published keys under their published names
(LiquidAI/LFM2-8B-A1B's ``config.json`` is the row the presets are cut
from). No bias anywhere; ``RMSNorm(x) = x rsqrt(mean(x^2) + norm_eps) g``.
A layer is ``x <- x + Mixer(RMSNorm_operator(x))``, then ``x <- x +
FFN(RMSNorm_ffn(x))``; after the last layer one more RMSNorm and the logits
through the TIED embedding, float32.

* mixer ``conv`` (``layer_types[i]``): ``[B | C | u] = h W_in`` (three
  thirds, in that order); ``v = B * u``; the depthwise causal convolution
  of ``conv_L_cache`` taps over ``v`` (ops/ssm.causal_conv1d: a tap whose
  source lies before the document's start, or in another segment of a
  packed row, adds zero); ``out = (C * conv) W_out``. The gates are LINEAR:
  no activation anywhere in the mixer.
* mixer ``full_attention``: grouped-query (``num_key_value_heads`` K/V
  heads, each serving ``heads / kv_heads`` query heads), an RMSNorm over
  each head's ``head_dim`` on q and on k (one gain of ``head_dim`` each)
  BEFORE the rotation, rotary halves (not interleaved pairs) at
  ``rope_theta``, causal softmax scaled by ``head_dim ** -0.5`` within a
  document. On a TPU the Pallas flash kernel, K/V heads repeated to the
  query heads (ops/flash_attention.py says why not the grouped kernel).
* FFN, the first ``num_dense_layers`` layers: SwiGLU ``W2(silu(W1 h) * W3
  h)`` of ``intermediate_size``.
* FFN, the other layers (ops/moe.py): sigmoid router in float32 over ALL
  ``num_experts``, the choice made on ``s + expert_bias``, the chosen
  scores normalised (``+ 1e-6`` under the sum, the release's) and scaled;
  SwiGLU experts of ``moe_intermediate_size``; no shared expert.
  ``experts_held = (first, count)``: this chip holds that slice of every
  routed layer (expert parallelism); the router still scores all
  ``num_experts``, a row routed to another chip's expert adds nothing
  here, forward and backward, and that partial sum goes on.
  ``expert_bias`` is a BUFFER (``is_buffer``): it moves the choice and
  never the weights, its gradient is zero, the config publishes neither a
  balancing loss nor an update rule for it, so the optimizer leaves it as
  the base has it and its delta is empty.

``vocab_size`` is the number of ids this chip holds, ``vocab_held`` says
which of the published vocabulary they are: a sliced vocabulary is a
smaller vocabulary (lookup, logits and loss are over the slice).

Training only: the family states no cache, so ``GenerationEngine`` refuses
it (``layer_caches`` names what a ``conv`` layer would keep, a
convolution tail a slot, which engine/kv_pool.py has no pool for).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import flash_attention, ssm
from ..ops.attention import causal_attention, remat_policy
from ..ops.embed import embed_lookup
from . import family
from .family import dense, rotary_embedding

_PUBLISHED_LAYERS = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))
# what a step's routed layers count, summed over layers (ops/moe.py's
# name: the registry's, under which it leaves the step and MinerLoop
# counts it when a sink is on)
TRAIN_COUNTERS = {
    "moe_rows": "train.moe.rows",
    "moe_rows_elsewhere": "train.moe.rows_elsewhere",
    "moe_rows_fullest": "train.moe.rows_fullest_expert",
    "moe_experts_touched": "train.moe.experts_touched",
    "moe_rows_past_prefix": "train.moe.rows_past_prefix",
    "moe_layers_past_prefix": "train.moe.layers_past_prefix",
}
# and its attention layers, where the flash kernels run the block pairs the
# rows' segment ids need (ops/flash_attention.block_pairs)
ATTN_COUNTERS = flash_attention.BLOCK_PAIR_COUNTERS


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig(family.FamilyConfig):
    # the published keys, under their published names
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    layer_types: tuple[str, ...] = _PUBLISHED_LAYERS
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    tie_word_embeddings: bool = True
    # the program's own, beside family.FamilyConfig's
    experts_held: tuple[int, int] = (0, 32)    # (first, count) on this chip
    vocab_held: tuple[int, int] = (0, 65536)   # (first, count) of the ids
    route_norm_eps: float = 1e-6
    param_dtype: str = "float32"
    remat: bool = True

    def __post_init__(self):
        self.refuse({
            "layer_types": (len(self.layer_types) != self.num_hidden_layers
                            or set(self.layer_types)
                            - {"conv", "full_attention"}),
            "num_dense_layers": not (
                0 <= self.num_dense_layers <= self.num_hidden_layers),
            "num_key_value_heads": (self.num_attention_heads
                                    % self.num_key_value_heads != 0),
            "head_dim": self.hidden_size % self.num_attention_heads != 0,
            "conv_bias": self.conv_bias,
            "use_expert_bias": not self.use_expert_bias,
            "experts_held": family.held_outside(self.experts_held,
                                                self.num_experts),
            "vocab_held": self.vocab_held[1] != self.vocab_size,
            "tie_word_embeddings": not self.tie_word_embeddings,
            "scan_blocks": self.scan_blocks,
        }, "models/lfm2_moe.py writes the equations of the LFM2-8B-A1B row "
           "only")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def layer_caches(self) -> tuple[str, ...]:
        """What each layer would keep for a served sequence: ``"kv"`` a
        K/V pair of heads a token, ``"conv"`` the last ``conv_L_cache - 1``
        rows of ``B * u`` a slot, which no pool of engine/kv_pool.py
        holds: the serve engine refuses the family on it."""
        return tuple("kv" if t == "full_attention" else "conv"
                     for t in self.layer_types)

    # cast before every use: the ``nn.Dense`` kernels, the experts' two
    # stacks, the lookup's rows (and the tied head). Not the convolution's
    # taps, a norm's gain, the router or ``expert_bias``: float32 in the
    # tree, float32 where they are used
    cast_first = ("kernel", "experts_in", "experts_down", "embed_tokens")

    def norm(self, name: str) -> nn.Module:
        return family.RMSNorm(self.norm_eps, "float32", name=name)

    def is_buffer(self, path: tuple[str, ...]) -> bool:
        """A leaf of the parameter tree that is no parameter: the
        optimizer neither moves nor decays it
        (engine/train.default_optimizer), so a step leaves it bit-equal to
        the base's and its delta is empty. Here the router's selection
        bias."""
        return path[-1] == "expert_bias"


_TINY = dict(
    vocab_size=512, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=5,
    layer_types=("conv", "full_attention", "conv", "conv", "conv"),
    num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
    num_experts=8, num_experts_per_tok=2, max_position_embeddings=256,
    experts_held=(0, 8), vocab_held=(0, 512), param_dtype="float32",
    dtype="float32", remat=False)

PRESETS: dict[str, Lfm2MoeConfig] = {
    # the published sizes: 8.34B parameters, never built on one chip
    "lfm2-8b-a1b": Lfm2MoeConfig(),
    # one chip's share of a stated deployment: published layer 0 (conv,
    # dense) and layers 2-5 (full_attention, conv, conv, conv: one whole
    # period, 3 : 1 = the published 18 : 6), with experts 0..7 of the 32
    # and ids 0..16,383 of the 65,536 that four chips share; the final norm
    # and the tied head too (stage 1 of a pipeline, so that it yields a
    # loss). benchmarks/configs/lfm2-8b-a1b-l5-e8-v16k.json
    "lfm2-8b-a1b-l5-e8-v16k": Lfm2MoeConfig(
        vocab_size=16384, vocab_held=(0, 16384), num_hidden_layers=5,
        layer_types=("conv", "full_attention", "conv", "conv", "conv"),
        num_dense_layers=1, experts_held=(0, 8)),
    # the same five layers at toy widths, all 8 experts, float32, for the
    # CPU
    "tiny-lfm2": Lfm2MoeConfig(**_TINY),
}


class Lfm2MoeBlock(nn.Module):
    cfg: Lfm2MoeConfig
    mixer: str                  # conv or full_attention
    routed: bool                # the FFN is the routed layer

    @nn.compact
    def __call__(self, x, attention_mask, segment_ids, position_ids):
        """-> (x, what the layer counted: the routed layer's rows, an
        attention layer's block pairs; {} where neither counts)."""
        # each half, norm to residual add, under its scope for the device
        # trace (docs/observability.md); `moe.route` / `moe.experts` open
        # inside `lfm2.moe_ffn`, which keeps what they leave: the norm, the
        # stacks' casts, the sort and the counters
        conv = self.mixer == "conv"
        with jax.named_scope("lfm2.conv" if conv else "lfm2.attn"):
            h = self.cfg.norm("operator_norm")(x)
            mix = self._conv if conv else self._attention
            out, stats = mix(h, attention_mask, segment_ids, position_ids)
            x = x + out
        with jax.named_scope("lfm2.moe_ffn" if self.routed
                             else "lfm2.dense_ffn"):
            h = self.cfg.norm("ffn_norm")(x)
            if not self.routed:
                return x + family.plain_swiglu(
                    h, self.cfg.intermediate_size, ("w1", "w3", "w2"),
                    self.cfg), stats
            out, rows = self._experts(h)
            return x + out, {**stats, **rows}

    def _conv(self, h, _mask, segment_ids, _pos):
        cfg = self.cfg
        E = cfg.hidden_size
        bcu = dense(3 * E, "in_proj", ("embed", "mlp"), cfg)(h)
        gate_b, gate_c, u = jnp.split(bcu, 3, axis=-1)
        taps = self.param("conv_weight", family.conv_init,
                          (cfg.conv_L_cache, E), jnp.float32)
        conv, _ = ssm.causal_conv1d(gate_b * u, taps, None, None,
                                    segment_ids)
        y = (gate_c.astype(jnp.float32) * conv).astype(cfg.compute_dtype())
        return dense(E, "out_proj", ("mlp", "embed"), cfg)(y), {}

    def _attention(self, h, attention_mask, segment_ids, position_ids):
        cfg = self.cfg
        B, T, E = h.shape
        Hq, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        q = dense(Hq * D, "q_proj", ("embed", "qkv"), cfg)(h)
        k = dense(Hkv * D, "k_proj", ("embed", "qkv"), cfg)(h)
        v = dense(Hkv * D, "v_proj", ("embed", "qkv"), cfg)(h)
        q = cfg.norm("q_layernorm")(q.reshape(B, T, Hq, D))
        k = cfg.norm("k_layernorm")(k.reshape(B, T, Hkv, D))
        v = v.reshape(B, T, Hkv, D)
        q = rotary_embedding(q, position_ids, cfg.rope_theta)
        k = rotary_embedding(k, position_ids, cfg.rope_theta)
        rep = Hq // Hkv
        attn = causal_attention(
            q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
            attention_mask=attention_mask, segment_ids=segment_ids,
            impl="flash")
        pairs = flash_attention.block_pairs(q, attention_mask, segment_ids)
        return dense(E, "out_proj", ("qkv", "embed"), cfg)(
            attn.reshape(B, T, Hq * D)), dict(zip(ATTN_COUNTERS, pairs or ()))

    def _experts(self, h):
        cfg = self.cfg
        # gate and up fused, gate columns first (ops/moe._experts_sorted);
        # ``expert_bias`` is a buffer (cfg.is_buffer)
        out, stats = family.routed_ffn(
            self, h, cfg, experts=cfg.num_experts,
            width=2 * cfg.moe_intermediate_size, bias="expert_bias",
            first="experts_in", router_experts=cfg.num_experts,
            count_fullest=True)
        return out.reshape(h.shape), {name: stats[k]
                                      for k, name in TRAIN_COUNTERS.items()}


class Lfm2Moe(family.Decoder):
    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, input_ids, *, attention_mask=None, segment_ids=None,
                 position_ids=None, deterministic: bool = True,
                 return_hidden: bool = False):
        """Logits [B, T, padded_vocab] (or the normed hidden states). What
        the layers counted, summed over layers, is sown once under
        ``intermediates/train_counters`` for a caller that asks for the
        collection (engine/train.py)."""
        del deterministic
        cfg = self.cfg
        B, T = input_ids.shape
        wte = family.embed_table(self, cfg)
        position_ids = family.default_positions(position_ids, B, T)
        with jax.named_scope("lfm2.embed"):
            x = embed_lookup(wte, input_ids).astype(cfg.compute_dtype())
        block = Lfm2MoeBlock
        if cfg.remat:
            block = nn.remat(Lfm2MoeBlock, policy=remat_policy())
        counted: dict = {}
        for i, mixer in enumerate(cfg.layer_types):
            x, stats = block(cfg, mixer, i >= cfg.num_dense_layers,
                             name=f"layer_{i}")(
                x, attention_mask, segment_ids, position_ids)
            for key, val in stats.items():
                counted[key] = counted[key] + val if key in counted else val
        if counted:
            self.sow("intermediates", "train_counters", counted)
        with jax.named_scope("lfm2.head"):
            x = cfg.norm("norm_f")(x)
            if return_hidden:
                return x
            return family.logits(x, wte, cfg)


make_model = family.make_model(Lfm2Moe, PRESETS)
