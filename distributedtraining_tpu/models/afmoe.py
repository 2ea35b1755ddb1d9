"""AFMoE-family decoder (``model_type: afmoe``): windowed rotary attention
layers beside global attention layers with no position term, every one
gated and with a norm over each head of q and k; two leading dense FFNs,
then routed + one shared SwiGLU expert; four norms a block; a muP
multiplier on the lookup. On the serving path.

The configuration carries the published keys under their published names
(arcee-ai/Trinity-Mini's ``config.json`` is the row the presets are cut
from). No bias anywhere. The readings marked (assumed) are inferences from
a key's name and the family's lineage; each stands in the benchmark
configuration's ``assumed`` and in benchmarks/reference/afmoe.py, which
takes the SAME reading.

* Norm ``N(x; w)``: ``x rsqrt(mean(x^2) + rms_norm_eps) w``, float32.
* Lookup: ``h0 = E[ids] * sqrt(hidden_size)`` (``mup_enabled: true``;
  assumed: the forward pass reads muP nowhere else).
* Block, FOUR norms: ``x <- x + N_post_attn(Attn(N_in(x)))``, then ``x <- x
  + N_post_mlp(FFN(N_pre_mlp(x)))``.
* Attention, every layer: ``q = N_d(reshape(u W_q))``, ``k = N_d(reshape(u
  W_k))`` a head (one gain of ``head_dim`` for all heads), ``v = u W_v``;
  where ``layer_types[i] == "sliding_attention"`` ONLY: rotary on q and k
  (``rope_theta``, the whole head, Llama's halves, after the norm;
  assumed) and a window, position ``i`` sees ``j`` with ``i -
  sliding_window < j <= i`` (assumed: the window counts the token itself);
  in ``"full_attention"`` layers NO position term and plain causal; scale
  ``head_dim^-0.5``; ``W_o [softmax(q k^T) v * sigmoid(u W_gate)]``, the
  gate elementwise over the heads' concatenated values, from the block's
  normed input.
* FFN, layers ``< num_dense_layers``: SwiGLU of ``intermediate_size``.
  Every other: ``s = sigmoid(u W_r)`` in float32 over ALL ``num_experts``;
  the ``num_experts_per_tok`` largest of ``s + b`` (``expert_bias``, a
  buffer: it moves the choice, never the weights); weights ``s_chosen /
  (sum + 1e-20)`` (``route_norm``) times ``route_scale``; SwiGLU experts of
  ``moe_intermediate_size``; plus ``num_shared_experts`` shared SwiGLU
  expert on every token. ``n_group = topk_group = 1``: no group limit
  (another value is refused). ``experts_held = (first, count)`` says which
  of the router's experts THIS chip holds, as in the sibling families.
* Final norm, untied head, float32 logits.

What each layer caches is stated per layer (``layer_caches``): ``"kv"``
the paged K/V pair for ever in a full layer, ``"kv_window"`` the same pair
only while the token is one of the ``sliding_window`` newest in a sliding
one; engine/kv_pool.py keeps a page group for each and gives a window
layer's pages back behind the window.

Serving only: the fleet plane does not know this family, and the flash
kernels have no window (ROADMAP M4).
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import family

SLIDING, FULL = "sliding_attention", "full_attention"


def _layer_types(n: int, every: int) -> tuple[str, ...]:
    return tuple(FULL if (i + 1) % every == 0 else SLIDING
                 for i in range(n))


@dataclasses.dataclass(frozen=True)
class AfmoeConfig(family.FamilyConfig):
    # the published keys, under their published names
    vocab_size: int = 200192
    max_position_embeddings: int = 131072
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    hidden_act: str = "silu"
    global_attn_every_n_layers: int = 4
    layer_types: tuple[str, ...] = _layer_types(32, 4)
    sliding_window: int = 2048
    rope_theta: float = 10000
    rope_scaling: None = None
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    num_expert_groups: int = 1
    num_limited_groups: int = 1
    n_group: int = 1
    topk_group: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    load_balance_coeff: float = 0.001    # training's; read by no layer
    use_grouped_mm: bool = True          # an implementation switch upstream
    tie_word_embeddings: bool = False
    # the program's own, beside family.FamilyConfig's
    experts_held: tuple[int, int] = (0, 128)   # (first, count) on this chip
    attention_impl: str = "dense"

    def __post_init__(self):
        self.refuse({
            "layer_types": (len(self.layer_types) != self.num_hidden_layers
                            or set(self.layer_types) - {SLIDING, FULL}),
            "num_dense_layers": not (0 <= self.num_dense_layers
                                     <= self.num_hidden_layers),
            "hidden_act": self.hidden_act != "silu",
            "rope_scaling": self.rope_scaling is not None,
            "mup_enabled": not self.mup_enabled,
            "score_func": self.score_func != "sigmoid",
            "n_group": self.n_group != 1,
            "topk_group": self.topk_group != 1,
            "num_expert_groups": self.num_expert_groups != 1,
            "num_limited_groups": self.num_limited_groups != 1,
            "num_shared_experts": self.num_shared_experts != 1,
            "num_key_value_heads": (self.num_attention_heads
                                    % self.num_key_value_heads != 0),
            "sliding_window": self.sliding_window < 1,
            "attention_impl": self.attention_impl not in ("dense",
                                                          "blockwise"),
            "experts_held": family.held_outside(self.experts_held,
                                                self.num_experts),
            "tie_word_embeddings": self.tie_word_embeddings,
            "scan_blocks": self.scan_blocks,
        }, "this block writes one reading of each key: see the module's "
           "docstring")

    @property
    def layer_caches(self) -> tuple[str, ...]:
        """What each layer keeps for a sequence (engine/kv_pool.py):
        ``"kv"`` a K/V pair of heads a token for ever in a full layer,
        ``"kv_window"`` the pair of the ``sliding_window`` newest tokens in
        a sliding one."""
        return tuple("kv" if t == FULL else "kv_window"
                     for t in self.layer_types)

    # the names ops/moe.route's caller reads (family.routed_ffn)
    @property
    def routed_scaling_factor(self) -> float:
        return self.route_scale

    @property
    def norm_topk_prob(self) -> bool:
        return self.route_norm

    is_buffer = staticmethod(lambda path: path[-1] == "expert_bias")

    # cast before every use: the ``nn.Dense`` kernels, the experts' two
    # stacks, the head; the lookup's rows straight after the gather. Not a
    # norm's gain, the router or its selection bias (float32 scores)
    cast_first = ("kernel", "experts_gate_up", "experts_down", "lm_head",
                  "embed_tokens")


PRESETS: dict[str, AfmoeConfig] = {
    # the published sizes: 26B parameters, never built on one chip
    "trinity-mini": AfmoeConfig(),
    # stage 1 of 8 pipeline stages at the published widths: published
    # layer 0 (sliding, dense: the two leading dense layers count once) and
    # layers 4-7 (sliding x 3, full: one whole period), every expert and
    # the whole vocabulary, the final norm and the head held here so that
    # it yields logits. benchmarks/configs/trinity-mini-l5.json
    "trinity-mini-l5": AfmoeConfig(
        num_hidden_layers=5, num_dense_layers=1,
        layer_types=(SLIDING,) * 4 + (FULL,)),
    # the same five layers at toy widths, float32, for the CPU: a window
    # of 8 so that a test's context passes it many times over
    "tiny-trinity": AfmoeConfig(
        vocab_size=512, max_position_embeddings=512, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=5, num_dense_layers=1,
        layer_types=(SLIDING,) * 4 + (FULL,), sliding_window=8,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=8, num_experts_per_tok=2, experts_held=(0, 8),
        param_dtype="float32", dtype="float32"),
}


def output_gate(z):
    """The attention's output gate, float32: ``sigmoid(z)``, elementwise."""
    return jax.nn.sigmoid(z.astype(jnp.float32))


class AfmoeBlock(nn.Module):
    cfg: AfmoeConfig
    sliding: bool
    routed: bool

    @nn.compact
    def __call__(self, x, step: family.Step):
        cfg = self.cfg
        h = cfg.norm("input_layernorm")(x)
        with jax.named_scope("afmoe.attn.window" if self.sliding
                             else "afmoe.attn.full"):
            y = family.grouped_query_attention(
                self, h, step, cfg, cfg.attention_impl, output_gate,
                qk_norm=True,
                rope_theta=cfg.rope_theta if self.sliding else None,
                window=cfg.sliding_window if self.sliding else None)
        x = x + cfg.norm("post_attention_layernorm")(y)
        h = cfg.norm("pre_mlp_layernorm")(x)
        if self.routed:
            with jax.named_scope("afmoe.moe"):
                y = self._experts(h, step)
        else:
            with jax.named_scope("afmoe.mlp"):
                y = family.swiglu(h, cfg.intermediate_size,
                                  ("gate_proj", "up_proj", "down_proj"), cfg)
        return x + cfg.norm("post_mlp_layernorm")(y)

    def _experts(self, h, step):
        cfg = self.cfg
        F = cfg.moe_intermediate_size
        routed, _ = family.routed_ffn(
            self, h, cfg, experts=cfg.num_experts, width=2 * F,
            live=step.live, sow=step.sow_kv, bias="expert_bias")
        with jax.named_scope("moe.shared"):
            shared = family.swiglu(h, cfg.num_shared_experts * F,
                                   family.SHARED_SWIGLU, cfg)
        return routed.reshape(h.shape) + shared


class Afmoe(family.ServedDecoder):
    cfg: AfmoeConfig

    def embed(self, table, input_ids):
        """``mup_enabled``: the lookup's rows times ``sqrt(hidden_size)``,
        rounded once."""
        with jax.named_scope("afmoe.embed"):
            rows = family.embed_lookup(table, input_ids).astype(jnp.float32)
            return (rows * math.sqrt(self.cfg.hidden_size)).astype(
                self.cfg.compute_dtype())

    def head(self, x, table):
        with jax.named_scope("afmoe.head"):
            return family.logits(x, table, self.cfg)

    def block(self, i: int) -> AfmoeBlock:
        cfg = self.cfg
        return AfmoeBlock(cfg, cfg.layer_types[i] == SLIDING,
                          i >= cfg.num_dense_layers, name=f"layer_{i}")


make_model = family.make_model(Afmoe, PRESETS)
