"""Solar-Open2-family decoder (``model_type: solar_open2``): per-channel
gated delta-rule layers (Kimi Delta Attention) beside gated grouped-query
attention with no position term, every FFN routed + shared experts of which
a chip holds its share, on the serving path.

The configuration carries the published keys under their published names
(upstage/Solar-Open2-250B's ``config.json`` is the row the presets are cut
from). No bias anywhere. The readings marked (assumed) are inferences from
a key's name and the family's lineage; each stands in the benchmark
configuration's ``assumed`` and in benchmarks/reference/solar_open2.py,
which takes the SAME reading.

* Norm ``N(x; w)``: ``x rsqrt(mean(x^2) + rms_norm_eps) w``, float32.
* Block: ``x <- x + Mixer(N(x))``, then ``x <- x + FFN(N(x))``.
* Delta-rule mixer (every layer not in ``gqa_layers``;
  ``linear_attn_config``: ``num_heads`` heads of ``head_dim``, ``num_kv_heads``
  null = as many key heads, ``short_conv_kernel_size`` taps;
  ops/delta_rule.py with a decay a key CHANNEL): ``[q~ | k~ | v~] = u
  W_qkv``; ``(q~, k~, v~) <- silu(causal depthwise conv1d(.))``, no bias,
  one over each stream (held as one over the three laid side by side);
  ``q = l2norm(q~) dk^-0.5``, ``k = l2norm(k~)``; ``g = -exp(A_log_h)
  softplus(W_f2 (W_f1 u) + dt_bias)``, ``dk`` log-decays a head
  (``kda_use_full_proj: false``: the projection goes through a rank of
  ``kda_low_rank`` (assumed: the head width)); ``beta = 2 sigmoid(W_b u)``
  a head (``kda_allow_neg_eigval: true``: the factor 2); the rule; the
  output ``rmsnorm(o; w) sigmoid(W_g2 (W_g1 u))`` a head (assumed:
  sigmoid, elementwise, the same low rank), then ``W_o``. What the layer
  keeps for a sequence is NOT per token: one float32 state ``[heads, dk,
  dv]`` and the last ``conv - 1`` rows of ``[q~ | k~ | v~]`` before the
  convolution (``ssm_state_shape``, ``ssm_tail_shape``).
* Attention (layers in ``gqa_layers``; ``use_rope: false``,
  ``use_gqa_gate: true``): ``num_attention_heads`` query and
  ``num_key_value_heads`` K/V heads of ``head_dim``, scale
  ``head_dim^-0.5``, causal, NO position term (``position_ids`` is taken
  and not read); ``W_o [softmax(q k^T) v * sigmoid(W_g u)]``, the gate
  elementwise over the heads' concatenated values (assumed). Caches one
  K/V pair of heads a token, in pages.
* FFN, every layer (``first_k_dense_replace`` 0; ``intermediate_size`` is
  read by no layer): sigmoid router with a selection bias over ALL
  ``n_routed_experts`` (assumed: the lineage's ``noaux_tc``; the config
  names no scoring function), ``num_experts_per_tok`` chosen, normalised,
  scaled; experts and the one shared expert SwiGLU of
  ``moe_intermediate_size``. ``experts_held = (first, count)`` says which of
  the router's experts THIS chip holds: the layer routes over all of them
  and computes the rows routed to its own; the rest is another chip's and
  is left out.

What each layer caches is stated per layer (``layer_caches``): ``"ssm"`` a
per-slot state with its convolution tail, ``"kv"`` the paged K/V pair;
engine/kv_pool.py builds both from it and from the shapes stated here. A
prefill may CONTINUE: ``ssm_init`` hands the delta-rule layers the state
and tail an earlier part of the same sequence left (a prefix-cache
snapshot, or the chunk before this one), and ``kv_pages`` the attention
layers' pages of it.

Serving only: no backward pass is written for the chunked delta rule, and
the fleet plane does not know this family (ROADMAP M2, M5).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import delta_rule
from . import family
from .family import dense

_LINEAR = (("head_dim", 128), ("num_heads", 64), ("num_kv_heads", None),
           ("short_conv_kernel_size", 4))


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config(family.FamilyConfig):
    # the published keys, under their published names
    vocab_size: int = 196608
    max_position_embeddings: int = 1048576
    hidden_size: int = 4096
    intermediate_size: int = 10240       # read by no layer
    moe_intermediate_size: int = 1280
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    n_shared_experts: int = 1
    n_routed_experts: int = 320
    routed_scaling_factor: float = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000
    partial_rotary_factor: float = 1
    use_rope: bool = False
    gqa_interval: int = 3
    gqa_layers: tuple[int, ...] = tuple(range(0, 48, 4))
    use_gqa_gate: bool = True
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    linear_attn_config: tuple = _LINEAR  # the published group, as pairs
    tie_word_embeddings: bool = False
    # the program's own, beside family.FamilyConfig's
    experts_held: tuple[int, int] = (0, 320)   # (first, count) on this chip
    kda_low_rank: int = 128
    chunk_size: int = delta_rule.CHUNK
    attention_impl: str = "dense"

    def __post_init__(self):
        linear = dict(self.linear_attn_config)
        self.refuse({
            "gqa_layers": not all(0 <= i < self.num_hidden_layers
                                  for i in self.gqa_layers),
            "first_k_dense_replace": self.first_k_dense_replace != 0,
            "use_rope": self.use_rope,
            "use_gqa_gate": not self.use_gqa_gate,
            "kda_use_full_proj": self.kda_use_full_proj,
            "kda_allow_neg_eigval": not self.kda_allow_neg_eigval,
            "linear_attn_config.num_kv_heads":
                linear["num_kv_heads"] not in (None, linear["num_heads"]),
            "num_key_value_heads": (self.num_attention_heads
                                    % self.num_key_value_heads != 0),
            "n_shared_experts": self.n_shared_experts != 1,
            "experts_held": family.held_outside(self.experts_held,
                                                self.n_routed_experts),
            "tie_word_embeddings": self.tie_word_embeddings,
            "scan_blocks": self.scan_blocks,
        }, "this block writes one reading of each key: see the module's "
           "docstring")

    @property
    def layer_caches(self) -> tuple[str, ...]:
        """What each layer keeps for a sequence (engine/kv_pool.py):
        ``"kv"`` a K/V pair of heads a TOKEN in the attention layers,
        ``"ssm"`` a fixed-size state a SLOT in every other."""
        return tuple("kv" if i in self.gqa_layers else "ssm"
                     for i in range(self.num_hidden_layers))

    # what a per-slot layer keeps, under the names kv_pool.make_state_pool
    # reads, and the name its gauge and counter carry (serve.kda.*)
    state_name = "kda"

    @property
    def ssm_state_shape(self) -> tuple[int, int, int]:
        linear = dict(self.linear_attn_config)
        return linear["num_heads"], linear["head_dim"], linear["head_dim"]

    @property
    def conv_dim(self) -> int:
        heads, dk, dv = self.ssm_state_shape
        return heads * (2 * dk + dv)

    @property
    def ssm_tail_shape(self) -> tuple[int, int]:
        return (dict(self.linear_attn_config)["short_conv_kernel_size"] - 1,
                self.conv_dim)

    # cast before every use: the ``nn.Dense`` kernels, the experts' two
    # stacks, the head; the lookup's rows straight after the gather. Not
    # ``A_log``, ``dt_bias``, the convolution (they enter the float32
    # recurrence), a norm's gain, the router or its selection bias (float32
    # scores): those leaves are float32 in the tree and stay so
    cast_first = ("kernel", "experts_gate_up", "experts_down", "lm_head",
                  "embed_tokens")


def _linear(**changed) -> tuple:
    return tuple(sorted(dict(_LINEAR, **changed).items()))


PRESETS: dict[str, SolarOpen2Config] = {
    # the published sizes: 250B parameters, never built on one chip
    "solar-open2-250b": SolarOpen2Config(),
    # one chip's share of a stated deployment: published layers 0-3
    # (attention, delta rule x 3: one whole period), with experts 0..39 of
    # the 320 that eight chips share and ids 0..24,575 of the vocabulary;
    # the final norm and the head too (stage 1 of a pipeline, head held
    # here so that it yields logits).
    # benchmarks/configs/solar-open2-250b-l4-e40-v24k.json
    "solar-open2-250b-l4-e40-v24k": SolarOpen2Config(
        num_hidden_layers=4, gqa_layers=(0,), vocab_size=24576,
        experts_held=(0, 40)),
    # the same four layers at toy widths, all 8 experts, float32, for the
    # CPU. The state stays [., 128, 128]: the decode kernel's tiles
    "tiny-solar": SolarOpen2Config(
        vocab_size=512, max_position_embeddings=512, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, n_routed_experts=8, num_experts_per_tok=3,
        gqa_layers=(0,), linear_attn_config=_linear(num_heads=2),
        experts_held=(0, 8), kda_low_rank=16, chunk_size=16,
        param_dtype="float32", dtype="float32"),
}


def output_gate(z):
    """A mixer's output gate, float32: ``sigmoid(z)``, elementwise. The
    delta-rule mixer's (through the low rank) and the attention's
    (``use_gqa_gate``)."""
    return jax.nn.sigmoid(z.astype(jnp.float32))


class SolarOpen2Block(nn.Module):
    cfg: SolarOpen2Config
    full_attention: bool

    @nn.compact
    def __call__(self, x, step: family.Step):
        cfg = self.cfg
        h = cfg.norm("mixer_norm")(x)
        if self.full_attention:
            with jax.named_scope("solar.gqa"):
                y = self._attention(h, step)
        else:
            with jax.named_scope("solar.kda"):
                y = self._delta(h, step)
        x = x + y
        with jax.named_scope("solar.moe_ffn"):
            return x + self._experts(cfg.norm("ffn_norm")(x), step)

    def _delta(self, u, step):
        cfg = self.cfg
        B, T, E = u.shape
        H, dk, dv = cfg.ssm_state_shape
        K, conv_dim = cfg.ssm_tail_shape[0] + 1, cfg.conv_dim
        r = cfg.kda_low_rank
        cdt, f32 = cfg.compute_dtype(), jnp.float32
        qkv = dense(conv_dim, "in_proj_qkv", ("embed", "mlp"), cfg)(u)
        conv_w = self.param("conv1d_weight", family.conv_init,
                            (K, conv_dim), f32)
        a_log = self.param("A_log", family.a_log_init, (H,), f32)
        dt_bias = self.param("dt_bias", family.dt_bias_init, (H * dk,), f32)
        f = dense(H * dk, "f_b_proj", (None, "mlp"), cfg)(
            dense(r, "f_a_proj", ("embed", None), cfg)(u))
        # dk log-decays a head, float32
        g = (-jnp.exp(a_log)[:, None]
             * jax.nn.softplus(f.astype(f32) + dt_bias
                               ).reshape(B, T, H, dk))
        beta = 2.0 * jax.nn.sigmoid(
            dense(H, "b_proj", ("embed", None), cfg)(u).astype(f32))
        gate = dense(H * dv, "g_b_proj", (None, "mlp"), cfg)(
            dense(r, "g_a_proj", ("embed", None), cfg)(u))

        def split(act):
            """silu(conv) -> q, k [.., H, dk] (q scaled, k of unit length,
            float32) and v [.., H, dv]."""
            act = jax.nn.silu(act)
            lead = act.shape[:-1]

            def unit(a):
                return a * jax.lax.rsqrt(
                    jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

            return (unit(act[..., :H * dk].reshape(*lead, H, dk))
                    * dk ** -0.5,
                    unit(act[..., H * dk:2 * H * dk].reshape(*lead, H, dk)),
                    act[..., 2 * H * dk:].reshape(*lead, H, dv))

        o = family.delta_rule_layer(self, qkv, conv_w, split, g, beta, step,
                                    cfg)
        w_o = self.param("o_norm", nn.initializers.ones_init(), (dv,), f32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.rms_norm_eps)
        o = o * w_o * output_gate(gate).reshape(B, T, H, dv)
        return dense(E, "out_proj", ("mlp", "embed"), cfg)(
            o.reshape(B, T, H * dv).astype(cdt))

    def _attention(self, h, step):
        return family.grouped_query_attention(
            self, h, step, self.cfg, self.cfg.attention_impl, output_gate)

    def _experts(self, h, step):
        cfg = self.cfg
        F = cfg.moe_intermediate_size
        routed, _ = family.routed_ffn(
            self, h, cfg, experts=cfg.n_routed_experts, width=2 * F,
            live=step.live, sow=step.sow_kv)
        with jax.named_scope("moe.shared"):
            shared = family.swiglu(h, cfg.n_shared_experts * F,
                                   family.SHARED_SWIGLU, cfg)
        return routed.reshape(h.shape) + shared


class SolarOpen2(family.ServedDecoder):
    """``position_ids`` is taken and not read: nothing here is
    positional."""
    cfg: SolarOpen2Config

    def block(self, i: int) -> SolarOpen2Block:
        return SolarOpen2Block(self.cfg, i in self.cfg.gqa_layers,
                               name=f"layer_{i}")


make_model = family.make_model(SolarOpen2, PRESETS)
