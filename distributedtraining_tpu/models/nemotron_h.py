"""Nemotron-H-family decoder (``model_type: nemotron_h``): Mamba-2 layers
beside attention and latent routed experts, on the serving path.

The configuration carries the published keys under their published names
(nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16's ``config.json`` is the
row the presets are cut from). A block is ``x <- x + mixer(RMSNorm(x))``
with exactly ONE mixer, named by the block's letter in
``hybrid_override_pattern``; after the last block ``norm_f`` and the
untied head. No bias anywhere but the convolution's.

* ``M``, Mamba-2 (ops/ssm.py). ``[z | xBC | dt] = in_proj(u)``;
  ``xBC <- silu(causal depthwise conv1d(xBC) + bias)`` split into ``x``
  (heads of ``mamba_head_dim``), ``B`` and ``C`` (``n_groups`` rows of
  ``ssm_state_size``; head ``h`` uses group ``h // (heads / groups)``);
  ``dt <- softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t + D
  x_t``; the gated group norm, gate first: ``RMSNorm_groups(y silu(z))``;
  ``out_proj``. What the layer keeps for a sequence is NOT per token: one
  float32 state ``[heads, head_dim, state]`` and the last ``conv_kernel -
  1`` rows of ``xBC`` before the convolution (``ssm_state_shape``,
  ``ssm_tail_shape``).
* ``*``, attention: grouped-query (``num_key_value_heads`` K/V heads),
  causal softmax scaled by ``head_dim ** -0.5``, NO rotary and no other
  position term (the family uses none: ``rope_theta`` and
  ``partial_rotary_factor`` stand in the published config and are not
  read). Caches a K/V pair of heads a token, as GPT-2 and Llama do.
* ``E``, experts in a latent (ops/moe.py): sigmoid router with a selection
  bias over ALL ``n_routed_experts``, ``num_experts_per_tok`` chosen,
  normalised, scaled; ``x_l = x W_in`` (hidden -> ``moe_latent_size``);
  expert ``e`` is ``relu(x_l U_e)^2 V_e`` in the latent; ``(sum_e w_e
  E_e(x_l)) W_out`` back to the hidden width; plus one shared expert on
  ``x`` itself, ``relu(x U_s)^2 V_s``. Caches nothing. ``experts_held =
  (first, count)`` says which of the router's experts THIS chip holds
  (expert parallelism): the layer routes over all of them and computes the
  rows routed to its own; the rest is another chip's and is left out.

What each layer caches is stated per layer (``layer_caches``), and
engine/kv_pool.py builds the two kinds of cache from it. The published
multi-token-prediction module (``num_nextn_predict_layers``) is a
drafter's: it adds nothing to these layers' logits and is not built.

Serving only: no backward pass is written for the scan, and the fleet
plane does not know this family (ROADMAP M2, M5).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import ssm
from . import family
from .family import dense

_PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(family.FamilyConfig):
    # the published keys, under their published names
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = _PUBLISHED_PATTERN
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attention_bias: bool = False
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    expand: int = 2
    mamba_hidden_act: str = "silu"
    mamba_proj_bias: bool = False
    use_conv_bias: bool = True
    intermediate_size: int = 2688
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_routed_experts: int = 512
    n_shared_experts: int = 1
    num_experts_per_tok: int = 22
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    mlp_hidden_act: str = "relu2"
    mlp_bias: bool = False
    use_bias: bool = False
    norm_eps: float = 1e-5
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 1
    # the program's own, beside family.FamilyConfig's
    experts_held: tuple[int, int] = (0, 512)   # (first, count) on this chip

    def __post_init__(self):
        self.refuse({
            "hybrid_override_pattern": (
                len(self.hybrid_override_pattern) != self.num_hidden_layers
                or set(self.hybrid_override_pattern) - set("M*E")),
            "n_group/topk_group": (self.n_group, self.topk_group) != (1, 1),
            "mlp_hidden_act": self.mlp_hidden_act != "relu2",
            "mamba_hidden_act": self.mamba_hidden_act != "silu",
            "bias": (self.attention_bias or self.mamba_proj_bias
                     or self.mlp_bias or self.use_bias
                     or not self.use_conv_bias),
            "expand": (self.mamba_num_heads * self.mamba_head_dim
                       != self.expand * self.hidden_size),
            "n_groups": self.mamba_num_heads % self.n_groups != 0,
            "n_shared_experts": self.n_shared_experts != 1,
            "experts_held": family.held_outside(self.experts_held,
                                                self.n_routed_experts),
            "tie_word_embeddings": self.tie_word_embeddings,
            "scan_blocks": self.scan_blocks,
        }, "models/nemotron_h.py writes the equations of the "
           "Nemotron-3-Super row only")

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def layer_caches(self) -> tuple[str | None, ...]:
        """What each layer keeps for a sequence (engine/kv_pool.py):
        ``"ssm"`` a fixed-size state a SLOT, ``"kv"`` a K/V pair of heads
        a TOKEN, None nothing."""
        return tuple({"M": "ssm", "*": "kv", "E": None}[c]
                     for c in self.hybrid_override_pattern)

    @property
    def ssm_state_shape(self) -> tuple[int, int, int]:
        return self.mamba_num_heads, self.mamba_head_dim, self.ssm_state_size

    @property
    def ssm_tail_shape(self) -> tuple[int, int]:
        return self.conv_kernel - 1, self.conv_dim

    # cast before every use: the ``nn.Dense`` kernels, the experts' two
    # stacks, the head; the lookup's rows straight after the gather. Not
    # ``A_log``, ``D``, ``dt_bias``, the convolution (they enter the float32
    # recurrence), a norm's gain, the router or its selection bias (float32
    # scores): those leaves are float32 in the tree and stay so
    cast_first = ("kernel", "experts_up", "experts_down", "lm_head",
                  "embed_tokens")

    def norm(self, name: str) -> nn.Module:
        return family.RMSNorm(self.norm_eps, "float32", name=name)


_TINY = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=11,
    hybrid_override_pattern="MEMEMEM*EME", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=8, mamba_head_dim=16,
    n_groups=2, ssm_state_size=128, intermediate_size=48,
    moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=96, n_routed_experts=8,
    num_experts_per_tok=3, max_position_embeddings=256,
    num_nextn_predict_layers=0, experts_held=(0, 8),
    param_dtype="float32", dtype="float32")

PRESETS: dict[str, NemotronHConfig] = {
    # the published sizes: 120.7B parameters, never built on one chip
    "nemotron-3-super-120b-a12b": NemotronHConfig(),
    # one chip's share of a stated deployment: the pattern's first period
    # (5 M, 5 E, 1 *: the published 40 : 40 : 8), with experts 0..127 of
    # the 512 that four chips share; the final norm and the head too
    # (stage 1 of an 8-stage pipeline, head held here so that it yields
    # logits). benchmarks/configs/nemotron-3-super-120b-a12b-l11-e128.json
    "nemotron-3-super-120b-a12b-l11-e128": NemotronHConfig(
        num_hidden_layers=11, hybrid_override_pattern="MEMEMEM*EME",
        num_nextn_predict_layers=0, experts_held=(0, 128)),
    # one period at toy widths, all 8 experts, float32, for the CPU. The
    # state width stays 128: the decode kernel's lane tile
    "tiny-nemotron-h": NemotronHConfig(**_TINY),
}


class NemotronHBlock(nn.Module):
    cfg: NemotronHConfig
    kind: str                   # the block's letter: M, * or E

    @nn.compact
    def __call__(self, x, step: family.Step):
        mixer = {"M": self._mamba, "*": self._attention,
                 "E": self._experts}[self.kind]
        return x + mixer(self.cfg.norm("norm")(x), step)

    def _mamba(self, u, step):
        cfg = self.cfg
        B, T, E = u.shape
        H, P, N = cfg.ssm_state_shape
        G, K, d_inner, conv_dim = (cfg.n_groups, cfg.conv_kernel,
                                   cfg.d_inner, cfg.conv_dim)
        cdt, f32 = cfg.compute_dtype(), jnp.float32
        zxbcdt = dense(2 * d_inner + 2 * G * N + H, "in_proj",
                        ("embed", "mlp"), cfg)(u)
        z = zxbcdt[..., :d_inner]
        xbc = zxbcdt[..., d_inner:d_inner + conv_dim]
        conv_w = self.param("conv1d_weight", family.conv_init,
                            (K, conv_dim), f32)
        conv_b = self.param("conv1d_bias", nn.initializers.zeros_init(),
                            (conv_dim,), f32)
        A = -jnp.exp(self.param("A_log", family.a_log_init, (H,), f32))
        D = self.param("D", nn.initializers.ones_init(), (H,), f32)
        dt_bias = self.param("dt_bias", family.dt_bias_init, (H,), f32)
        dt = jax.nn.softplus(zxbcdt[..., d_inner + conv_dim:].astype(f32)
                             + dt_bias)

        def split(act):
            """silu(conv) -> x [.., H, P], B and C [.., G, N]."""
            act = jax.nn.silu(act).astype(cdt)
            lead = act.shape[:-1]
            return (act[..., :d_inner].reshape(*lead, H, P),
                    act[..., d_inner:d_inner + G * N].reshape(*lead, G, N),
                    act[..., d_inner + G * N:].reshape(*lead, G, N))

        def prefill(conv, h0):
            xs, b, c = split(conv)
            return ssm.ssd_prefill(xs, dt, A, b, c, D, step.live_len, h0,
                                   chunk=cfg.chunk_size)

        def decode(conv, states):
            xs, b, c = split(conv)
            return ssm.ssm_decode_update(states, step.slots, xs, dt[:, 0], A,
                                         b, c, D)

        y = family.slot_state_layer(self, xbc, conv_w, conv_b, step, cfg,
                                    prefill, decode)
        # the gated norm, gate first, over n_groups groups of the width
        gated = (y.reshape(B, T, G, d_inner // G)
                 * jax.nn.silu(z.astype(f32)).reshape(B, T, G, -1))
        gain = self.param("mixer_norm", nn.initializers.ones_init(),
                          (d_inner,), f32)
        gated = gated * jax.lax.rsqrt(
            jnp.mean(gated * gated, axis=-1, keepdims=True) + cfg.norm_eps)
        y = (gated.reshape(B, T, d_inner) * gain).astype(cdt)
        return dense(E, "out_proj", ("mlp", "embed"), cfg)(y)

    def _attention(self, h, step):
        return family.grouped_query_attention(self, h, step, self.cfg,
                                              "dense")

    def _experts(self, h, step):
        cfg = self.cfg
        routed, _ = family.routed_ffn(
            self, h, cfg, experts=cfg.n_routed_experts,
            width=cfg.moe_intermediate_size, live=step.live,
            sow=step.sow_kv, first="experts_up", latent=cfg.moe_latent_size)
        with jax.named_scope("moe.shared"):
            shared = family.relu2(
                h, cfg.moe_shared_expert_intermediate_size,
                ("shared_up_proj", "shared_down_proj"), cfg)
        return routed.reshape(h.shape) + shared


class NemotronH(family.ServedDecoder):
    """``kv_pages`` one pair for each ``*`` layer, ``ssm_pools`` /
    ``ssm_init`` one for each ``M`` layer. ``position_ids`` is taken and
    not read: nothing here is positional."""
    cfg: NemotronHConfig
    final_norm = "norm_f"

    def block(self, i: int) -> NemotronHBlock:
        return NemotronHBlock(self.cfg, self.cfg.hybrid_override_pattern[i],
                              name=f"layer_{i}")


make_model = family.make_model(NemotronH, PRESETS)
