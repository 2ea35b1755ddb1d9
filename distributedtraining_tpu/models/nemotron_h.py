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
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import moe, ssm
from ..ops.attention import causal_attention
from ..ops.embed import embed_lookup
from ..ops.paged_attention import paged_attention
from .gpt2 import pad_vocab
from .llama import RMSNorm, _dense

_PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
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
    # the program's own
    experts_held: tuple[int, int] = (0, 512)   # (first, count) on this chip
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    logits_dtype: str = "float32"
    vocab_multiple: int = 128
    remat: bool = False
    scan_blocks: bool = False

    def __post_init__(self):
        first, count = self.experts_held
        unsupported = {
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
            "experts_held": not (0 <= first and count >= 1
                                 and first + count <= self.n_routed_experts),
            "tie_word_embeddings": self.tie_word_embeddings,
            "scan_blocks": self.scan_blocks,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"NemotronHConfig: {', '.join(bad)} not "
                             "supported (models/nemotron_h.py writes the "
                             "equations of the Nemotron-3-Super row only)")

    @property
    def padded_vocab(self) -> int:
        return pad_vocab(self.vocab_size, self.vocab_multiple)

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    # the K/V geometry of the attention layers, under the names
    # engine/kv_pool.row_widths reads
    @property
    def n_head(self) -> int:
        return self.num_attention_heads

    @property
    def n_kv_head(self) -> int:
        return self.num_key_value_heads

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

    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def storage_dtype(self):
        return jnp.dtype(self.param_dtype)

    def rounds_first(self, path: tuple[str, ...]) -> bool:
        """See ``GPT2Config.rounds_first``. Cast before every use: the
        ``nn.Dense`` kernels, the experts' two stacks, the head; the
        lookup's rows straight after the gather. Not ``A_log``, ``D``,
        ``dt_bias``, the convolution (they enter the float32 recurrence),
        a norm's gain, the router or its selection bias (float32 scores):
        those leaves are float32 in the tree and stay so."""
        return path[-1] in _CAST_FIRST


_CAST_FIRST = ("kernel", "experts_up", "experts_down", "lm_head",
               "embed_tokens")

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


def _norm(cfg, name: str) -> RMSNorm:
    return RMSNorm(cfg.norm_eps, "float32", name=name)


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


def _conv_init(key, shape, dtype):
    """PyTorch's default for a fan-in of K: U(-1/sqrt(K), 1/sqrt(K))."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _dt_bias_init(key, shape, dtype):
    """The inverse softplus of a step drawn log-uniformly from
    [0.001, 0.1] (the family's ``time_step_min`` / ``time_step_max``)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _relu2(h, width: int, names: tuple[str, str], cfg):
    up = _dense(width, names[0], ("embed", "mlp"), cfg)(h)
    act = jnp.square(nn.relu(up.astype(jnp.float32))).astype(up.dtype)
    return _dense(cfg.hidden_size, names[1], ("mlp", "embed"), cfg)(act)


class NemotronHBlock(nn.Module):
    cfg: NemotronHConfig
    kind: str                   # the block's letter: M, * or E

    @nn.compact
    def __call__(self, x, attention_mask, segment_ids, live, live_len,
                 kv_lens=None, sow_kv=False, kv_pages=None,
                 page_tables=None, ssm_pools=None, slots=None,
                 ssm_init=None):
        h = _norm(self.cfg, "norm")(x)
        mixer = {"M": self._mamba, "*": self._attention,
                 "E": self._experts}[self.kind]
        return x + mixer(h, attention_mask, segment_ids, live, live_len,
                         kv_lens, sow_kv, kv_pages, page_tables, ssm_pools,
                         slots, ssm_init)

    def _mamba(self, u, _mask, _seg, _live, live_len, kv_lens, sow_kv,
               _pages, _tables, ssm_pools, slots, ssm_init):
        cfg = self.cfg
        B, T, E = u.shape
        H, P, N = cfg.ssm_state_shape
        G, K, d_inner, conv_dim = (cfg.n_groups, cfg.conv_kernel,
                                   cfg.d_inner, cfg.conv_dim)
        cdt, f32 = cfg.compute_dtype(), jnp.float32
        zxbcdt = _dense(2 * d_inner + 2 * G * N + H, "in_proj",
                        ("embed", "mlp"), cfg)(u)
        z = zxbcdt[..., :d_inner]
        xbc = zxbcdt[..., d_inner:d_inner + conv_dim]
        conv_w = self.param("conv1d_weight", _conv_init, (K, conv_dim), f32)
        conv_b = self.param("conv1d_bias", nn.initializers.zeros_init(),
                            (conv_dim,), f32)
        A = -jnp.exp(self.param("A_log", _a_log_init, (H,), f32))
        D = self.param("D", nn.initializers.ones_init(), (H,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,), f32)
        dt = jax.nn.softplus(zxbcdt[..., d_inner + conv_dim:].astype(f32)
                             + dt_bias)

        def split(act):
            """silu(conv) -> x [.., H, P], B and C [.., G, N]."""
            act = jax.nn.silu(act).astype(cdt)
            lead = act.shape[:-1]
            return (act[..., :d_inner].reshape(*lead, H, P),
                    act[..., d_inner:d_inner + G * N].reshape(*lead, G, N),
                    act[..., d_inner + G * N:].reshape(*lead, G, N))

        if ssm_pools is None:
            with jax.named_scope("ssm.prefill"):
                # from zero, or from what the sequence's earlier part left
                h0, tail0 = (None, None) if ssm_init is None else ssm_init
                # `tail0` is named only when there is one: the fault
                # injectors of benchmarks/tools swap in a
                # `causal_conv1d` of the older signature
                conv, tail = ssm.causal_conv1d(
                    xbc, conv_w, conv_b, live_len,
                    **({} if tail0 is None else {"tail0": tail0}))
                xs, b, c = split(conv)
                y, state = ssm.ssd_prefill(xs, dt, A, b, c, D, live_len, h0,
                                           chunk=cfg.chunk_size)
            if sow_kv:
                # the whole of what this layer keeps for the sequence
                self.sow("intermediates", "ssm_cache", (state, tail))
        else:
            with jax.named_scope("ssm.decode"):
                states, tails = ssm_pools
                conv, tails = ssm.conv_decode_update(
                    tails, slots, xbc[:, 0], conv_w, conv_b)
                xs, b, c = split(conv)
                y, states = ssm.ssm_decode_update(
                    states, slots, xs, dt[:, 0], A, b, c, D)
                y = y[:, None]
            self.sow("intermediates", "ssm_cache", (states, tails))
            self.sow("intermediates", "serve_stats", {
                "ssm_slot_steps": jnp.sum(kv_lens > 0).astype(jnp.int32)})
        # the gated norm, gate first, over n_groups groups of the width
        gated = (y.reshape(B, T, G, d_inner // G)
                 * jax.nn.silu(z.astype(f32)).reshape(B, T, G, -1))
        gain = self.param("mixer_norm", nn.initializers.ones_init(),
                          (d_inner,), f32)
        gated = gated * jax.lax.rsqrt(
            jnp.mean(gated * gated, axis=-1, keepdims=True) + cfg.norm_eps)
        y = (gated.reshape(B, T, d_inner) * gain).astype(cdt)
        return _dense(E, "out_proj", ("mlp", "embed"), cfg)(y)

    def _attention(self, h, attention_mask, segment_ids, _live, _live_len,
                   kv_lens, sow_kv, kv_pages, page_tables, _pools, _slots,
                   _init):
        cfg = self.cfg
        B, T, E = h.shape
        Hq, Hkv, Dh = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        q = _dense(Hq * Dh, "q_proj", ("embed", "qkv"), cfg)(h)
        k = _dense(Hkv * Dh, "k_proj", ("embed", "qkv"), cfg)(h)
        v = _dense(Hkv * Dh, "v_proj", ("embed", "qkv"), cfg)(h)
        q = q.reshape(B, T, Hq, Dh)
        k, v = k.reshape(B, T, Hkv, Dh), v.reshape(B, T, Hkv, Dh)
        if sow_kv:
            self.sow("intermediates", "kv_cache", (k, v))
        if kv_pages is not None:
            attn = paged_attention(q, kv_pages[0], kv_pages[1], page_tables,
                                   kv_lens, k, v)
        else:
            rep = Hq // Hkv
            attn = causal_attention(
                q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                attention_mask=attention_mask, segment_ids=segment_ids,
                impl="dense")
        return _dense(E, "o_proj", ("qkv", "embed"), cfg)(
            attn.reshape(B, T, Hq * Dh))

    def _experts(self, h, _mask, _seg, live, _live_len, _lens, sow_kv,
                 _pages, _tables, _pools, _slots, _init):
        cfg = self.cfg
        B, T, E = h.shape
        cdt = cfg.compute_dtype()
        L, F = cfg.moe_latent_size, cfg.moe_intermediate_size
        held = cfg.experts_held
        normal = nn.initializers.normal(0.02)
        w_router = self.param("router", normal,
                              (E, cfg.n_routed_experts), jnp.float32)
        # a buffer in the release: it moves the choice, never the weights
        bias = self.param("e_score_correction_bias",
                          nn.initializers.zeros_init(),
                          (cfg.n_routed_experts,), jnp.float32)
        w_up = self.param("experts_up", normal, (held[1], L, F),
                          cfg.storage_dtype())
        w_down = self.param("experts_down", normal, (held[1], F, L),
                            cfg.storage_dtype())
        flat = h.reshape(B * T, E)
        choice, weights = moe.route(
            flat, w_router, bias, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.norm_topk_prob)
        with jax.named_scope("moe.latent_in"):
            x_l = _dense(L, "latent_in", ("embed", None), cfg)(flat)
        routed, stats = moe.routed_experts(
            x_l, choice, weights, w_up.astype(cdt), w_down.astype(cdt),
            held=held, live=None if live is None else live.reshape(B * T))
        if sow_kv:
            self.sow("intermediates", "serve_stats", stats)
        with jax.named_scope("moe.latent_out"):
            routed = _dense(E, "latent_out", (None, "embed"), cfg)(routed)
        with jax.named_scope("moe.shared"):
            shared = _relu2(h, cfg.moe_shared_expert_intermediate_size,
                            ("shared_up_proj", "shared_down_proj"), cfg)
        return routed.reshape(B, T, E) + shared


class NemotronH(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids, *, attention_mask=None, segment_ids=None,
                 position_ids=None, deterministic: bool = True,
                 return_hidden: bool = False, kv_lens=None,
                 sow_kv: bool = False, kv_pages=None, page_tables=None,
                 ssm_pools=None, slots=None, ssm_init=None):
        """The serving hooks are gpt2.GPT2.__call__'s (``sow_kv`` sows
        each layer's fresh cache, ``kv_pages``/``page_tables``/``kv_lens``
        attend over the paged cache: one pair for each ``*`` layer, in
        layer order), and two of this family's: ``ssm_pools`` (one
        ``(states, tails)`` pair for each ``M`` layer, in layer order) and
        ``slots`` [B], the pools' rows this step moves on by one token;
        the moved pools are sown back under ``ssm_cache``. Without them
        an ``M`` layer starts from a zero state, or from ``ssm_init`` (one
        ``(state, tail)`` pair an ``M`` layer: what the sequence's earlier
        part left), and sows the state after the last live position
        (``attention_mask`` says which are live).
        ``position_ids`` is taken and not read: nothing here is
        positional."""
        del position_ids, deterministic
        cfg = self.cfg
        B, T = input_ids.shape
        wte = self.param(
            "embed_tokens",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         ("vocab", "embed")),
            (cfg.padded_vocab, cfg.hidden_size), cfg.storage_dtype())
        # the rows a routed layer counts and a state-space layer feeds on:
        # not a prefill bucket's padding, not a decode bucket's empty slots
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
        for i, kind in enumerate(cfg.hybrid_override_pattern):
            pages = pools = init = None
            if kind == "*" and kv_pages is not None:
                pages, n_kv = kv_pages[n_kv], n_kv + 1
            if kind == "M":
                if ssm_pools is not None:
                    pools = ssm_pools[n_ssm]
                if ssm_init is not None:
                    init = ssm_init[n_ssm]
                n_ssm += 1
            x = NemotronHBlock(cfg, kind, name=f"layer_{i}")(
                x, attention_mask, segment_ids, live, live_len, kv_lens,
                sow_kv, pages, page_tables, pools, slots, init)
        x = _norm(cfg, "norm_f")(x)
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


def make_model(preset_or_cfg) -> tuple[NemotronH, NemotronHConfig]:
    cfg = (PRESETS[preset_or_cfg] if isinstance(preset_or_cfg, str)
           else preset_or_cfg)
    return NemotronH(cfg), cfg
