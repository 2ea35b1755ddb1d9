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

from ..ops import delta_rule, moe, ssm
from ..ops.attention import causal_attention
from ..ops.embed import embed_lookup
from ..ops.paged_attention import paged_attention
from .gpt2 import pad_vocab
from .llama import RMSNorm, _dense
from .nemotron_h import _a_log_init, _conv_init, _dt_bias_init

_LINEAR = (("head_dim", 128), ("num_heads", 64), ("num_kv_heads", None),
           ("short_conv_kernel_size", 4))


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
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
    # the program's own
    experts_held: tuple[int, int] = (0, 320)   # (first, count) on this chip
    kda_low_rank: int = 128
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
        linear = dict(self.linear_attn_config)
        unsupported = {
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
            "experts_held": not (0 <= first and count >= 1
                                 and first + count <= self.n_routed_experts),
            "tie_word_embeddings": self.tie_word_embeddings,
            "scan_blocks": self.scan_blocks,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"SolarOpen2Config: {', '.join(bad)} not "
                             "supported (this block writes one reading of "
                             "each key: see the module's docstring)")

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

    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def storage_dtype(self):
        return jnp.dtype(self.param_dtype)

    def rounds_first(self, path: tuple[str, ...]) -> bool:
        """See ``GPT2Config.rounds_first``. Cast before every use: the
        ``nn.Dense`` kernels, the experts' two stacks, the head; the
        lookup's rows straight after the gather. Not ``A_log``,
        ``dt_bias``, the convolution (they enter the float32 recurrence),
        a norm's gain, the router or its selection bias (float32 scores):
        those leaves are float32 in the tree and stay so."""
        return path[-1] in _CAST_FIRST


_CAST_FIRST = ("kernel", "experts_gate_up", "experts_down", "lm_head",
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


def _norm(cfg, name: str) -> RMSNorm:
    return RMSNorm(cfg.rms_norm_eps, "float32", name=name)


def _swiglu(h, width: int, names: tuple[str, str, str], cfg):
    gate = _dense(width, names[0], ("embed", "mlp"), cfg)(h)
    up = _dense(width, names[1], ("embed", "mlp"), cfg)(h)
    act = moe.clamped_swiglu(gate, up, None).astype(gate.dtype)
    return _dense(cfg.hidden_size, names[2], ("mlp", "embed"), cfg)(act)


class SolarOpen2Block(nn.Module):
    cfg: SolarOpen2Config
    full_attention: bool

    @nn.compact
    def __call__(self, x, attention_mask, segment_ids, live, live_len,
                 kv_lens=None, sow_kv=False, kv_pages=None,
                 page_tables=None, ssm_pools=None, slots=None,
                 ssm_init=None):
        cfg = self.cfg
        h = _norm(cfg, "mixer_norm")(x)
        if self.full_attention:
            with jax.named_scope("solar.gqa"):
                y = self._attention(h, attention_mask, segment_ids, kv_lens,
                                    sow_kv, kv_pages, page_tables)
        else:
            with jax.named_scope("solar.kda"):
                y = self._delta(h, live_len, kv_lens, sow_kv, ssm_pools,
                                slots, ssm_init)
        x = x + y
        with jax.named_scope("solar.moe_ffn"):
            return x + self._experts(_norm(cfg, "ffn_norm")(x), live, sow_kv)

    def _delta(self, u, live_len, kv_lens, sow_kv, ssm_pools, slots,
               ssm_init):
        cfg = self.cfg
        B, T, E = u.shape
        H, dk, dv = cfg.ssm_state_shape
        K, conv_dim = cfg.ssm_tail_shape[0] + 1, cfg.conv_dim
        r = cfg.kda_low_rank
        cdt, f32 = cfg.compute_dtype(), jnp.float32
        qkv = _dense(conv_dim, "in_proj_qkv", ("embed", "mlp"), cfg)(u)
        conv_w = self.param("conv1d_weight", _conv_init, (K, conv_dim), f32)
        a_log = self.param("A_log", _a_log_init, (H,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H * dk,), f32)
        f = _dense(H * dk, "f_b_proj", (None, "mlp"), cfg)(
            _dense(r, "f_a_proj", ("embed", None), cfg)(u))
        # dk log-decays a head, float32
        g = (-jnp.exp(a_log)[:, None]
             * jax.nn.softplus(f.astype(f32) + dt_bias
                               ).reshape(B, T, H, dk))
        beta = 2.0 * jax.nn.sigmoid(
            _dense(H, "b_proj", ("embed", None), cfg)(u).astype(f32))
        gate = _dense(H * dv, "g_b_proj", (None, "mlp"), cfg)(
            _dense(r, "g_a_proj", ("embed", None), cfg)(u))

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

        if ssm_pools is None:
            with jax.named_scope("kda.prefill"):
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
            with jax.named_scope("kda.decode"):
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
                "kda_slot_steps": jnp.sum(kv_lens > 0).astype(jnp.int32)})
        w_o = self.param("o_norm", nn.initializers.ones_init(), (dv,), f32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.rms_norm_eps)
        o = o * w_o * output_gate(gate).reshape(B, T, H, dv)
        return _dense(E, "out_proj", ("mlp", "embed"), cfg)(
            o.reshape(B, T, H * dv).astype(cdt))

    def _attention(self, h, attention_mask, segment_ids, kv_lens, sow_kv,
                   kv_pages, page_tables):
        cfg = self.cfg
        B, T, E = h.shape
        Hq, Hkv, Dh = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        q = _dense(Hq * Dh, "q_proj", ("embed", "qkv"), cfg)(h)
        k = _dense(Hkv * Dh, "k_proj", ("embed", "qkv"), cfg)(h)
        v = _dense(Hkv * Dh, "v_proj", ("embed", "qkv"), cfg)(h)
        gate = _dense(Hq * Dh, "g_proj", ("embed", "qkv"), cfg)(h)
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
                impl=cfg.attention_impl)
        attn = (attn.reshape(B, T, Hq * Dh).astype(jnp.float32)
                * output_gate(gate)).astype(cfg.compute_dtype())
        return _dense(E, "o_proj", ("qkv", "embed"), cfg)(attn)

    def _experts(self, h, live, sow_kv):
        cfg = self.cfg
        B, T, E = h.shape
        cdt = cfg.compute_dtype()
        G, F, held = (cfg.n_routed_experts, cfg.moe_intermediate_size,
                      cfg.experts_held)
        normal = nn.initializers.normal(0.02)
        w_router = self.param("router", normal, (E, G), jnp.float32)
        # a buffer in the lineage's releases: it moves the choice, never
        # the weights
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
            live=None if live is None else live.reshape(B * T))
        if sow_kv:
            self.sow("intermediates", "serve_stats", stats)
        with jax.named_scope("moe.shared"):
            shared = _swiglu(
                h, cfg.n_shared_experts * F,
                ("shared_gate_proj", "shared_up_proj", "shared_down_proj"),
                cfg)
        return routed.reshape(B, T, E) + shared


class SolarOpen2(nn.Module):
    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, input_ids, *, attention_mask=None, segment_ids=None,
                 position_ids=None, deterministic: bool = True,
                 return_hidden: bool = False, kv_lens=None,
                 sow_kv: bool = False, kv_pages=None, page_tables=None,
                 ssm_pools=None, slots=None, ssm_init=None):
        """The serving hooks are nemotron_h.NemotronH.__call__'s:
        ``kv_pages`` one pair for each attention layer, in layer order,
        ``ssm_pools`` one ``(states, tails)`` pair for each delta-rule
        layer, ``slots`` [B] the pools' rows this step moves on by one
        token; the moved pools are sown back under ``ssm_cache``. Without
        pools a delta-rule layer runs the whole of ``input_ids`` and sows
        the state after the last live position (``attention_mask`` says
        which are live): from zero, or from ``ssm_init``, one ``(state
        [B, ..], tail [B, ..])`` pair a delta-rule layer: what the
        sequence's earlier part left. ``position_ids`` is taken and not
        read: nothing here is positional."""
        del position_ids, deterministic
        cfg = self.cfg
        B, T = input_ids.shape
        wte = self.param(
            "embed_tokens",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         ("vocab", "embed")),
            (cfg.padded_vocab, cfg.hidden_size), cfg.storage_dtype())
        # the rows a routed layer counts and a delta-rule layer feeds on:
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
        for i in range(cfg.num_hidden_layers):
            full = i in cfg.gqa_layers
            pages = pools = init = None
            if full and kv_pages is not None:
                pages, n_kv = kv_pages[n_kv], n_kv + 1
            if not full:
                if ssm_pools is not None:
                    pools = ssm_pools[n_ssm]
                if ssm_init is not None:
                    init = ssm_init[n_ssm]
                n_ssm += 1
            x = SolarOpen2Block(cfg, full, name=f"layer_{i}")(
                x, attention_mask, segment_ids, live, live_len, kv_lens,
                sow_kv, pages, page_tables, pools, slots, init)
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


def make_model(preset_or_cfg) -> tuple[SolarOpen2, SolarOpen2Config]:
    cfg = (PRESETS[preset_or_cfg] if isinstance(preset_or_cfg, str)
           else preset_or_cfg)
    return SolarOpen2(cfg), cfg
