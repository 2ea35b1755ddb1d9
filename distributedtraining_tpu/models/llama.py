"""TPU-first Flax Llama family (Llama-2-7B / Llama-3-8B presets).

Targets BASELINE.json configs 4-5 (Llama-2-7B LoRA-delta miner on v4-32;
Llama-3-8B full-param delta on multi-host v5e-64). The reference never ships
these models — it trains GPT-2 only — but its delta/merge machinery is
model-agnostic, and these presets are what the scale configs exercise.

Architecture: RMSNorm pre-norm, rotary position embeddings, SwiGLU MLP,
grouped-query attention. Same TPU idioms as gpt2.py: logical sharding axes on
every param, bf16 compute / fp32 storage, optional remat, packed-sequence
masks.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax.numpy as jnp

from ..ops.attention import (
    cached_attention, causal_attention, remat_policy)
from ..ops.embed import embed_lookup
from .family import FamilyConfig, RMSNorm, dense, rotary_embedding


@dataclasses.dataclass(frozen=True)
class LlamaConfig(FamilyConfig):
    vocab_size: int = 32000
    max_seq_len: int = 4096
    n_embd: int = 4096
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: int = 32
    intermediate_size: int = 11008
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: bool = True
    # flash = Pallas kernel on TPU; declining backends fall back to the
    # blockwise lax spelling at long T (ops/attention.py), so no path
    # materializes [T, T] scores. GQA kv heads are broadcast to query
    # heads before the call either way.
    attention_impl: str = "flash"
    vocab_multiple: int = 128
    # lax.scan over the block stack (see gpt2.GPT2Config.scan_blocks): at
    # 32-80 layers this is the difference between minutes and seconds of
    # XLA compile. stack_blocks/unstack_blocks convert layouts.
    scan_blocks: bool = False
    # logits storage dtype (see gpt2.GPT2Config.logits_dtype); at Llama-3's
    # 128k padded vocab the f32 logits are by far the largest activation
    logits_dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    # see ``GPT2Config.rounds_first``. Every matrix is a bias-free
    # ``nn.Dense`` kernel or the untied head, cast before its product; the
    # lookup's rows are cast straight after the gather, which rounds the
    # same values. Not an RMSNorm's scale: it multiplies in float32
    cast_first = ("kernel", "lm_head", "wte")


PRESETS: dict[str, LlamaConfig] = {
    "llama2-7b": LlamaConfig(),
    "llama3-8b": LlamaConfig(vocab_size=128256, max_seq_len=8192,
                             n_kv_head=8, intermediate_size=14336,
                             rope_theta=500000.0),
    "tiny-llama": LlamaConfig(vocab_size=512, max_seq_len=128, n_embd=64,
                              n_layer=2, n_head=4, n_kv_head=2,
                              intermediate_size=128, remat=False),
}


class LlamaBlock(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, attention_mask, segment_ids, position_ids,
                 kv_ctx=None, kv_lens=None, sow_kv=False,
                 kv_pages=None, page_tables=None):
        """KV-cache hooks mirror gpt2.Block: ``sow_kv`` sows post-RoPE,
        PRE-GQA-broadcast (k, v) — the cache stores Hkv heads and the
        decode path broadcasts to query heads at attention time, so a
        GQA cache is n_head/n_kv_head times smaller than the activations
        it replaces. The PAGED decode mode (``kv_pages``/``page_tables``,
        ops/paged_attention.py) is GQA-native: the kernel groups query
        heads per kv head in-kernel, so the decode path never
        materializes the ``jnp.repeat`` head broadcast at all."""
        cfg = self.cfg
        B, T, E = x.shape
        Hq, Hkv, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim

        h = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="attn_norm")(x)
        q = dense(Hq * D, "wq", ("embed", "qkv"), cfg)(h).reshape(B, T, Hq, D)
        k = dense(Hkv * D, "wk", ("embed", "qkv"), cfg)(h).reshape(B, T, Hkv, D)
        v = dense(Hkv * D, "wv", ("embed", "qkv"), cfg)(h).reshape(B, T, Hkv, D)
        q = rotary_embedding(q, position_ids, cfg.rope_theta)
        k = rotary_embedding(k, position_ids, cfg.rope_theta)
        if sow_kv:
            self.sow("intermediates", "kv_cache", (k, v))
        if kv_pages is not None:
            from ..ops.paged_attention import paged_attention
            attn = paged_attention(q, kv_pages[0], kv_pages[1],
                                   page_tables, kv_lens, k, v)
        elif kv_ctx is not None:
            k_ctx, v_ctx = kv_ctx
            k_full = jnp.concatenate([k_ctx, k], axis=1)
            v_full = jnp.concatenate([v_ctx, v], axis=1)
            if Hkv != Hq:
                rep = Hq // Hkv
                k_full = jnp.repeat(k_full, rep, axis=2)
                v_full = jnp.repeat(v_full, rep, axis=2)
            attn = cached_attention(q, k_full, v_full, kv_lens)
        else:
            if Hkv != Hq:  # GQA: broadcast kv heads to query heads
                rep = Hq // Hkv
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            attn = causal_attention(q, k, v, attention_mask=attention_mask,
                                    segment_ids=segment_ids,
                                    impl=cfg.attention_impl)
        attn = dense(E, "wo", ("qkv", "embed"), cfg)(attn.reshape(B, T, Hq * D))
        x = x + attn

        h = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="mlp_norm")(x)
        gate = dense(cfg.intermediate_size, "w_gate", ("embed", "mlp"), cfg)(h)
        up = dense(cfg.intermediate_size, "w_up", ("embed", "mlp"), cfg)(h)
        down = dense(E, "w_down", ("mlp", "embed"), cfg)(nn.silu(gate) * up)
        # pin the residual stream to batch sharding at the block boundary:
        # with fsdp-sharded params GSPMD otherwise reshards activations
        # off the batch axis (B-fold activation blowup at 8B/seq 8k);
        # the pin forces the ZeRO-3 strategy — params all-gather, batch
        # stays sharded. No-op without ambient logical_axis_rules.
        return nn.with_logical_constraint(x + down,
                                          ("batch", "seq", None))


class _BlockScan(nn.Module):
    """nn.scan target: LlamaBlock with scan's (carry, out) contract."""
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, attention_mask, segment_ids, position_ids):
        blk = LlamaBlock
        if self.cfg.remat:
            blk = nn.remat(LlamaBlock, policy=remat_policy())
        x = blk(self.cfg, name="block")(x, attention_mask, segment_ids,
                                        position_ids)
        return x, None


class Llama(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, *, attention_mask=None, segment_ids=None,
                 position_ids=None, deterministic: bool = True,
                 return_hidden: bool = False,
                 kv_ctx=None, kv_lens=None, sow_kv: bool = False,
                 kv_pages=None, page_tables=None):
        """``return_hidden=True`` skips the LM head and returns the final
        normed hidden states (fused-CE path, ops.losses) — at Llama vocab
        sizes (32k/128k padded) the [B, T, V] logits this avoids are the
        single largest activation tensor in the step.

        ``kv_ctx``/``kv_lens``/``sow_kv``/``kv_pages``/``page_tables``
        are the serving plane's KV-cache hooks — see gpt2.GPT2.__call__;
        the cache stores n_kv_head heads (GQA) and requires the unrolled
        block layout."""
        cfg = self.cfg
        B, T = input_ids.shape
        if (kv_ctx is not None or kv_pages is not None or sow_kv) \
                and cfg.scan_blocks:
            raise ValueError(
                "KV-cache generation needs the unrolled block layout; "
                "rebuild the serving model with scan_blocks=False "
                "(wire artifacts are unrolled already)")
        wte = self.param(
            "wte",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         ("vocab", "embed")),
            (cfg.padded_vocab, cfg.n_embd), cfg.storage_dtype())
        if position_ids is None:
            position_ids = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        # mesh-aware backward: see ops/embed.py (dp x fsdp meshes would
        # otherwise fully rematerialize the cotangent in the wte scatter)
        x = embed_lookup(wte, input_ids).astype(cfg.compute_dtype())
        x = nn.with_logical_constraint(x, ("batch", "seq", None))

        if cfg.scan_blocks:
            scan = nn.scan(
                _BlockScan,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=(nn.broadcast, nn.broadcast, nn.broadcast),
                length=cfg.n_layer,
                metadata_params={nn.meta.PARTITION_NAME: "layers"})
            x, _ = scan(cfg, name="layers")(x, attention_mask, segment_ids,
                                            position_ids)
        elif kv_ctx is not None or kv_pages is not None or sow_kv:
            # serving forward: no backward pass, so remat (and sowing
            # through jax.checkpoint, which is undefined) is skipped;
            # param names are identical with or without the wrapper
            for i in range(cfg.n_layer):
                x = LlamaBlock(cfg, name=f"layer_{i}")(
                    x, attention_mask, segment_ids, position_ids,
                    kv_ctx[i] if kv_ctx is not None else None,
                    kv_lens, sow_kv,
                    kv_pages[i] if kv_pages is not None else None,
                    page_tables)
        else:
            block = LlamaBlock
            if cfg.remat:
                block = nn.remat(LlamaBlock, policy=remat_policy())
            for i in range(cfg.n_layer):
                x = block(cfg, name=f"layer_{i}")(x, attention_mask,
                                                  segment_ids, position_ids)
        x = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="final_norm")(x)
        x = nn.with_logical_constraint(x, ("batch", "seq", None))
        if return_hidden:
            return x
        lm_head = self.param(
            "lm_head",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         ("vocab", "embed")),
            (cfg.padded_vocab, cfg.n_embd), cfg.storage_dtype())
        logits = jnp.einsum("bte,ve->btv", x, lm_head.astype(cfg.compute_dtype()),
                            preferred_element_type=jnp.float32)
        # same pin as gpt2: head all-gathers over fsdp, hidden stays put
        logits = nn.with_logical_constraint(logits, ("batch", None, "vocab"))
        return logits.astype(jnp.dtype(cfg.logits_dtype))

    def init_params(self, rng, *, seq_len: int = 8):
        """Raw (unboxed) param pytree; logical axis metadata is recovered
        separately via parallel.sharding.logical_param_specs."""
        dummy = jnp.zeros((1, seq_len), jnp.int32)
        return nn.meta.unbox(self.init(rng, dummy)["params"])


def make_model(preset_or_cfg) -> tuple[Llama, LlamaConfig]:
    cfg = PRESETS[preset_or_cfg] if isinstance(preset_or_cfg, str) else preset_or_cfg
    return Llama(cfg), cfg


def draft_compat(cfg: LlamaConfig, target_cfg) -> str | None:
    """Speculative-serving hook (engine/speculative.py): why a Llama
    with this config cannot DRAFT for a target with ``target_cfg``
    (None = compatible). Token-id spaces must coincide — the fleet's
    small GPT-2 base can draft for a Llama target exactly when both
    were trained over the same tokenizer (equal REAL ``vocab_size``;
    padded device vocab is irrelevant, sampling slices it off)."""
    tv = getattr(target_cfg, "vocab_size", None)
    if cfg.vocab_size != tv:
        return (f"draft vocab_size {cfg.vocab_size} != target "
                f"vocab_size {tv}: proposal ids would not name the "
                "same tokens")
    return None


def stack_blocks(params, n_layer: int):
    """Unrolled ``layer_0..layer_{L-1}`` -> scan layout (``layers/block``)."""
    from .gpt2 import stack_blocks as _stack
    return _stack(params, n_layer, prefix="layer_", scan_key="layers")


def unstack_blocks(params, n_layer: int):
    """Scan layout -> unrolled layout (inverse of stack_blocks)."""
    from .gpt2 import unstack_blocks as _unstack
    return _unstack(params, n_layer, prefix="layer_", scan_key="layers")
