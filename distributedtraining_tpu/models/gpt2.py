"""TPU-first Flax GPT-2.

Capability parity with the reference's training target
(``openai-community/gpt2`` via HF AutoModelForCausalLM, neurons/miner.py:60)
— same architecture family (learned positions, pre-LN, gelu_new MLP, tied
embeddings) — but built for XLA/TPU rather than loaded from torch:

- fused QKV projection (one [E, 3E] matmul feeds the MXU instead of three)
- bf16 activations with fp32 params and fp32 softmax/logit accumulation
- logical sharding axis names on every parameter (``nn.with_logical_partitioning``)
  so parallel/sharding.py can map them onto any dp/fsdp/tp mesh without
  touching the model
- optional ``jax.checkpoint`` rematerialization per block (HBM for FLOPs)
- packed-sequence support (segment_ids) so training never pads
  (the reference pads every example to 64 tokens, neurons/miner.py:70)

The reference appends a ``[PAD]`` token and resizes embeddings
(training_manager.py:44-45), silently changing checkpoint shape; here the
vocab is padded up-front to a multiple of 128 (``vocab_multiple``) — both a
TPU lane-alignment win and an explicit, documented shape contract.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import (
    cached_attention, causal_attention, causal_attention_qkv, remat_policy)
from ..ops.embed import embed_lookup
from .family import FamilyConfig


@dataclasses.dataclass(frozen=True)
class GPT2Config(FamilyConfig):
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    layer_norm_epsilon: float = 1e-5
    dropout: float = 0.0
    dtype: str = "bfloat16"        # activation/compute dtype
    param_dtype: str = "float32"   # storage dtype
    remat: bool = False
    # flash is the TPU default: ops/flash_attention.py's rule selects the
    # Pallas kernel on TPU at block-aligned T without a padding mask, and
    # the XLA paths otherwise. Its gain over dense is not measured on the
    # current chip.
    attention_impl: str = "flash"  # "dense" | "flash" | "ring"
    vocab_multiple: int = 128      # pad vocab to a lane-aligned multiple
    # lax.scan over the block stack: one block traced/compiled once instead
    # of n_layer inlined copies. Changes the param-tree layout (per-block
    # leaves gain a leading [n_layer] axis under "h"/"block" instead of
    # h_0..h_{L-1}); stack_blocks/unstack_blocks convert. Same math.
    scan_blocks: bool = False
    # storage dtype of the [B, T, V] logits buffer. MXU accumulation stays
    # f32 either way (preferred_element_type); "bfloat16" halves the single
    # largest activation tensor's HBM round-trips at a small CE-input
    # precision cost (the loss still reduces in f32). Opt-in pending an
    # on-chip measurement.
    logits_dtype: str = "float32"

    n_kv_head = None               # as many K/V heads as query heads

    @property
    def max_seq_len(self) -> int:
        return self.n_positions

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    def rounds_first(self, path: tuple[str, ...]) -> bool:
        """Whether EVERY use of the leaf at ``path`` of an unrolled base
        in the serving forward casts it to the compute dtype first, so
        that the serving tree (engine/serve_weights.py) may hold it
        rounded, once per revision: a block's four ``nn.Dense`` (which
        promotes kernel AND bias). Not ``wte`` / ``wpe``, which the
        lookup reads as stored (``wte[id] + wpe[pos]`` is a float32 sum,
        rounded after); not a LayerNorm, which multiplies by its float32
        scale."""
        return len(path) == 3 and path[1] in _DENSE

    # The tied head multiplies by ``wte`` ROUNDED, the lookup reads it as
    # stored: the serving tree holds the head's operand as a leaf of its
    # own under the first name, rounded from the second.
    serving_head = ("lm_head", "wte")


_DENSE = ("c_attn", "c_proj", "c_fc", "mlp_proj")

# Preset registry; "tiny" is the test model (fast CPU init/step).
PRESETS: dict[str, GPT2Config] = {
    "gpt2-124m": GPT2Config(),
    "gpt2-355m": GPT2Config(n_embd=1024, n_layer=24, n_head=16),
    "gpt2-774m": GPT2Config(n_embd=1280, n_layer=36, n_head=20),
    "gpt2-1.5b": GPT2Config(n_embd=1600, n_layer=48, n_head=25),
    "tiny": GPT2Config(vocab_size=512, n_positions=128, n_embd=64,
                       n_layer=2, n_head=4, vocab_multiple=128),
    # soak-scale: enough capacity that a multi-hour CPU soak keeps
    # descending instead of hitting tiny's ~2.4 byte-LM ceiling in the
    # first minutes (scripts/soak.py)
    "mini": GPT2Config(vocab_size=512, n_positions=128, n_embd=128,
                       n_layer=4, n_head=4, vocab_multiple=128),
}


def _dense(features: int, name: str, kernel_axes: tuple, cfg: GPT2Config,
           use_bias: bool = True) -> nn.Dense:
    return nn.Dense(
        features,
        use_bias=use_bias,
        dtype=cfg.compute_dtype(),
        param_dtype=cfg.storage_dtype(),
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(stddev=0.02), kernel_axes),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), (kernel_axes[-1],)),
        name=name,
    )


class Block(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, attention_mask, segment_ids, deterministic,
                 kv_ctx=None, kv_lens=None, sow_kv=False,
                 kv_pages=None, page_tables=None):
        """``kv_ctx``/``kv_lens``/``sow_kv`` are the serving plane's
        KV-cache hooks (engine/serve.py). ``sow_kv=True`` sows this
        block's (k, v) into the ``intermediates`` collection so a prefill
        pass can populate a cache; ``kv_ctx=(k_ctx, v_ctx)`` switches
        attention to decode mode — the current tokens attend over the
        padded cached context (valid through ``kv_lens``) plus
        themselves. ``kv_pages=(k_pages, v_pages)`` (+ ``page_tables``)
        is the PAGED decode mode: attention reads this layer's page-pool
        slice directly through the table (ops/paged_attention.py — the
        fused TPU kernel, or its XLA twin off-TPU) instead of a
        pre-gathered context; the fresh (k, v) still reach the pool via
        the sow + the engine's post-step scatter. All default off,
        leaving the training forward byte-identical to before."""
        cfg = self.cfg
        B, T, E = x.shape
        with jax.named_scope("gpt2.attn"):
            h = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.compute_dtype(),
                             param_dtype=cfg.storage_dtype(),
                             scale_init=nn.with_logical_partitioning(
                                 nn.initializers.ones_init(), ("embed",)),
                             bias_init=nn.with_logical_partitioning(
                                 nn.initializers.zeros_init(), ("embed",)),
                             name="ln_1")(x)
            qkv = _dense(3 * E, "c_attn", ("embed", "qkv"), cfg)(h)
            if sow_kv or kv_pages is not None or kv_ctx is not None:
                # the serve paths sow and page k and v a head at a time
                q, k, v = jnp.split(qkv, 3, axis=-1)
                q = q.reshape(B, T, cfg.n_head, cfg.head_dim)
                k = k.reshape(B, T, cfg.n_head, cfg.head_dim)
                v = v.reshape(B, T, cfg.n_head, cfg.head_dim)
                if sow_kv:
                    self.sow("intermediates", "kv_cache", (k, v))
                if kv_pages is not None:
                    from ..ops.paged_attention import paged_attention
                    attn = paged_attention(q, kv_pages[0], kv_pages[1],
                                           page_tables, kv_lens, k, v)
                elif kv_ctx is not None:
                    k_ctx, v_ctx = kv_ctx
                    attn = cached_attention(q,
                                            jnp.concatenate([k_ctx, k], axis=1),
                                            jnp.concatenate([v_ctx, v], axis=1),
                                            kv_lens)
                else:
                    attn = causal_attention(q, k, v, attention_mask=attention_mask,
                                            segment_ids=segment_ids,
                                            impl=cfg.attention_impl)
                attn = attn.reshape(B, T, E)
            else:
                # training and eval: the attention core takes c_attn's
                # array as it lies and hands c_proj its own (where the
                # flash kernels run they read q, k and v out of it: no
                # split, no transpose, no copy)
                attn = causal_attention_qkv(
                    qkv, cfg.n_head, attention_mask=attention_mask,
                    segment_ids=segment_ids, impl=cfg.attention_impl)
            attn = _dense(E, "c_proj", ("qkv", "embed"), cfg)(attn)
            if cfg.dropout > 0:
                attn = nn.Dropout(cfg.dropout)(attn, deterministic=deterministic)
            x = x + attn

        with jax.named_scope("gpt2.mlp"):
            h = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.compute_dtype(),
                             param_dtype=cfg.storage_dtype(),
                             scale_init=nn.with_logical_partitioning(
                                 nn.initializers.ones_init(), ("embed",)),
                             bias_init=nn.with_logical_partitioning(
                                 nn.initializers.zeros_init(), ("embed",)),
                             name="ln_2")(x)
            h = _dense(4 * E, "c_fc", ("embed", "mlp"), cfg)(h)
            h = nn.gelu(h, approximate=True)  # gelu_new, as in GPT-2
            h = _dense(E, "mlp_proj", ("mlp", "embed"), cfg)(h)
            if cfg.dropout > 0:
                h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
            return x + h


class _BlockScan(nn.Module):
    """nn.scan target: Block with the (carry, out) contract scan requires."""
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, attention_mask, segment_ids, deterministic):
        blk = Block
        if self.cfg.remat:
            blk = nn.remat(Block, static_argnums=(4,), policy=remat_policy())
        x = blk(self.cfg, name="block")(x, attention_mask, segment_ids,
                                        deterministic)
        return x, None


class GPT2(nn.Module):
    """Decoder-only transformer; ``__call__`` returns [B, T, padded_vocab] logits."""
    cfg: GPT2Config

    @nn.compact
    def __call__(self, input_ids, *, attention_mask=None, segment_ids=None,
                 position_ids=None, deterministic: bool = True,
                 return_hidden: bool = False,
                 kv_ctx=None, kv_lens=None, sow_kv: bool = False,
                 kv_pages=None, page_tables=None):
        """``return_hidden=True`` skips the LM head and returns the final
        normed hidden states [B, T, E] — the fused cross-entropy path
        (ops.losses.fused_linear_cross_entropy) computes the head matmul
        tile-by-tile inside the loss instead of materializing logits.

        KV-cache generation hooks (engine/serve.py): ``sow_kv=True`` sows
        each block's (k, v) into ``intermediates`` (apply with
        ``mutable=["intermediates"]`` to read them back — the prefill
        path); ``kv_ctx`` is a per-layer tuple of (k_ctx, v_ctx) padded
        context arrays with real lengths ``kv_lens`` [B] — the
        decode-step path. Both require the unrolled block layout
        (``scan_blocks=False``); the serving engine always runs one."""
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
        wpe = self.param(
            "wpe",
            nn.with_logical_partitioning(nn.initializers.normal(0.01),
                                         (None, "embed")),
            (cfg.n_positions, cfg.n_embd), cfg.storage_dtype())

        # embed_lookup (ops/embed.py): gather forward everywhere; on
        # dp x fsdp meshes the backward switches to the one-hot einsum so
        # the cotangent never pays GSPMD's involuntary full
        # rematerialization resharding onto the table's fsdp axis.
        # Positions index with the 1-D arange (NOT [None, :]): a
        # [1, T, E] intermediate would carry a degenerately batch-sharded
        # size-1 axis. [T, E] broadcasts identically and stays replicated.
        with jax.named_scope("gpt2.embed"):
            if position_ids is None:
                x = embed_lookup(wte, input_ids) + embed_lookup(
                    wpe, jnp.arange(T))
            else:
                x = embed_lookup(wte, input_ids) + embed_lookup(
                    wpe, position_ids)
            # pin the embedding output (and, critically, its COTANGENT —
            # the constraint applies to both) to batch sharding: on hybrid
            # (dcn_dp) meshes the partitioner otherwise reshards dx onto
            # the embed/fsdp axis for the wte/wpe scatter backward, a
            # transfer that is inexpressible on the hybrid device order and
            # falls back to involuntary full rematerialization. No-op
            # without ambient logical_axis_rules (single-device paths).
            x = nn.with_logical_constraint(x, ("batch", None, None))
            x = x.astype(cfg.compute_dtype())
            if cfg.dropout > 0:
                x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)

        if cfg.attention_impl == "flash" and not (
                kv_ctx is not None or kv_pages is not None or sow_kv):
            # where the flash kernels walk the block pairs the rows' segment
            # ids need, the step counts them, every layer alike (engine/
            # train.py hands `train_counters` out beside the loss)
            from ..ops import flash_attention
            pairs = flash_attention.block_pairs(
                jax.ShapeDtypeStruct((B, T, cfg.n_head, cfg.head_dim),
                                     cfg.compute_dtype()),
                attention_mask, segment_ids)
            if pairs is not None:
                self.sow("intermediates", "train_counters", {
                    name: cfg.n_layer * n for name, n in zip(
                        flash_attention.BLOCK_PAIR_COUNTERS, pairs)})

        if cfg.scan_blocks:
            # one Block program, lax.scan'd n_layer times: ~L-fold smaller
            # HLO (compile time) at identical step math. "layers" has no
            # mesh rule -> per-layer leaves replicate exactly like the
            # unrolled layout's.
            scan = nn.scan(
                _BlockScan,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast, nn.broadcast, nn.broadcast),
                length=cfg.n_layer,
                metadata_params={nn.meta.PARTITION_NAME: "layers"})
            x, _ = scan(cfg, name="h")(x, attention_mask, segment_ids,
                                       deterministic)
        elif kv_ctx is not None or kv_pages is not None or sow_kv:
            # serving forward: remat is for backward-pass memory and a
            # generation step never differentiates, so the cache paths
            # skip it (sowing through jax.checkpoint is also undefined);
            # param names are identical with or without the wrapper
            for i in range(cfg.n_layer):
                x = Block(cfg, name=f"h_{i}")(
                    x, attention_mask, segment_ids, deterministic,
                    kv_ctx[i] if kv_ctx is not None else None,
                    kv_lens, sow_kv,
                    kv_pages[i] if kv_pages is not None else None,
                    page_tables)
        else:
            block = Block
            if cfg.remat:
                block = nn.remat(Block, static_argnums=(4,),
                                 policy=remat_policy())
            for i in range(cfg.n_layer):
                x = block(cfg, name=f"h_{i}")(x, attention_mask, segment_ids,
                                              deterministic)

        with jax.named_scope("gpt2.head"):
            x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.compute_dtype(),
                             param_dtype=cfg.storage_dtype(),
                             scale_init=nn.with_logical_partitioning(
                                 nn.initializers.ones_init(), ("embed",)),
                             bias_init=nn.with_logical_partitioning(
                                 nn.initializers.zeros_init(), ("embed",)),
                             name="ln_f")(x)
            if return_hidden:
                return x
            # tied lm head: logits accumulate fp32 on the MXU. The logical
            # constraint pins logits to batch x vocab(tp) sharding so the
            # partitioner all-gathers the (small) head over fsdp rather than
            # resharding the [B, T, E] hidden states onto the embed axis — on
            # hybrid (dcn_dp) meshes that reshard is inexpressible and falls
            # back to involuntary full rematerialization. No-op without an
            # ambient logical_axis_rules context (single-device paths).
            if self.has_variable("params", "lm_head"):
                # a serving tree (engine/serve_weights.py): the head's operand
                # was rounded when the revision was installed. No base and no
                # training tree has this leaf: they trace the line below.
                head = self.get_variable("params", "lm_head")
            else:
                head = wte.astype(cfg.compute_dtype())
            logits = jnp.einsum("bte,ve->btv", x, head,
                                preferred_element_type=jnp.float32)
            logits = nn.with_logical_constraint(logits, ("batch", None, "vocab"))
            # the astype fuses into the matmul epilogue, so "bfloat16" means the
            # stored buffer (not the accumulation) shrinks
            return logits.astype(jnp.dtype(cfg.logits_dtype))

    def init_params(self, rng, *, seq_len: int = 8):
        """Raw (unboxed) param pytree; logical axis metadata is recovered
        separately via parallel.sharding.logical_param_specs."""
        dummy = jnp.zeros((1, seq_len), jnp.int32)
        return nn.meta.unbox(self.init(rng, dummy)["params"])


def make_model(preset_or_cfg) -> tuple[GPT2, GPT2Config]:
    cfg = PRESETS[preset_or_cfg] if isinstance(preset_or_cfg, str) else preset_or_cfg
    return GPT2(cfg), cfg


def draft_compat(cfg: GPT2Config, target_cfg) -> str | None:
    """Speculative-serving hook (engine/speculative.py): why a GPT-2
    with this config cannot DRAFT for a target with ``target_cfg``
    (None = compatible). Proposals are raw token ids the target scores
    verbatim, so the REAL vocabularies must match exactly — the padded
    device vocab may differ freely (sampling slices to ``vocab_size``).
    The drafter's position capacity is a soft limit (the draft engine
    stops proposing past it), not a compatibility failure."""
    tv = getattr(target_cfg, "vocab_size", None)
    if cfg.vocab_size != tv:
        return (f"draft vocab_size {cfg.vocab_size} != target "
                f"vocab_size {tv}: proposal ids would not name the "
                "same tokens")
    return None


def stack_blocks(params, n_layer: int, *, prefix: str = "h_",
                 scan_key: str = "h"):
    """Unrolled layout (``h_0..h_{L-1}``) -> scan layout (``h/block`` with a
    leading [L] axis on every per-block leaf). Boundary adapters: HF
    converters (models/convert.py) and, via the wire helpers in
    engine/train.py (wire_out/wire_in), every transport artifact — bases
    and full-param deltas ALWAYS travel unrolled, so ``--scan-blocks`` is
    a per-role execution choice, not a fleet-wide protocol flag. A
    genuinely foreign stacked payload is still diagnosed by name at the
    loader (serialization._diagnose_block_layout_mismatch)."""
    blocks = [params[f"{prefix}{i}"] for i in range(n_layer)]
    # host numpy stays host numpy: transport-fetched deltas arrive as numpy
    # and averagers may gather ~100 of them before merging chunk-at-a-time
    # (delta.chunked_weighted_merge) — a jnp.stack here would commit every
    # full-param delta to device HBM at the wire boundary, defeating the
    # merge's O(chunk x params) device-memory bound
    stacked = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs) if isinstance(xs[0], np.ndarray)
        else jnp.stack(xs), *blocks)
    out = {k: v for k, v in params.items()
           if not (k.startswith(prefix) and k[len(prefix):].isdigit())}
    out[scan_key] = {"block": stacked}
    return out


def unstack_blocks(params, n_layer: int, *, prefix: str = "h_",
                   scan_key: str = "h"):
    """Scan layout -> unrolled layout (inverse of stack_blocks)."""
    stacked = params[scan_key]["block"]
    out = {k: v for k, v in params.items() if k != scan_key}
    for i in range(n_layer):
        out[f"{prefix}{i}"] = jax.tree_util.tree_map(
            lambda x, i=i: x[i], stacked)
    return out
