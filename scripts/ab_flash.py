#!/usr/bin/env python3
"""A/B of the causal attention kernels, on the chip, before one is wired in.

Two families, both in the installed jax:

  lib     jax.experimental.pallas.ops.tpu.flash_attention, re-blocked: the
          forward, dK/dV and dQ kernels each with their own blocks
  splash  jax.experimental.pallas.ops.tpu.splash_attention: the mask is
          read at trace time, a fully masked block is no kernel step, the
          backward is one fused kernel (or two, `unfused`)

and `landed`, whatever `ops/flash_attention.py` does on this tree. Every
candidate is timed at the train cell's shape (B=4, H=20, T=1024, D=64,
bfloat16, packed segment ids off the 128 grid) forward alone and forward +
gradient, and checked there against `causal_attention(impl="dense")`
(forward and the three gradients, packed and unpacked); every forward
candidate is timed and checked at the six forward-only shapes
(1, {20, 25}, {256, 512, 1024}, 64) with its blocks clipped to T. The
candidates that lost live only here.

    chiprun -- python scripts/ab_flash.py          # the table, on the chip
    JAX_PLATFORMS=cpu python scripts/ab_flash.py --aot
                                  # compile every candidate for a described
                                  # v5e; nothing runs, no chip needed
    chiprun -- python scripts/ab_flash.py --gqa
                                  # grouped-query heads (32 over 8 K/V heads
                                  # of 64) at B=2, T=8,192, packed: K/V
                                  # repeated to the query heads through
                                  # `landed`, against the library's grouped
                                  # (MQA) kernel mapped over the K/V heads

    chiprun -- python scripts/ab_flash.py --packed
                                  # `landed`'s two paths: the library's
                                  # kernels over the constants of the causal
                                  # mask against its own over the pair list
                                  # computed from the rows' segment ids, at
                                  # the LFM2 train cell's shape, GPT-2's,
                                  # and T of 2,048 and 4,096 between them,
                                  # on the cells' own documents; the cost of
                                  # a grid step that runs and of one that
                                  # does not, and the threshold the rule
                                  # takes

    chiprun -- python scripts/ab_flash.py --layout
                                  # where the operands lie: the library's
                                  # kernels heads first, PR 40's pair list
                                  # heads first (a head a grid row, blocks
                                  # of 64 lanes), and `landed`'s pair list
                                  # rows-major ([B, T, H D], two heads of
                                  # 64 a 128-lane block) with a head's
                                  # lanes taken by masks (what landed) or
                                  # by static slices; each the kernels alone and from
                                  # c_attn's fused [B, T, 3E] to c_proj's
                                  # [B, T, E] with the splits and
                                  # transposes it needs; GPT-2's shape, T =
                                  # 512 (one block a row) and LFM2's

A time is the device's: `reps` calls chained inside ONE jitted loop (each
call's output is the next one's query), the wall clock around it with
`block_until_ready`, the fastest of `--trials`, over `reps`. One compile
serves the timing (reps = n) and the check (reps = 1).
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu import flash_attention as fa
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as sk, splash_attention_mask as sm)

from distributedtraining_tpu.ops import flash_attention as landed_mod
from distributedtraining_tpu.ops.attention import causal_attention

TRAIN = (4, 20, 1024, 64)
FORWARD_ONLY = [(1, h, t, 64) for h in (20, 25) for t in (256, 512, 1024)]
# atol of tests_tpu/test_flash_attention_tpu.py: forward, gradients
ATOL = (3e-2, 1e-1)


# -- the two families, in the framework's [B, T, H, D] layout ---------------

def lib(fwd, dkv=None, dq=None):
    """fwd = (block_q, block_k_major, block_k); dkv = (block_q_major,
    block_k_major, block_q, block_k); dq = (block_q, block_k_major,
    block_k). Blocks are clipped to T."""
    dkv = dkv or (fwd[0], fwd[1], fwd[0], fwd[2])
    dq = dq or fwd

    def fn(q, k, v, seg):
        T, D = q.shape[1], q.shape[3]
        c = lambda xs: tuple(min(x, T) for x in xs)
        f, kv, dq_ = c(fwd), c(dkv), c(dq)
        blocks = fa.BlockSizes(
            block_q=f[0], block_k_major=f[1], block_k=f[2], block_b=1,
            block_q_major_dkv=kv[0], block_k_major_dkv=kv[1],
            block_q_dkv=kv[2], block_k_dkv=kv[3],
            block_q_dq=dq_[0], block_k_major_dq=dq_[1], block_k_dq=dq_[2])
        s = None if seg is None else fa.SegmentIds(q=seg, kv=seg)
        out = fa.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), segment_ids=s, causal=True,
            sm_scale=D ** -0.5, block_sizes=blocks)
        return out.transpose(0, 2, 1, 3).astype(q.dtype)
    return fn


def splash(fwd, dkv=None, dq=None):
    """fwd = (block_q, block_kv, block_kv_compute); dkv likewise for the
    backward; dq = (block_q_dq, block_kv_dq) asks for the two-kernel
    backward, None for the fused one. Blocks are clipped to T."""
    dkv = dkv or fwd

    def fn(q, k, v, seg):
        T, H, D = q.shape[1:]
        c = lambda xs: tuple(min(x, T) for x in xs)
        f, b = c(fwd), c(dkv)
        blocks = sk.BlockSizes(
            block_q=f[0], block_kv=f[1], block_kv_compute=f[2],
            block_q_dkv=b[0], block_kv_dkv=b[1], block_kv_dkv_compute=b[2],
            block_q_dq=None if dq is None else min(dq[0], T),
            block_kv_dq=None if dq is None else min(dq[1], T),
            use_fused_bwd_kernel=dq is None)
        kernel = sk.make_splash_mha(
            sm.MultiHeadMask([sm.CausalMask((T, T))] * H),
            block_sizes=blocks, head_shards=1, q_seq_shards=1)
        qs = (q * D ** -0.5).astype(q.dtype).transpose(0, 2, 1, 3)
        ks, vs = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        if seg is None:
            out = jax.vmap(lambda a, b_, c_: kernel(a, b_, c_))(qs, ks, vs)
        else:
            out = jax.vmap(lambda a, b_, c_, s: kernel(
                a, b_, c_, segment_ids=sk.SegmentIds(q=s, kv=s)))(
                    qs, ks, vs, seg)
        return out.transpose(0, 2, 1, 3).astype(q.dtype)
    return fn


def landed(q, k, v, seg):
    out = landed_mod.flash_attention(q, k, v, segment_ids=seg)
    if out is None:
        raise SystemExit("ops/flash_attention.py declined the shape "
                         f"{q.shape}: no TPU backend?")
    return out


def dense(q, k, v, seg):
    return causal_attention(q, k, v, segment_ids=seg, impl="dense")


def candidates():
    """(name, fn, role): role says which sweep a candidate belongs to —
    'fwd' ones are also run at the forward-only shapes."""
    out = [("landed", landed, "fwd"),
           # the parent's rule, spelled out: one q block of 1,024 rows
           ("lib parent 1024/256", lib((1024, 256, 256)), "fwd")]
    sizes = (128, 256, 512)
    for bq in sizes + (1024,):
        for bk in sizes:
            if (bq, bk) != (1024, 256):
                out.append((f"lib fwd {bq}/{bk}", lib((bq, bk, bk)), "fwd"))
    mid = (256, 256, 256)
    for bq in sizes:
        for bk in sizes:
            out.append((f"lib dkv {bq}/{bk}",
                        lib(mid, (bq, bk, bq, bk), mid), "bwd"))
            out.append((f"lib dq {bq}/{bk}",
                        lib(mid, None, (bq, bk, bk)), "bwd"))
    grid = [(bq, bkv, c) for bq in (256, 512) for bkv in (256, 512)
            for c in (128, 256, 512) if c <= bkv]
    grid += [(128, 128, 128), (1024, 512, 512), (512, 1024, 512),
             (1024, 1024, 512), (1024, 1024, 1024)]
    for g in grid:
        tag = "/".join(map(str, g))
        out.append((f"splash fwd {tag}", splash(g, (512, 512, 512)), "fwd"))
        out.append((f"splash fused {tag}", splash((512, 512, 512), g), "bwd"))
    for g in ((256, 256, 256), (512, 512, 512)):
        tag = "/".join(map(str, g))
        out.append((f"splash unfused {tag}", splash(g, g, g[:2]), "bwd"))
    return out


# -- grouped-query heads ----------------------------------------------------

GQA = (2, 32, 8, 8192, 64)         # B, query heads, K/V heads, T, D


def gqa_repeat(q, k, v, seg):
    rep = q.shape[2] // k.shape[2]
    return landed(q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                  seg)


def gqa_grouped(q, k, v, seg):
    """The library's MQA kernel (one K/V head under a group of query
    heads), mapped over the batch and the K/V heads, with `landed`'s
    blocks."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    kernel = sk.make_splash_mqa_single_device(
        sm.MultiHeadMask([sm.CausalMask((T, T))] * (Hq // Hkv)),
        block_sizes=landed_mod._block_sizes(T))
    qs = (q * D ** -0.5).astype(q.dtype).reshape(B, T, Hkv, Hq // Hkv, D)
    out = jax.vmap(jax.vmap(
        lambda a, b, c, s: kernel(a, b, c, segment_ids=sk.SegmentIds(
            q=s, kv=s)), in_axes=(0, 0, 0, None)))(
        qs.transpose(0, 2, 3, 1, 4), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), seg)          # [B, Hkv, G, T, D]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, Hq, D).astype(q.dtype)


def run_gqa(args, sharding):
    """Both spellings at the LFM2 train cell's shape, forward and forward
    + gradient; the grouped kernel is checked against the repeated one
    (a dense oracle would hold 17 GB of scores)."""
    B, Hq, Hkv, T, D = GQA
    q, _, _, do, seg = make_inputs((B, Hq, T, D), args.seed, True)
    _, k, v, _, _ = make_inputs((B, Hkv, T, D), args.seed + 1, False)
    inputs = on_device((q, k, v, do, seg), sharding)
    rows, ref = [], None
    for name, fn in (("gqa repeat (landed)", gqa_repeat),
                     ("gqa grouped (splash mqa)", gqa_grouped)):
        row = {"shape": list(GQA), "candidate": name}
        try:
            if sharding is not None:
                for grad in (False, True):
                    programs(fn, grad).trace(*inputs, 1).lower(
                        lowering_platforms=("tpu",)).compile()
                row["compiled"] = True
            else:
                row["fwd_ms"] = time_ms(programs(fn, False), inputs,
                                        args.reps, args.trials)
                prog = programs(fn, True)
                got = prog(*inputs, 1)
                row["fwd_bwd_ms"] = time_ms(prog, inputs, args.reps,
                                            args.trials)
                if ref is None:
                    ref = got
                else:
                    row["err_packed"] = worst(got, ref)
                    row["ok"] = (row["err_packed"][0] <= ATOL[0]
                                 and max(row["err_packed"][1:]) <= ATOL[1])
        except Exception as e:
            row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


# -- the pair list from the segment ids ---------------------------------------

# B, H, T, D and the traffic mix whose documents fill the rows (its batch and
# row length overridden where the shape is neither cell's)
PACKED = [((4, 20, 1024, 64), "packed-b4-t1024"),
          ((2, 32, 2048, 64), "packed-b2-t8192"),
          ((2, 32, 4096, 64), "packed-b2-t8192"),
          ((2, 32, 8192, 64), "packed-b2-t8192")]
# q and kv block alike, forward and backward, computed in one piece; the
# first is what `landed`'s rule gives these T
PACKED_BLOCKS = [512, 256, 128]


def packed(from_ids: bool, block: int):
    """`landed` with the pair list forced on (every packed row) or off (the
    library's kernels over the causal constants), at blocks of `block`."""
    def fn(q, k, v, seg):
        kept = landed_mod.TABLE_MIN_BLOCKS, landed_mod._block_sizes
        landed_mod.TABLE_MIN_BLOCKS = 0 if from_ids else q.shape[1] + 1
        landed_mod._block_sizes = lambda T: sk.BlockSizes(
            block_q=block, block_kv=block, block_kv_compute=block,
            block_q_dkv=block, block_kv_dkv=block,
            block_kv_dkv_compute=block, use_fused_bwd_kernel=True)
        try:
            return landed(q, k, v, seg)
        finally:
            landed_mod.TABLE_MIN_BLOCKS, landed_mod._block_sizes = kept
    return fn


def cell_documents(mix_name, B, T, seed):
    """[B, T] segment ids of the benchmark's own packed rows. The script
    reads the cells' generator by path: nothing a cell measures imports
    this file."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "gen", os.path.join(ROOT, "benchmarks", "traffic", "gen.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    mix = dict(gen.load_mix(mix_name), batch=B, seq_len=T)
    return next(gen.packed_batches(mix, seed, 1024))["segment_ids"]


def steps(seg, H, block):
    """(grid steps that run under the causal constants, pairs the ids need,
    the constants' grid steps in all) of one forward or backward call."""
    needed = np.asarray(landed_mod.needed_pairs(seg, seg, block))
    B, n, _ = needed.shape
    return H * B * n * (n + 1) // 2, H * int(needed.sum()), H * B * n * n


def run_packed(args, sharding):
    rows = []
    for shape, mix_name in PACKED:
        B, H, T, D = shape
        seg = cell_documents(mix_name, B, T, args.seed)
        q, k, v, do, _ = make_inputs(shape, args.seed, False)
        inputs = on_device((q, k, v, do, seg), sharding)
        ref = {}
        for block in PACKED_BLOCKS:
            if block > T // 2:
                continue
            for from_ids in (False, True):
                causal, pairs, grid = steps(seg, H, block)
                row = {"shape": list(shape), "candidate":
                       f"{'pair list' if from_ids else 'causal constants'} "
                       f"{block}",
                       "steps_run": pairs if from_ids else causal,
                       "steps": pairs if from_ids else grid}
                fn = packed(from_ids, block)
                try:
                    if sharding is not None:
                        for grad in (False, True):
                            programs(fn, grad).trace(*inputs, 1).lower(
                                lowering_platforms=("tpu",)).compile()
                        row["compiled"] = True
                    else:
                        row["fwd_ms"] = time_ms(programs(fn, False), inputs,
                                                args.reps, args.trials)
                        prog = programs(fn, True)
                        got = prog(*inputs, 1)
                        row["fwd_bwd_ms"] = time_ms(prog, inputs, args.reps,
                                                    args.trials)
                        # the pair list against the constants at the same
                        # blocks: out, dK and dV to the bit; dQ adds up in
                        # float32 where the library sums bfloat16 shares,
                        # so it may differ by a rounding of its largest
                        if not from_ids:
                            ref[block] = got
                        else:
                            err = worst(got, ref[block])
                            row["err_packed"] = err
                            dq_scale = float(jnp.max(jnp.abs(
                                ref[block][1].astype(jnp.float32))))
                            row["ok"] = (err[0] == err[2] == err[3] == 0.0
                                         and err[1] <= dq_scale * 2 ** -7)
                except Exception as e:
                    row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
                rows.append(row)
                print(json.dumps(row), flush=True)
    if sharding is None:
        print(packed_summary(rows), flush=True)
    return rows


def packed_summary(rows):
    """Per shape and block: what a grid step that runs costs (the pair
    list's time over its pairs: it visits nothing else), what an empty step
    of the constants' grid then costs (their time less their running steps
    at that price, over their empty steps), and the fewest blocks a row at
    which the pair list won."""
    lines, won = [], {}
    by = {(tuple(r["shape"]), r["candidate"]): r for r in rows
          if "fwd_ms" in r}
    for (shape, name), listed in by.items():
        if not name.startswith("pair list"):
            continue
        block = int(name.split()[-1])
        cau = by.get((shape, f"causal constants {block}"))
        if cau is None:
            continue
        for key, label in (("fwd_ms", "forward"), ("bwd", "backward")):
            t = [(r["fwd_bwd_ms"] - r["fwd_ms"]) if key == "bwd" else r[key]
                 for r in (cau, listed)]
            runs = 1e3 * t[1] / listed["steps_run"]
            empty = (1e3 * t[0] - cau["steps_run"] * runs) / max(
                1, cau["steps"] - cau["steps_run"])
            lines.append(
                f"{','.join(map(str, shape))} block {block} {label}: "
                f"{t[0]:.3f} -> {t[1]:.3f} ms, {cau['steps_run']} of "
                f"{cau['steps']} steps run -> {listed['steps_run']} pairs; "
                f"a step that runs {runs:.3f} us, an empty step of the "
                f"constants' grid {empty:.3f} us")
        if block == 512:
            won[shape[2] // 512] = (
                listed["fwd_ms"] < cau["fwd_ms"]
                and listed["fwd_bwd_ms"] < cau["fwd_bwd_ms"])
    lines.append("pair list faster, forward and forward + backward, by "
                 f"blocks a row at 512: {won}; the rule engages at "
                 f"TABLE_MIN_BLOCKS = {landed_mod.TABLE_MIN_BLOCKS}")
    return "\n".join(lines)


# -- where the operands lie ---------------------------------------------------

LAYOUT = [((4, 20, 1024, 64), "packed-b4-t1024"),
          ((4, 20, 512, 64), "packed-b4-t1024"),
          ((2, 32, 8192, 64), "packed-b2-t8192")]


@contextlib.contextmanager
def patched(**values):
    """`landed`'s module with these attributes for the length of a trace."""
    kept = {name: getattr(landed_mod, name) for name in values}
    for name, value in values.items():
        setattr(landed_mod, name, value)
    try:
        yield
    finally:
        for name, value in kept.items():
            setattr(landed_mod, name, value)


def _head_by_slice(ref, g, D):
    """`landed._head` by a static slice: head g's D lanes alone."""
    return ref[:, g * D:(g + 1) * D]


def _accumulate_by_slice(ref, g, D, share=None, *, scale=None,
                         rows=slice(None)):
    """`landed._accumulate` on head g's lanes alone."""
    at = rows, slice(g * D, (g + 1) * D)
    x = ref[at]
    if scale is not None:
        x = jnp.tile(scale, (1, -(-D // 128)))[:, :D] * x
    ref[at] = x if share is None else x + share


def _rejitted(call):
    """A kernel call of `landed` under a jit of its own FUNCTION: jit's
    traces are cached by the function and the operands' shapes, and one
    made with the landed spelling must not answer for another."""
    def own(*args, **kwargs):
        return call.__wrapped__(*args, **kwargs)
    return jax.jit(own, static_argnames=("H", "block", "save_residuals",
                                         "interpret"))


SLICES = dict(_head=_head_by_slice, _accumulate=_accumulate_by_slice,
              _fwd_call=_rejitted(landed_mod._fwd_call),
              _dkv_call=_rejitted(landed_mod._dkv_call))


def _heads_first_call(q, k, v, seg, lse=None, do=None, di=None, *,
                      block, save_residuals=False):
    """PR 40's grid and layout around `landed`'s kernel bodies: a head a
    grid row, `[block, D]` blocks of `[B, H, T, D]` arrays (a head of 64 in
    half a 128-lane tile). Forward, or with `do` the fused backward."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    m = landed_mod
    B, H, T, D = q.shape
    backward = do is not None
    needed = m.needed_pairs(seg, seg, block)
    *table, count = m._pair_list(needed.swapaxes(1, 2) if backward
                                 else needed)
    (q_ids, q_spec), (kv_ids, kv_spec) = m._ids_operands(
        seg, seg, block, kv_axis=0 if backward else 1)
    head = lambda which: pl.BlockSpec(
        (None, None, block, D),
        lambda h, p, row, *at: (row[p], h, at[which][p], 0))
    stat = lambda which: pl.BlockSpec(
        (1, 1, block), lambda h, p, row, *at: (row[p] * H + h, 0,
                                                at[which][p]))
    f32 = jnp.float32
    if not backward:
        out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
        out_specs = [head(0)]
        if save_residuals:
            out_shape.append(jax.ShapeDtypeStruct((B * H, 1, T), f32))
            out_specs.append(stat(0))
        name = m._kernel_name(is_mqa=False, save_residuals=save_residuals,
                              is_segmented=True, phase="fwd")
        out, *rest = pl.pallas_call(
            functools.partial(m._fwd_kernel, block=block, D=D),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(H, count),
                in_specs=[head(0), head(1), head(1), q_spec, kv_spec],
                out_specs=out_specs,
                scratch_shapes=[pltpu.VMEM((1, block, 128), f32),
                                pltpu.VMEM((1, block, 128), f32),
                                pltpu.VMEM((block, D), f32)]),
            out_shape=out_shape, name=name,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
        )(*table, q, k, v, q_ids, kv_ids)
        return (out, rest[0]) if save_residuals else out
    name = m._kernel_name(is_mqa=False, save_residuals=False,
                          is_segmented=True, phase="dkv")
    return pl.pallas_call(
        functools.partial(m._dkv_kernel, block=block, D=D),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(H, count),
            in_specs=[head(1), head(0), head(0), q_spec, kv_spec, stat(1),
                      head(1), stat(1)],
            out_specs=[pl.BlockSpec((None, None, T, D),
                                    lambda h, p, row, *_: (row[p], h, 0, 0)),
                       head(0), head(0)],
            scratch_shapes=[pltpu.VMEM((T, D), f32),
                            pltpu.VMEM((block, D), f32),
                            pltpu.VMEM((block, D), f32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] * 3, name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=m._vmem_limit(T, 128, True)),
    )(*table, q, k, v, q_ids, kv_ids, lse, do, di)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def heads_first_pairs(q, k, v, seg, block):
    return _heads_first_call(q, k, v, seg, block=block)


def _heads_first_fwd(q, k, v, seg, block):
    out, lse = _heads_first_call(q, k, v, seg, block=block,
                                 save_residuals=True)
    return out, (q, k, v, seg, out, lse)


def _heads_first_bwd(block, residuals, do):
    q, k, v, seg, out, lse = residuals
    di = jnp.einsum("bhsd,bhsd->bhs", out.astype(jnp.float32),
                    do.astype(jnp.float32)).reshape(lse.shape)
    return (*_heads_first_call(q, k, v, seg, lse, do, di, block=block),
            None)


heads_first_pairs.defvjp(_heads_first_fwd, _heads_first_bwd)


def layout_candidates(H, D, T):
    """(name, where, fn(x, seg) -> out, the layout of x): `where` is
    "kernels" (operands and result already as the kernels take them) or
    "block" (from c_attn's fused [B, T, 3E] to c_proj's [B, T, E], with
    every split, scaling and transpose between)."""
    m = landed_mod
    block = m._block_sizes(T).block_q
    always = dict(TABLE_MIN_BLOCKS=1)        # T = 512 is one block a row
    never = dict(TABLE_MIN_BLOCKS=T)
    E = H * D

    def library(x, seg):
        q, k, v = x
        ids = sk.SegmentIds(q=seg, kv=seg)
        return jax.vmap(m._causal_kernel(T, H))(q * D ** -0.5, k, v, ids)

    def pairs(x, seg):
        return heads_first_pairs(*x, seg, block)

    def rows_major(spelling):
        def fn(x, seg):
            with patched(**spelling):
                return m._packed_attention(tuple(x), seg, seg, H, block)
        return fn

    def split(x):
        return [a.reshape(*a.shape[:2], H, D)
                for a in jnp.split(x[0], 3, axis=-1)]

    def block_library(x, seg):
        with patched(**never):
            return m.flash_attention(*split(x), segment_ids=seg).reshape(
                *x[0].shape[:2], E)

    def block_pairs(x, seg):
        q, k, v = (a.transpose(0, 2, 1, 3) for a in split(x))
        out = heads_first_pairs(q, k, v, seg, block)
        return out.transpose(0, 2, 1, 3).reshape(*x[0].shape[:2], E)

    def block_rows_major(spelling):
        def fn(x, seg):
            with patched(**always, **spelling):
                return m.flash_attention_qkv(x[0], H, segment_ids=seg)
        return fn

    return [
        ("library, heads first", "kernels", library, "heads_first"),
        ("pair list, heads first (PR 40)", "kernels", pairs, "heads_first"),
        ("pair list, rows-major, lane slices", "kernels", rows_major(SLICES),
         "rows_major"),
        ("pair list, rows-major, lane masks", "kernels", rows_major({}),
         "rows_major"),
        ("library, heads first", "block", block_library, "fused"),
        ("pair list, heads first (PR 40)", "block", block_pairs, "fused"),
        ("pair list, rows-major, lane slices", "block",
         block_rows_major(SLICES), "fused"),
        ("pair list, rows-major, lane masks", "block",
         block_rows_major({}), "fused"),
    ]


def layout_programs(fn, grad):
    """(x, do, seg, reps) -> the LAST call's (out, dx): `reps` calls
    chained through the first operand (forward: the output goes back into
    q's place; with the gradient: dx is the next x)."""
    def feed(x, out):
        if len(x) == 3:
            return (out,) + tuple(x[1:])
        return (jax.lax.dynamic_update_slice(x[0], out, (0, 0, 0)),)

    def fwd(x, do, seg, reps):
        def body(_, x):
            return feed(x, fn(x, seg))
        return fn(jax.lax.fori_loop(0, reps - 1, body, x), seg), None

    def fwd_bwd(x, do, seg, reps):
        def body(_, c):
            out, vjp = jax.vjp(lambda x_: fn(x_, seg), c[1])
            return out, vjp(do)[0]
        return jax.lax.fori_loop(0, reps, body, (jnp.zeros_like(do), x))
    return jax.jit(fwd_bwd if grad else fwd)


def run_layout(args, sharding):
    rows = []
    for shape, mix_name in LAYOUT:
        B, H, T, D = shape
        seg = cell_documents(mix_name, B, T, args.seed)
        rng = np.random.default_rng(args.seed)
        qkv = rng.standard_normal((B, T, 3, H, D)).astype(np.float32)
        do = rng.standard_normal((B, T, H, D)).astype(np.float32)

        def put(x, dt=jnp.bfloat16):
            if sharding is not None:
                return jax.ShapeDtypeStruct(x.shape, dt, sharding=sharding)
            return jnp.asarray(x, dt)
        laid = {
            "heads_first": (tuple(put(qkv[:, :, i].transpose(0, 2, 1, 3))
                                  for i in range(3)),
                            put(do.transpose(0, 2, 1, 3))),
            "rows_major": (tuple(put(qkv[:, :, i].reshape(B, T, H * D))
                                 for i in range(3)),
                           put(do.reshape(B, T, H * D))),
            "fused": ((put(qkv.reshape(B, T, 3 * H * D)),),
                      put(do.reshape(B, T, H * D))),
        }
        seg = put(seg, jnp.int32)
        ref = None
        for name, where, fn, layout in layout_candidates(H, D, T):
            x, cot = laid[layout]
            row = {"shape": list(shape), "candidate": name, "where": where}
            try:
                if sharding is not None:
                    for grad in (False, True):
                        layout_programs(fn, grad).trace(
                            x, cot, seg, 1).lower(
                            lowering_platforms=("tpu",)).compile()
                    row["compiled"] = True
                else:
                    row["fwd_ms"] = time_ms(layout_programs(fn, False),
                                            (x, cot, seg), args.reps,
                                            args.trials)
                    prog = layout_programs(fn, True)
                    row["fwd_bwd_ms"] = time_ms(prog, (x, cot, seg),
                                                args.reps, args.trials)
                    if where == "block":
                        # out [B, T, E] and d(qkv) [B, T, 3E] against the
                        # library's, in one layout whatever the kernels'
                        out, dx = prog(x, cot, seg, 1)
                        got = (out, *jnp.split(dx[0], 3, axis=-1))
                        if ref is None:
                            ref = got
                        row["err_packed"] = worst(got, ref)
                        row["ok"] = (row["err_packed"][0] <= ATOL[0] and
                                     max(row["err_packed"][1:]) <= ATOL[1])
            except Exception as e:
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            rows.append(row)
            print(json.dumps(row), flush=True)
    return rows


# -- inputs -----------------------------------------------------------------

def make_inputs(shape, seed, packed):
    B, H, T, D = shape
    rng = np.random.default_rng(seed)
    q, k, v, do = (rng.standard_normal((B, T, H, D)).astype(np.float32)
                   for _ in range(4))
    seg = None
    if packed:
        # documents of Pareto lengths from 64 (shape 1.2), packed with no
        # padding: the boundaries fall off every block grid (T // 64
        # documents of at least 64 tokens always cover the row)
        lens = (64 * (1.0 + rng.pareto(1.2, (B, T // 64)))).astype(np.int64)
        seg = np.stack([np.repeat(np.arange(row.size), row)[:T]
                        for row in lens]).astype(np.int32)
    return q, k, v, do, seg


def programs(fn, grad):
    """(q, k, v, do, seg, reps) -> the LAST call's outputs, `reps` calls
    chained through q. reps is traced: one compile for any count."""
    def fwd(q, k, v, do, seg, reps):
        del do
        return jax.lax.fori_loop(
            0, reps, lambda _, o: fn(o, k, v, seg), q),

    def fwd_bwd(q, k, v, do, seg, reps):
        def body(_, c):
            out, vjp = jax.vjp(lambda a, b, c_: fn(a, b, c_, seg),
                               c[1], k, v)
            return (out,) + vjp(do)
        z = jnp.zeros_like(q)
        zk = jnp.zeros_like(k)          # fewer K/V heads under --gqa
        return jax.lax.fori_loop(0, reps, body, (z, q, zk, zk))
    return jax.jit(fwd_bwd if grad else fwd)


def on_device(arrays, sharding=None):
    q, k, v, do, seg = arrays
    put = lambda x, dt: None if x is None else jnp.asarray(x, dt)
    if sharding is not None:        # --aot: shapes on the described device
        put = lambda x, dt: None if x is None else jax.ShapeDtypeStruct(
            x.shape, dt, sharding=sharding)
    return (put(q, jnp.bfloat16), put(k, jnp.bfloat16), put(v, jnp.bfloat16),
            put(do, jnp.bfloat16), put(seg, jnp.int32))


def worst(got, ref):
    return [float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                  - r.astype(jnp.float32))))
            for g, r in zip(got, ref)]


def time_ms(prog, args, reps, trials):
    jax.block_until_ready(prog(*args, reps))
    best = float("inf")
    for _ in range(trials):
        t = time.perf_counter()
        jax.block_until_ready(prog(*args, reps))
        best = min(best, time.perf_counter() - t)
    return 1e3 * best / reps


# -- the run ----------------------------------------------------------------

def run_shape(shape, cands, args, oracle_cache, sharding):
    """One row per candidate at one shape: times and worst differences."""
    grad = shape == TRAIN
    rows = []
    cases = [True, False] if grad else [False]     # packed, unpacked
    inputs = {p: on_device(make_inputs(shape, args.seed, p), sharding)
              for p in cases}
    if sharding is None:
        for p in cases:
            oracle_cache[shape, p] = programs(dense, grad)(*inputs[p], 1)
    reps = args.reps if grad else 10 * args.reps
    for name, fn, role in cands:
        row = {"shape": list(shape), "candidate": name}
        try:
            if sharding is not None:
                for p in cases:
                    programs(fn, grad).trace(*inputs[p], 1).lower(
                        lowering_platforms=("tpu",)).compile()
                if grad and role == "fwd":
                    programs(fn, False).trace(*inputs[True], 1).lower(
                        lowering_platforms=("tpu",)).compile()
                row["compiled"] = True
            else:
                timed = cases[0]
                if role == "fwd":
                    row["fwd_ms"] = time_ms(programs(fn, False),
                                            inputs[timed], reps, args.trials)
                for p in cases if grad else []:
                    prog = programs(fn, True)
                    key = "packed" if p else "unpacked"
                    row[f"err_{key}"] = worst(prog(*inputs[p], 1),
                                              oracle_cache[shape, p])
                    if p == timed:
                        row["fwd_bwd_ms"] = time_ms(prog, inputs[p], reps,
                                                    args.trials)
                if not grad:
                    row["err_unpacked"] = worst(
                        programs(fn, False)(*inputs[False], 1),
                        oracle_cache[shape, False])
                errs = [e for k_, e in row.items() if k_.startswith("err_")]
                row["ok"] = all(e[0] <= ATOL[0] and max(e[1:] or [0])
                                <= ATOL[1] for e in errs)
        except Exception as e:  # a candidate the compiler refuses is a row
            row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def table(rows):
    lines = ["| shape B,H,T,D | candidate | fwd ms | fwd+bwd ms | worst |out|, "
             "|dq|, |dk|, |dv| difference from dense (packed; unpacked) | ok |",
             "| --- | --- | --- | --- | --- | --- |"]
    ms = lambda x: "" if x is None else f"{x:.4f}"
    er = lambda e: "" if e is None else ", ".join(f"{x:.3g}" for x in e)
    for r in rows:
        if "error" in r:
            note = r["error"]
        elif "compiled" in r:
            note = "compiled"
        else:
            note = f"{er(r.get('err_packed'))}; {er(r.get('err_unpacked'))}"
        lines.append(f"| {','.join(map(str, r['shape']))} | {r['candidate']}"
                     f"{' (' + r['where'] + ')' if 'where' in r else ''} "
                     f"| {ms(r.get('fwd_ms'))} | {ms(r.get('fwd_bwd_ms'))} "
                     f"| {note} | {r.get('ok', '')} |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--aot", action="store_true",
                    help="compile for a described v5e; nothing runs")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--trials", type=int, default=5)
    ap.add_argument("--gqa", action="store_true",
                    help="the grouped-query A/B alone (see above)")
    ap.add_argument("--packed", action="store_true",
                    help="causal constants against the pair list (see above)")
    ap.add_argument("--layout", action="store_true",
                    help="where the operands lie (see above)")
    ap.add_argument("--only", default="",
                    help="substring a candidate's name must hold")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "ab_flash.json"))
    args = ap.parse_args(argv)

    sharding = None
    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
        landed_mod._on_tpu = lambda: True
    elif jax.default_backend() != "tpu":
        print(f"ab_flash: needs a TPU, found {jax.default_backend()} "
              "(use --aot to compile without one)", file=sys.stderr)
        return 3
    dev = jax.devices()[0]
    print(f"ab_flash: {dev.platform} {dev.device_kind} x{jax.device_count()} "
          f"jax {jax.__version__} aot={args.aot}", flush=True)

    cands = [c for c in candidates() if args.only in c[0]]
    rows, oracle = [], {}
    if args.gqa:
        rows += run_gqa(args, sharding)
        args.out = os.path.splitext(args.out)[0] + "_gqa.json"
    elif args.packed:
        rows += run_packed(args, sharding)
        args.out = os.path.splitext(args.out)[0] + "_packed.json"
    elif args.layout:
        rows += run_layout(args, sharding)
        args.out = os.path.splitext(args.out)[0] + "_layout.json"
    else:
        rows += run_shape(TRAIN, cands, args, oracle, sharding)
        fwd_only = [c for c in cands if c[2] == "fwd"]
        for shape in FORWARD_ONLY:
            rows += run_shape(shape, fwd_only, args, oracle, sharding)
    text = table(rows)
    print(text)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": dev.device_kind, "jax": jax.__version__,
                   "aot": args.aot, "seed": args.seed, "reps": args.reps,
                   "rows": rows}, f, indent=1)
    with open(os.path.splitext(args.out)[0] + ".md", "w") as f:
        f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
