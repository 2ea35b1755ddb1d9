"""How the roofline reader applies the per-call counts to a trace's events,
on a slice recorded from the chip that holds what once misled it
(`data/trace_flash_slice.json`): the backward pass as two kernels, and
`slice-start` instructions that only name a flash call among their
operands."""

import json
import os
import types

import pytest

from readers import trace_kernel, xplane

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SMALL = {"n_embd": 768, "n_layer": 12}       # gpt2-124m, the slice's model
ARGS = {"pattern": "flash", "model": "flash_attention",
        "module_pattern": "train_step"}


def _recorded():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "trace_flash_slice.json")
    with open(path) as f:
        return json.load(f)


def _trace(rec, drop: str = ""):
    ops = {p: [tuple(e) for e in evs if not (drop and drop in
                                             e[0].partition(" = ")[0])]
           for p, evs in rec["device_ops"].items()}
    mods = {p: [tuple(e) for e in evs]
            for p, evs in rec["device_modules"].items()}
    span = (xplane.WINDOW_SPAN, rec["window"][0],
            rec["window"][1] - rec["window"][0])
    return xplane.from_events(ops, [span], mods)


def _rec(trace, **stats):
    ctx = types.SimpleNamespace(model_cfg=lambda: SMALL)
    run = types.SimpleNamespace(stats=dict(batch=8, seq_len=1024, **stats))
    return types.SimpleNamespace(ctx=ctx, run=run, peaks=PEAKS, trace=trace)


def test_only_the_kernels_own_events_are_timed():
    rec = _recorded()
    t = _trace(rec)
    calls = xplane.matching_ops(t, "flash", "custom-call")
    # 8 dkv + 8 dq in the slice, the first dkv ends before the window
    assert len(calls) == rec["expect"]["kernel_events_inside"] == 15
    assert sum(s for _, s in calls) * 1e9 == pytest.approx(
        rec["expect"]["kernel_ns_inside"])
    assert {n.rsplit(".", 1)[0][:18] for n, _ in calls} == {
        "%flash_mha_bwd_dkv", "%flash_mha_bwd_dq_"}
    # the whole text of 28 more events inside the window holds `flash`
    whole = [e for e in rec["device_ops"]["/device:TPU:0"]
             if "flash" in e[0] and e[1] >= 0]
    assert len(whole) == 15 + 28


def test_steps_in_the_window_are_the_programs_runs_cut_runs_by_share():
    rec = _recorded()
    # one run of 87.63 ms ends with the 22.1 ms window, one starts after
    # it, one program has another name: 22.1 / 87.63 of a step
    assert xplane.program_runs(_trace(rec), "train_step") == pytest.approx(
        22.1 / 87.63)
    assert xplane.program_runs(_trace(rec), "no_such_program") == 0.0


def test_flash_share_credits_the_algorithm_once_per_layer_and_step():
    rec = _recorded()
    # B8 T1024 E768, causal: forward 2*8*1024^2*768 = 12,884,901,888
    # operations = 65.41 us at 197 TFLOP/s (its 50.3 MB take 61.5 us);
    # backward 5/2 of that = 163.51 us (100.7 MB: 122.9 us): 228.92 us a
    # layer and step, 12 layers, 22.1/87.63 of a step in the window
    least = (22.1 / 87.63) * 12 * (12_884_901_888 + 32_212_254_720) / 197e12
    assert least == pytest.approx(692.8e-6, rel=1e-3)
    want = 100.0 * least / (rec["expect"]["kernel_ns_inside"] / 1e9)
    got = trace_kernel.read(_rec(_trace(rec)), **ARGS)
    assert got == pytest.approx(want) and 5.5 < got < 5.7
    # a backward pass in ONE kernel that takes no longer than dkv alone
    # did the same work in less time: the share goes up, not down
    fused = trace_kernel.read(_rec(_trace(rec, drop="bwd_dq")), **ARGS)
    assert fused > got * 1.5
    # without the program's runs there is nothing to credit
    assert trace_kernel.read(_rec(_trace(rec)), **dict(
        ARGS, module_pattern="no_such_program")) is None


def test_paged_share_reads_the_logged_live_tokens():
    ops = {"/device:TPU:0": [
        ("%paged_decode_attention.36 = bf16[32,1,1280]{2,1,0} custom-call("
         "s32[32,64]{1,0} %copy-done.4)", 0, 300_000),
        ("%convert_reduce_fusion.2 = f32[32]{0} fusion(bf16[32,1,1280]{2,1,0}"
         " %paged_decode_attention.36)", 300_000, 1_000)]}
    t = xplane.from_events(ops, [(xplane.WINDOW_SPAN, 0, 1_000_000)])
    rec = _rec(t, traced_live_tokens=3000)
    # 12 layers x K and V of 3,000 tokens x 768 x 2 bytes = 110,592,000
    # bytes = 135.03 us at 819 GB/s, over the kernel's own 300 us
    got = trace_kernel.read(rec, pattern="paged_decode_attention",
                            model="paged_decode")
    assert got == pytest.approx(100.0 * (110_592_000 / 819e9) / 300e-6)
