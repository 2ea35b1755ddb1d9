"""The LFM2-MoE train step's share of the chip's peak, from the host clock
and the step's own counters: tokens a second times the operations a step
NEEDS (`kernel_math_lfm2_moe.train_step_flops`: expert rows as the routed
layers counted them, attention by the documents' own lengths) over the
peak. Where the program has no such counters (the parent of the PR that
added them, or a cell of another family) there is nothing to read: None."""

from . import kernel_math_lfm2_moe as km


def read(rec, *, what: str):
    s = rec.run.stats
    rows, doc_sq = s.get("moe_rows_per_step"), s.get("doc_sq_per_step")
    if not s.get("steps") or rows is None or not doc_sq:
        return None
    if what != "mfu_pct":
        raise ValueError(what)
    flops = km.train_step_flops(rec.ctx.config, s["tokens_per_step"], rows,
                                doc_sq)
    return 100.0 * flops * s["steps"] / s["window_s"] / (
        rec.ctx.device["count"] * rec.peaks["bf16_flops_per_s"])
