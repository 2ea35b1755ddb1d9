"""The train engine's two numbers from the host clock: milliseconds a
step, and the share of the chip's peak the model's own operations reach."""

from . import kernel_math


def read(rec, *, what: str):
    s = rec.run.stats
    if not s.get("steps"):
        return None
    if what == "step_ms":
        return 1e3 * s["window_s"] / s["steps"]
    if what == "mfu_pct":
        tokens_per_s = s["steps"] * s["tokens_per_step"] / s["window_s"]
        flops = kernel_math.train_flops_per_token(rec.ctx.model_cfg(),
                                                  s["seq_len"])
        return 100.0 * tokens_per_s * flops / (
            rec.ctx.device["count"] * rec.peaks["bf16_flops_per_s"])
    raise ValueError(what)
