"""A kernel's share of its roofline, from the device trace: the least time
the chip could take for the work the ALGORITHM needs in the traced window
(operations and bytes from shapes, `kernel_math`) over the device time of
every event of the kernel in it. The events are told by the instruction's
own name and its opcode (`xplane.matching_ops`); they decide the time and
never the credit, so a re-run under remat, a backward pass split over two
kernels or fused into one, each change the time and leave the work as it is.

`model` says how the work follows from the cell:
  flash_attention  one causal forward and one backward per layer per train
                   step; the steps in the traced window are the runs of the
                   program matching `module_pattern` on the device's
                   `XLA Modules` line, a cut run counting by its share
  paged_decode     the bytes are the live KV of the decode steps inside the
                   traced slice, which the driver logged per step, once per
                   layer
"""

from . import kernel_math, xplane


def read(rec, *, pattern: str, model: str, opcode: str = "custom-call",
         module_pattern: str = ""):
    if rec.trace is None:
        return None
    spent = sum(s for _, s in xplane.matching_ops(rec.trace, pattern, opcode))
    if spent <= 0:
        return None
    cfg, s = rec.ctx.model_cfg(), rec.run.stats
    if model == "flash_attention":
        steps = xplane.program_runs(rec.trace, module_pattern)
        if steps <= 0:
            return None
        least = steps * cfg["n_layer"] * sum(
            kernel_math.roofline_seconds(*kernel_math.flash_attention_call(
                s["batch"], s["seq_len"], cfg["n_embd"], backward=backward),
                rec.peaks) for backward in (False, True))
    elif model == "paged_decode":
        live = s.get("traced_live_tokens")
        if not live:
            return None
        least = kernel_math.roofline_seconds(
            0.0, cfg["n_layer"] * kernel_math.paged_decode_bytes(
                live, cfg["n_embd"]), rec.peaks)
    else:
        raise ValueError(model)
    return 100.0 * least / spent
