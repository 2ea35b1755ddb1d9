"""The AFMoE family's two paged decode attentions' shares of their
rooflines, from the device trace: the least time the chip could take to
read what the ALGORITHM needs in the traced slice
(`kernel_math_gqa_window_moe`) over the device time of every event of the
kernel in it. As in `trace_kernel`, the events are told by the
instruction's OWN name and its opcode; they decide the time and never the
credit.

`model` says how the work follows from the cell:
  gqa_window_decode  the K and V rows of `min(context, window)` tokens a
                     live slot a decode step inside the slice (the
                     program's counter `serve.kv.window.live_tokens`, read
                     by the driver when the slice opens and closes), once
                     in each `sliding_attention` layer
  gqa_full_decode    the K and V rows of the live tokens of the decode
                     steps inside the slice (`traced_live_tokens`), once
                     in each `full_attention` layer

Where the program has no such kernel or counter (the parent of the PR that
added them, or a cell of another family) there is nothing to read: None.
"""

from . import kernel_math_gqa_window_moe as km, xplane


def read(rec, *, pattern: str, model: str, opcode: str = "custom-call"):
    if rec.trace is None:
        return None
    spent = sum(s for _, s in xplane.matching_ops(rec.trace, pattern, opcode))
    if spent <= 0:
        return None
    c, s = rec.ctx.config, rec.run.stats
    kinds = c.get("layer_types")
    if not kinds:
        return None
    heads = (c["num_key_value_heads"], c["head_dim"])
    if model == "gqa_window_decode":
        live = s.get("traced_window_live_tokens")
        if not live:
            return None
        nbytes = km.gqa_window_decode_bytes(
            live, *heads, kinds.count("sliding_attention"))
    elif model == "gqa_full_decode":
        live = s.get("traced_live_tokens")
        if not live:
            return None
        nbytes = km.gqa_full_decode_bytes(
            live, *heads, kinds.count("full_attention"))
    else:
        raise ValueError(model)
    return 100.0 * km.roofline_seconds(0.0, nbytes, rec.peaks) / spent
