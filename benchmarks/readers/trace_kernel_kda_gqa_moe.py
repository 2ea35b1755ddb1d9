"""The Solar-Open2 family's own two kernels' shares of their rooflines,
from the device trace (its held experts' grouped product is read by
`trace_kernel_mla_moe`, as the accepted expert cells' is): the least time
the chip could take for the work the ALGORITHM needs in the traced slice
(`kernel_math_kda_gqa_moe`) over the device time of every event of the
kernel in it. As in `trace_kernel`, the events are told by the
instruction's OWN name and its opcode; they decide the time and never the
credit.

`model` says how the work follows from the cell:
  kda_decode        the live slots' states of the decode steps inside the
                    slice, once read and once written in every delta-rule
                    layer, with the step's q, k, v and per-channel decay
                    (the program's counter `serve.kda.slot_steps`, read by
                    the driver when the slice opens and closes)
  gqa_paged_decode  the K and V rows of the live tokens of the decode
                    steps inside the slice (`traced_live_tokens`), once in
                    each attention layer the configuration holds

Where the program has no such kernel or counter (the parent of the PR that
added them, or a cell of another family) there is nothing to read: None.
"""

from . import kernel_math_kda_gqa_moe as km, xplane


def read(rec, *, pattern: str, model: str, opcode: str = "custom-call"):
    if rec.trace is None:
        return None
    spent = sum(s for _, s in xplane.matching_ops(rec.trace, pattern, opcode))
    if spent <= 0:
        return None
    c, s = rec.ctx.config, rec.run.stats
    if model == "kda_decode":
        steps, linear = (s.get("traced_kda_slot_steps"),
                         c.get("linear_attn_config"))
        if not steps or not linear:
            return None
        work = km.kda_decode_work(steps, linear["num_heads"],
                                  linear["head_dim"])
    elif model == "gqa_paged_decode":
        live, layers = s.get("traced_live_tokens"), c.get("gqa_layers")
        if not live or not layers:
            return None
        work = (0.0, km.gqa_paged_decode_bytes(
            live, c["num_key_value_heads"], c["head_dim"], len(layers)))
    else:
        raise ValueError(model)
    return 100.0 * km.roofline_seconds(*work, rec.peaks) / spent
