"""The LFM2-MoE train step's two kernel families' shares of their
rooflines, from the device trace: the least time the chip could take for
the work the ALGORITHM needs in the traced slice (`kernel_math_lfm2_moe`)
over the device time of every event of the kernels in it. As in
`trace_kernel`, the events are told by the instruction's OWN name and its
opcode; they decide the time and never the credit (remat's second forward
is time, not work). The steps in the slice are the runs of the program
matching `module_pattern`, a cut run counting by its share.

`model` says how the work follows from the cell:
  moe_train     the rows the TRACED steps computed here (the program's
                counter `train.moe.rows`, read by the driver where the
                slice opens and closes), through a held expert's three
                matrices forward, for the rows' gradient and for the
                matrices', against the bytes of the experts those steps
                TOUCHED (`train.moe.experts_touched`: the grouped product
                skips an expert without rows)
  flash_packed  causal attention inside the DOCUMENTS of the packed rows
                (the driver's sum of their lengths squared, a step), not
                the [T, T] half square

Where the program has no such kernel or counter there is nothing to read:
None."""

from . import kernel_math_lfm2_moe as km, xplane


def read(rec, *, pattern: str, model: str, module_pattern: str,
         opcode: str = "custom-call"):
    if rec.trace is None:
        return None
    spent = sum(s for _, s in xplane.matching_ops(rec.trace, pattern, opcode))
    steps = xplane.program_runs(rec.trace, module_pattern)
    if spent <= 0 or steps <= 0:
        return None
    c, s = rec.ctx.config, rec.run.stats
    if model == "moe_train":
        rows = s.get("traced_moe_rows")
        if rows is None:            # a program that counts nothing
            return None
        least = km.roofline_seconds(*km.moe_train_work(
            rows, s["traced_moe_experts"], c["hidden_size"],
            c["moe_intermediate_size"]), rec.peaks)
    elif model == "flash_packed":
        doc_sq = s.get("doc_sq_per_step")
        if not doc_sq:
            return None
        heads = c["num_attention_heads"]
        least = steps * km.attention_layers(c) * sum(
            km.roofline_seconds(*km.flash_packed_work(
                doc_sq, s["tokens_per_step"], heads,
                c["num_key_value_heads"], c["hidden_size"] // heads,
                backward), rec.peaks) for backward in (False, True))
    else:
        raise ValueError(model)
    return 100.0 * least / spent
