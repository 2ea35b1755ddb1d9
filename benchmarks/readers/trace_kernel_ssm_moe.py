"""The Nemotron-H family's two kernels' shares of their rooflines, from the
device trace: the least time the chip could take for the work the ALGORITHM
needs in the traced slice (`kernel_math_ssm_moe`) over the device time of
every event of the kernel in it. As in `trace_kernel`, the events are told
by the instruction's OWN name and its opcode; they decide the time and
never the credit.

`model` says how the work follows from the cell:
  ssm_decode   the live slots' states of the decode steps inside the slice,
               once read and once written in every state-space layer (the
               program's counter `serve.ssm.slot_steps`, read by the
               driver when the slice opens and closes)
  moe_latent   the rows computed here and the touched experts that the
               programs inside the slice reported (`serve.moe.rows` /
               `serve.moe.experts_touched`), through the two matrices of
               one latent expert

Where the program has no such kernel or counter (the parent of the PR that
added them, or a cell of another family) there is nothing to read: None.
"""

from . import kernel_math_ssm_moe as km, xplane


def read(rec, *, pattern: str, model: str, opcode: str = "custom-call"):
    if rec.trace is None:
        return None
    spent = sum(s for _, s in xplane.matching_ops(rec.trace, pattern, opcode))
    if spent <= 0:
        return None
    c, s = rec.ctx.config, rec.run.stats
    if model == "ssm_decode":
        steps = s.get("traced_ssm_slot_steps")
        if not steps:
            return None
        work = km.ssm_decode_work(steps, c["mamba_num_heads"],
                                  c["mamba_head_dim"], c["ssm_state_size"])
    elif model == "moe_latent":
        rows, touched = s.get("traced_moe_rows"), s.get("traced_moe_experts")
        if not rows or not touched:
            return None
        work = km.moe_latent_work(rows, touched, c["moe_latent_size"],
                                  c["moe_intermediate_size"])
    else:
        raise ValueError(model)
    return 100.0 * km.roofline_seconds(*work, rec.peaks) / spent
