"""The GigaChat-3.5 family's own two kernels' shares of their rooflines,
from the device trace (its held experts' grouped product is read by
`trace_kernel_mla_moe`, as the accepted expert cell's is): the least time
the chip could take for the work the ALGORITHM needs in the traced slice
(`kernel_math_gdn_mla_moe`) over the device time of every event of the
kernel in it. As in `trace_kernel`, the
events are told by the instruction's OWN name and its opcode; they decide
the time and never the credit.

`model` says how the work follows from the cell:
  gdn_decode   the live slots' states of the decode steps inside the slice,
               once read and once written in every linear layer (the
               program's counter `serve.gdn.slot_steps`, read by the
               driver when the slice opens and closes)
  mla_hybrid   the latent rows of the live tokens of the decode steps
               inside the slice (`traced_live_tokens`), once in each
               full-attention layer the configuration holds

Where the program has no such kernel or counter (the parent of the PR that
added them, or a cell of another family) there is nothing to read: None.
"""

from . import kernel_math_gdn_mla_moe as km, xplane


def read(rec, *, pattern: str, model: str, opcode: str = "custom-call"):
    if rec.trace is None:
        return None
    spent = sum(s for _, s in xplane.matching_ops(rec.trace, pattern, opcode))
    if spent <= 0:
        return None
    c, s = rec.ctx.config, rec.run.stats
    if model == "gdn_decode":
        steps = s.get("traced_gdn_slot_steps")
        if not steps or "linear_num_value_heads" not in c:
            return None
        work = km.gdn_decode_work(
            steps, c["linear_num_value_heads"], c["linear_key_head_dim"],
            c["linear_value_head_dim"])
    elif model == "mla_hybrid":
        live, layers = (s.get("traced_live_tokens"),
                        c.get("full_attention_layers"))
        if not live or not layers:
            return None
        work = (0.0, km.mla_hybrid_decode_bytes(
            live, c["kv_lora_rank"], c["qk_rope_head_dim"], len(layers)))
    else:
        raise ValueError(model)
    return 100.0 * km.roofline_seconds(*work, rec.peaks) / spent
