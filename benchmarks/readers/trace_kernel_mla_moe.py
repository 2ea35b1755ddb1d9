"""The DeepSeek-V3 family's two kernels' shares of their rooflines, from the
device trace: the least time the chip could take for the work the
ALGORITHM needs in the traced slice (`kernel_math_mla_moe`) over the device
time of every event of the kernel in it. As in `trace_kernel`, the events
are told by the instruction's OWN name and its opcode; they decide the time
and never the credit.

`model` says how the work follows from the cell:
  moe_experts  the routed rows and the touched experts that the programs
               inside the slice reported (the program's counters
               `serve.moe.rows` / `serve.moe.experts_touched`, read by the
               driver when the slice opens and closes), through the three
               matrices of one expert. The least time is taken of the
               slice's totals; the issue's sum of per-run maxima is the
               same number wherever every run lies on one side of the
               ridge, as here (an expert's weights are read once for its
               rows, so a run is bandwidth-bound below 240 rows an expert:
               a prefill of 5,120 tokens), and larger otherwise: the
               totals never flatter.
  mla_decode   the latent rows of the live tokens of the decode steps
               inside the slice (`traced_live_tokens`), once per layer

Where the program has no such kernel or counter (the parent of the PR
that added them, or a cell of another family) there is nothing to read:
None.
"""

from . import kernel_math_mla_moe as km, xplane


def read(rec, *, pattern: str, model: str, opcode: str = "custom-call"):
    if rec.trace is None:
        return None
    spent = sum(s for _, s in xplane.matching_ops(rec.trace, pattern, opcode))
    if spent <= 0:
        return None
    c, s = rec.ctx.config, rec.run.stats
    if model == "moe_experts":
        rows, touched = s.get("traced_moe_rows"), s.get("traced_moe_experts")
        if not rows or not touched:
            return None
        least = km.roofline_seconds(*km.moe_experts_work(
            rows, touched, c["hidden_size"], c["moe_intermediate_size"]),
            rec.peaks)
    elif model == "mla_decode":
        live = s.get("traced_live_tokens")
        if not live:
            return None
        least = km.roofline_seconds(0.0, km.mla_decode_bytes(
            live, c["kv_lora_rank"], c["qk_rope_head_dim"],
            c["num_hidden_layers"]), rec.peaks)
    else:
        raise ValueError(model)
    return 100.0 * least / spent
