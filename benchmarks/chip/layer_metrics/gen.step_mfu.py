"""Model FLOPs of everything the window generated (each batch's prefill
and decode steps, ``work``), over the window (less any time spent writing
the trace) times the chip's bf16 peak.  The whole serving step's share of
the peak, beside ``decode.hbm_roofline_share``.  Moves
``gen_tokens_per_s``."""


def read(rec, ctx):
    c = rec.counters
    if not c.get("batches"):
        return None
    m, w = ctx.model, ctx.work
    flops = sum(w.forward_flops(m, b["batch"], b["prompt_len"]) +
                sum(w.decode_flops(m, b["batch"], b["prompt_len"] + k)
                    for k in range(b["decode_steps"]))
                for b in c["batches"])
    return 100.0 * flops / (rec.work_window_s * ctx.chips *
                            ctx.peak["bf16_flops_per_s"])
