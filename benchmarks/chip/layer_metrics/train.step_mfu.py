"""Forward and backward model FLOPs of every step in the window
(``work.train_flops``; recomputation not counted), over the window (less
any time spent writing the trace) times the chips times the bf16 peak.
Moves ``train_tokens_per_s``."""


def read(rec, ctx):
    c = rec.counters
    if not c.get("steps"):
        return None
    p = ctx.cell["params"]
    flops = c["steps"] * ctx.work.train_flops(ctx.model, p["global_batch"],
                                              p["seq_len"])
    return 100.0 * flops / (rec.work_window_s * ctx.chips *
                            ctx.peak["bf16_flops_per_s"])
