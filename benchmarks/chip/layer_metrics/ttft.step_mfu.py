"""Prefill model FLOPs of every request in the window, over the window
(less any time spent writing the trace) times the chips times the bf16
peak: the whole request's share of the peak, beside ``prefill.mfu``.
Moves ``ttft_p90_s``."""


def read(rec, ctx):
    shapes = rec.counters.get("prefills")
    if not shapes:
        return None
    flops = sum(ctx.work.forward_flops(ctx.model, p["batch"], p["prompt_len"])
                for p in shapes)
    return 100.0 * flops / (rec.work_window_s * ctx.chips *
                            ctx.peak["bf16_flops_per_s"])
