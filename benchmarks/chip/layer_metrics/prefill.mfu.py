"""Prefill model FLOPs (``work.forward_flops``) of the traced prefills,
over the device time of the prefill programs (launched inside
``bench.prefill``, from the trace) times the chip's bf16 peak.  Moves
``ttft_p90_s``."""


def read(rec, ctx):
    t = rec.trace_reduction or {}
    prog = t.get("programs", {}).get("bench.prefill")
    shapes = rec.counters.get("prefills")
    if not prog or not prog["s"] or not shapes:
        return None
    flops = ctx.work.forward_flops(ctx.model, shapes[0]["batch"],
                                   shapes[0]["prompt_len"])
    return 100.0 * flops * prog["n"] / (prog["s"] *
                                        ctx.peak["bf16_flops_per_s"])
