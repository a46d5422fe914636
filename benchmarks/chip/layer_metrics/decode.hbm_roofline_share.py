"""The least bytes the traced decode steps had to move (``work``: weights
once, the shared block's KV over the valid positions, SSM and conv state
read and written, logits written), over the chip's HBM bandwidth times
the device time of the decode programs (the programs launched inside
``bench.decode``, from the trace).  Moves ``gen_tokens_per_s``."""


def read(rec, ctx):
    t = rec.trace_reduction or {}
    prog = t.get("programs", {}).get("bench.decode")
    traced = rec.counters.get("batches", [])[
        :t.get("span_counts", {}).get("bench.run_batch", 0)]
    steps = [(b["batch"], b["prompt_len"] + k) for b in traced
             for k in range(b["decode_steps"])]
    if not prog or not prog["s"] or not steps:
        return None
    per_step = sum(ctx.work.decode_bytes(ctx.model, batch, pos)
                   for batch, pos in steps) / len(steps)
    return 100.0 * per_step * prog["n"] / (ctx.peak["hbm_bytes_per_s"] *
                                           prog["s"])
