"""Share of the time inside ``Engine.run_batch`` that is neither the
prefill nor a decode step (``Engine.prefill_s``, ``Engine.decode_step_s``,
host clock around ``block_until_ready``): the Engine's host sampling and
bookkeeping.  Moves ``gen_tokens_per_s``."""


def read(rec, ctx):
    c = rec.counters
    if not c.get("run_batch_s"):
        return None
    device = sum(c["prefill_s"]) + sum(c["decode_s"])
    return 100.0 * (1.0 - device / sum(c["run_batch_s"]))
