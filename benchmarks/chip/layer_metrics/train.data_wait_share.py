"""Share of the window spent in ``next()`` of the training loop's
``PrefetchIterator`` (harness span ``bench.next_batch``).  Moves
``train_tokens_per_s``."""


def read(rec, ctx):
    if not rec.counters.get("steps"):
        return None
    return 100.0 * rec.span_seconds("bench.next_batch") / rec.work_window_s
