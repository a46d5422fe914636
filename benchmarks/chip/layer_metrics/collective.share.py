"""Share of the device's busy time spent in collectives (all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute), from the
profiler's trace (``tracereduce``: ``collective_s`` is read on the first
chip, ``busy_s`` averaged over the chips).  Reads
``collective.share.<kind>`` of every kind of cell; each moves its cell's
end-to-end metric.  A cell on one chip runs no collective and reads 0."""


def read(rec, ctx):
    t = rec.trace_reduction
    if not t or not t.get("busy_s"):
        return None
    return 100.0 * t["collective_s"] / t["busy_s"]
