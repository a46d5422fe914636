"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, from the profiler's
trace (``tracereduce``), averaged over the cell's chips.  Reads
``device.idle_share.<kind>`` of every kind of cell; each moves its cell's
end-to-end metric."""


def read(rec, ctx):
    t = rec.trace_reduction
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
