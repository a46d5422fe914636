"""From the profiler's ``.xplane.pb`` to the numbers the per-layer readers
and the result's ``breakdown`` take: device busy time and idle share, the
device operations that took most time (by self time: a loop's
own time leaves out the ops of its body), the longest idle gaps labelled by
the harness span open on the host at the time, collective time, and the
device time of the programs launched inside each harness span (a
program's executions on the device's modules line, put to the span that
was open on the host when most of them were launched).

Intervals are (start_ns, end_ns).  A chip's busy time is the union of its
op intervals inside the window, so overlapping or nested ops count once.
The window is the stretch of the trace covered by the harness's own spans
(``bench.*``), which bound the units of work the trace holds.
"""

from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LAUNCH = "PJRT_LoadedExecutable_Execute"
SPAN_PREFIX = "bench."
OUTSIDE = "outside bench spans"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"allreduce|allgather|reducescatter|alltoall", re.I)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping and nested intervals; sorted, disjoint."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of [lo, hi] between the merged busy intervals."""
    out, t = [], lo
    for s, e in union(clip(busy, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The innermost span (latest start) open at the gap's midpoint."""
    mid = (gap[0] + gap[1]) / 2
    open_ = [(s, name) for name, s, e in spans if s <= mid <= e]
    return max(open_)[1] if open_ else OUTSIDE


OPCODE = re.compile(r"\s([a-z][\w-]*)\(")


def short_name(hlo: str) -> str:
    """'%fusion.634 = bf16[...] fusion(...)' -> '%fusion.634 fusion': the
    op's name and its opcode, without the shapes."""
    name, _, rest = hlo.partition(" = ")
    m = OPCODE.search(rest)
    return f"{name} {m.group(1)}" if m else name[:80]


def self_times(ops: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float]]:
    """(name, self time) of each op of one line: its duration less that of
    the ops nested directly inside it.  The ops line holds a ``while``
    loop and the ops of its body alike, so without this the loops that
    contain the work would top the list."""
    out: List[list] = []
    stack: List[list] = []                      # [name, start, end, self]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            out.append(stack.pop())
        if stack and e <= stack[-1][2]:
            stack[-1][3] -= e - s
        stack.append([name, s, e, e - s])
    out.extend(stack)
    return [(name, self_) for name, _, _, self_ in out]


def top(named: Sequence[Tuple[str, float]], n: int = 10):
    """Seconds per name, the n largest, as [[name, seconds], ...]."""
    acc: Dict[str, float] = {}
    for name, sec in named:
        acc[name] = acc.get(name, 0.0) + sec
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def clock_shift(launches: Sequence[float], starts: Sequence[float]) -> float:
    """The least shift of a chip's clock that puts no program's start on
    the device before the host launched it (the i-th launch runs the i-th
    program): the device's clock runs about a millisecond behind the
    host's in a v5e trace.  Where the counts differ the pairing is off
    somewhere, and the median lead stands in for the largest."""
    lead = sorted(h - d for h, d in zip(sorted(launches), sorted(starts)))
    if not lead:
        return 0.0
    return max(0.0, lead[-1] if len(launches) == len(starts)
               else lead[len(lead) // 2])


def program_spans(modules: Sequence[Tuple[str, float, float]],
                  launches: Sequence[float],
                  spans: Sequence[Tuple[str, float, float]]
                  ) -> List[Tuple[str, float, float]]:
    """[(span, start, end)] of each program execution (``modules``, on the
    host's clock): the span that was open when the host launched it.  An
    execution is put to the last launch at or before its start, and all
    executions of one program to the span that most of them fall in, so
    that one launch paired wrongly moves nothing."""
    launches = sorted(launches)
    votes: Dict[str, collections.Counter] = {}
    for name, s, _ in modules:
        i = bisect.bisect_right(launches, s) - 1
        at = launches[i] if i >= 0 else None     # launched before the trace
        votes.setdefault(name, collections.Counter())[
            label((at, at), spans) if at is not None else OUTSIDE] += 1
    span_of = {name: c.most_common(1)[0][0] for name, c in votes.items()}
    return [(span_of[name], s, e) for name, s, e in modules]


def load(path: str):
    """{chip index: [(op name, start_ns, end_ns)]} on the host's clock,
    the harness spans [(name, start_ns, end_ns)], and {chip index:
    [(span, start_ns, end_ns)]} of each program execution
    (``program_spans``) of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    chips: Dict[int, list] = {}
    modules: Dict[int, list] = {}
    spans, launches = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                chips.setdefault(int(m.group(1)), []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif m and line.name == MODULES_LINE:
                modules.setdefault(int(m.group(1)), []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name == LAUNCH:
                        launches.append(e.start_ns)
    programs = {}
    for c, ops in chips.items():
        mods = modules.get(c, [])
        d = clock_shift(launches, [s for _, s, _ in mods])
        chips[c] = [(name, s + d, e + d) for name, s, e in ops]
        programs[c] = program_spans([(name, s + d, e + d)
                                     for name, s, e in mods], launches, spans)
    return chips, spans, programs


def reduce(chips: Dict[int, list], spans: Sequence[Tuple[str, float, float]],
           programs: Dict[int, list] = None, n: int = 10) -> Dict:
    """The trace's numbers, in seconds.  ``busy_s`` is averaged over the
    chips; ops, gaps, collectives and programs are read on the first
    chip.  ``programs`` is {span: {"n": executions, "s": device seconds}}
    and ``span_counts`` {span: how many the trace holds}."""
    work = [s for s in spans if s[0] != SPAN_PREFIX + "stop_trace"]
    if not chips or not work:
        return {}
    lo, hi = min(s for _, s, _ in work), max(e for _, _, e in work)
    busy = {c: union(clip([(s, e) for _, s, e in ops], lo, hi))
            for c, ops in chips.items()}
    first = min(chips)
    ops0 = [(name, max(s, lo), min(e, hi)) for name, s, e in chips[first]
            if e > lo and s < hi]
    idle = sorted(gaps(busy[first], lo, hi), key=lambda g: g[0] - g[1])[:n]
    coll = [(s, e) for name, s, e in ops0 if COLLECTIVE.search(name)]
    ns = 1e-9
    progs: Dict[str, Dict[str, float]] = {}
    for span, s, e in (programs or {}).get(first, []):
        if e > lo and s < hi:
            p = progs.setdefault(span, {"n": 0, "s": 0.0})
            p["n"] += 1
            p["s"] += (min(e, hi) - max(s, lo)) * ns
    return {
        "programs": progs,
        "span_counts": dict(collections.Counter(name for name, _, _ in work)),
        "window_s": (hi - lo) * ns,
        "busy_s": sum(total(b) for b in busy.values()) / len(busy) * ns,
        "collective_s": total(coll) * ns,
        "device_ops": [[k, v * ns] for k, v in
                       top([(short_name(name), t)
                            for name, t in self_times(ops0)], n)],
        "idle_gaps": [[label(g, work), (g[1] - g[0]) * ns] for g in idle],
    }
