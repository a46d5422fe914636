"""Device time by the program's block scopes, and idle gaps by the
program's own host spans: what ``tracereduce`` cannot yet say of a trace.

The model runs each block kind under a ``jax.named_scope`` (``SCOPES``).
XLA keeps the scope path in each instruction's ``op_name`` metadata, and
the profiler writes it into the trace as the ``tf_op`` stat of the op's
event metadata, keyed by the program's id, the number in the module's
name (``jit_decode_step(1234...)``).  ``ProfileData`` does not expose
event metadata, so ``op_names`` reads it from the ``.xplane.pb`` itself.

Each op on "XLA Ops" is put to the program execution that encloses it on
the same chip, and so to the ``bench.*`` span that ``tracereduce``'s vote
puts that program to.  An op's scopes are the components of its op_name
path that, stripped of wrappers such as ``jvp(...)`` or
``transpose(...)``, are scope names, outer first; an op with none is
``unscoped``.  Self times are ``tracereduce.self_times``', so a loop's body
is not counted twice.

Idle gaps are labelled with the innermost host span open at their middle
among the harness's ``bench.*`` spans and the program's ``engine.*`` and
``host.*`` spans; the window stays the ``bench.*`` spans' stretch.
Nothing here changes a number that ``tracereduce.reduce`` returns.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Iterator, Optional, Sequence, Tuple

import tracereduce as tr

SCOPES = ("embed", "ssm", "ssd", "attention", "mlp", "moe", "lm_head",
          "loss", "optimizer")
UNSCOPED = "unscoped"
HOST_PREFIXES = ("bench.", "engine.", "host.")
STOP_TRACE = tr.SPAN_PREFIX + "stop_trace"
WRAPPER = re.compile(r"^[\w.-]+\((.*)\)$")
PROGRAM_ID = re.compile(r"\((\d+)\)$")
DETAIL_CHARS = 240


def scope_path(op_name: Optional[str]) -> Tuple[str, ...]:
    """The scopes of an op_name path, outer first:
    'jit(f)/transpose(jvp(ssm))/ssd/dot_general' -> ('ssm', 'ssd')."""
    out = []
    for part in (op_name or "").split("/"):
        m = WRAPPER.match(part)
        while m:
            part = m.group(1)
            m = WRAPPER.match(part)
        if part in SCOPES:
            out.append(part)
    return tuple(out)


# -- reading event metadata from the .xplane.pb -------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} not read")
        yield key >> 3, value


def _map_value(entry) -> Optional[memoryview]:
    return next((v for f, v in _fields(entry) if f == 2), None)


def op_names(path: str) -> Dict[Tuple[int, str], str]:
    """{(program id, op text): op_name path} of every op on the trace's
    device planes that carries one.  XSpace.planes is field 1; an XPlane
    holds its name (2), event metadata (4: id -> XEventMetadata, with name
    2 and stats 5) and stat metadata (5: id -> XStatMetadata, with name
    2); an XStat holds its stat metadata id (1) and an int (3, 4), a
    string (5) or a reference to a stat metadata's name (7)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[Tuple[int, str], str] = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(v)
            elif f == 5:
                meta = dict(_fields(_map_value(v)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not tr.DEVICE_PLANE.match(name):
            continue
        for entry in events:
            text, pid, op_name = "", None, None
            for f, v in _fields(_map_value(entry)):
                if f == 2:
                    text = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    which = stat_names.get(stat.get(1))
                    if which == "program_id":
                        pid = stat.get(3, stat.get(4))
                    elif which == "tf_op":
                        value = (bytes(stat[5]).decode() if 5 in stat else
                                 stat_names.get(stat.get(7), ""))
                        # 'op_name:op_type'; jax leaves the type empty
                        op_name = value.rpartition(":")[0] or value
            if pid is not None and op_name:
                out[(pid, text)] = op_name
    return out


def load(path: str):
    """The trace as ``reduce`` takes it: {chip: [(op text, start, end)]}
    and {chip: [(module name, bench span, start, end)]} on the host's
    clock (aligned as ``tracereduce.load`` aligns them), the host spans
    [(name, start, end)] whose names start with ``HOST_PREFIXES``, and
    ``op_names``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[int, list] = {}
    mods: Dict[int, list] = {}
    spans, launches = [], []
    for plane in data.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (tr.OPS_LINE, tr.MODULES_LINE):
                into = ops if line.name == tr.OPS_LINE else mods
                into.setdefault(int(m.group(1)), []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name == tr.LAUNCH:
                        launches.append(e.start_ns)
    bench = [s for s in spans if s[0].startswith(tr.SPAN_PREFIX)]
    chips, modules = {}, {}
    for c, chip_ops in ops.items():
        ms = mods.get(c, [])
        d = tr.clock_shift(launches, [s for _, s, _ in ms])
        chips[c] = [(name, s + d, e + d) for name, s, e in chip_ops]
        shifted = [(name, s + d, e + d) for name, s, e in ms]
        modules[c] = [(name, span, s, e) for (name, _, _), (span, s, e) in
                      zip(shifted, tr.program_spans(shifted, launches,
                                                    bench))]
    return chips, modules, spans, op_names(path)


# -- the reduction ---------------------------------------------------------

def _enclosing(modules: Sequence[Tuple[str, str, float, float]],
               starts: Sequence[float], t: float):
    """The program execution running at ``t``, or None."""
    i = bisect.bisect_right(starts, t) - 1
    return modules[i] if i >= 0 and t < modules[i][3] else None


def reduce(chips: Dict[int, list], modules: Dict[int, list],
           spans: Sequence[Tuple[str, float, float]],
           names: Dict[Tuple[int, str], str], n: int = 10) -> Dict:
    """Seconds on the first chip, inside the ``bench.*`` window:

    - ``scopes``: {bench span: {innermost scope or "unscoped": self
      seconds}}, a partition of the span's programs' op time;
    - ``scopes_inclusive``: the same with each op counted toward every
      scope on its path (``ssd`` toward ``ssm`` too);
    - ``device_ops``: the ``n`` ops of most self time, each name labelled
      with its innermost scope, '%copy.67 copy [unscoped]'; and
      ``op_detail``: [label, the op's text (shapes), its op_name] of each;
    - ``idle_gaps``: the ``n`` longest gaps, each labelled with the
      innermost ``bench.*``, ``engine.*`` or ``host.*`` span;
    - ``span_seconds``: {host span: seconds} inside the window.
    """
    work = [s for s in spans if s[0].startswith(tr.SPAN_PREFIX) and
            s[0] != STOP_TRACE]
    if not chips or not work:
        return {}
    host = [s for s in spans if s[0] != STOP_TRACE]
    lo, hi = min(s for _, s, _ in work), max(e for _, _, e in work)
    first = min(chips)
    ops0 = [(name, s, e) for name, s, e in chips[first] if e > lo and s < hi]
    mods = sorted(modules.get(first, []), key=lambda m: m[2])
    starts = [m[2] for m in mods]
    ns = 1e-9
    split: Dict[str, Dict[str, float]] = {}
    inclusive: Dict[str, Dict[str, float]] = {}
    labelled, detail = [], {}
    for i, sec in tr.self_times([(i, max(s, lo), min(e, hi))
                                 for i, (_, s, e) in enumerate(ops0)]):
        text, s, _ = ops0[i]
        mod = _enclosing(mods, starts, s)
        span, op_name = tr.OUTSIDE, None
        if mod is not None:
            span = mod[1]
            pid = PROGRAM_ID.search(mod[0])
            if pid:
                op_name = names.get((int(pid.group(1)), text))
        path = scope_path(op_name)
        inner = path[-1] if path else UNSCOPED
        by = split.setdefault(span, {})
        by[inner] = by.get(inner, 0.0) + sec * ns
        by = inclusive.setdefault(span, {})
        for scope in set(path) or {UNSCOPED}:
            by[scope] = by.get(scope, 0.0) + sec * ns
        key = f"{tr.short_name(text)} [{inner}]"
        labelled.append((key, sec))
        detail.setdefault(key, [text[:DETAIL_CHARS], op_name])
    busy = tr.union(tr.clip([(s, e) for _, s, e in chips[first]], lo, hi))
    idle = sorted(tr.gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:n]
    span_s: Dict[str, float] = {}
    for name, s, e in host:
        if e > lo and s < hi:
            span_s[name] = span_s.get(name, 0.0) + (min(e, hi) -
                                                    max(s, lo)) * ns
    top = tr.top(labelled, n)
    return {
        "scopes": split,
        "scopes_inclusive": inclusive,
        "device_ops": [[k, v * ns] for k, v in top],
        "op_detail": [[k] + detail[k] for k, _ in top],
        "idle_gaps": [[tr.label(g, host), (g[1] - g[0]) * ns]
                      for g in idle],
        "span_seconds": span_s,
    }


def shares(split: Dict[str, float]) -> Dict[str, float]:
    """{scope: % of the span's op time}, from one span's ``scopes``."""
    whole = sum(split.values())
    return {k: 100.0 * v / whole for k, v in split.items()} if whole else {}
