"""The trace reduction: synthetic intervals (overlaps, nesting, gaps,
labels, collectives), the alignment of the device's clock, and a small
trace recorded on a TPU v5e (three ``bench.run_batch`` spans of four
1024x1024 bf16 matmuls each, separated by 2 ms host sleeps in
``bench.next_batch`` spans)."""

from pathlib import Path

import pytest

import chipbench_util  # noqa: F401  (puts the benchmark on sys.path)
import tracereduce as tr

SAMPLE = Path(__file__).parent / "data" / "trace_sample.xplane.pb"


def test_union_merges_overlap_and_nesting():
    got = tr.union([(5, 9), (0, 2), (1, 3), (6, 7), (9, 10), (12, 13)])
    assert got == [(0, 3), (5, 10), (12, 13)]
    assert tr.total([(0, 10), (2, 3), (5, 12)]) == 12


def test_gaps_inside_window():
    busy = [(2, 4), (3, 5), (8, 9), (11, 20)]
    assert tr.gaps(busy, 0, 12) == [(0, 2), (5, 8), (9, 11)]
    assert tr.gaps([], 0, 3) == [(0, 3)]
    assert tr.gaps([(0, 5)], 1, 4) == []


def test_label_takes_innermost_open_span():
    spans = [("bench.run_batch", 0, 100), ("bench.inner", 40, 60)]
    assert tr.label((45, 55), spans) == "bench.inner"
    assert tr.label((10, 20), spans) == "bench.run_batch"
    assert tr.label((200, 210), spans) == "outside bench spans"


def test_reduce_synthetic():
    ns = 1e9
    chips = {0: [("fusion.1", 0.1 * ns, 0.3 * ns),
                 ("fusion.1", 0.2 * ns, 0.25 * ns),     # nested
                 ("all-reduce.3", 0.5 * ns, 0.6 * ns),
                 ("fusion.2", 0.55 * ns, 0.7 * ns),     # overlaps it
                 ("fusion.9", 2.0 * ns, 3.0 * ns)],     # after the window
             1: [("fusion.1", 0.0, 0.5 * ns)]}
    spans = [("bench.step", 0.0, 0.4 * ns), ("bench.next_batch",
                                             0.4 * ns, 0.5 * ns),
             ("bench.step", 0.5 * ns, 1.0 * ns),
             ("bench.stop_trace", 1.0 * ns, 5.0 * ns)]
    r = tr.reduce(chips, spans)
    assert r["window_s"] == pytest.approx(1.0)
    # chip 0 busy 0.2 + 0.2, chip 1 busy 0.5: the mean
    assert r["busy_s"] == pytest.approx((0.4 + 0.5) / 2)
    assert r["collective_s"] == pytest.approx(0.1)
    # the nested fusion.1 counts once: the outer one's self time is 0.15
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.2)]
    gaps = dict((round(s, 6), name) for name, s in r["idle_gaps"])
    assert gaps == {0.3: "bench.step", 0.1: "bench.step",
                    0.2: "bench.next_batch"}
    assert [g[1] for g in r["idle_gaps"]] == sorted(
        (g[1] for g in r["idle_gaps"]), reverse=True)


def test_self_times_leave_out_nested_ops():
    ops = [("while.1", 0, 10), ("fusion.a", 1, 4), ("fusion.b", 5, 9),
           ("fusion.c", 6, 7), ("copy.d", 12, 13)]
    assert sorted(tr.self_times(ops)) == [
        ("copy.d", 1), ("fusion.a", 3), ("fusion.b", 3), ("fusion.c", 1),
        ("while.1", 3)]


def test_program_spans_vote_by_launch():
    spans = [("bench.run_batch", 0, 100), ("bench.decode", 10, 20),
             ("bench.decode", 50, 60)]
    launches = [11, 30, 51, 70]
    # two decode executions, one small program launched between them,
    # and one decode execution whose start lies after the next launch
    # (a wrong pairing): the vote puts it to bench.decode all the same
    modules = [("jit_a(1)", 12, 25), ("jit_b(2)", 31, 33),
               ("jit_a(1)", 52, 58), ("jit_a(1)", 71, 80)]
    got = tr.program_spans(modules, launches, spans)
    assert [g[0] for g in got] == ["bench.decode", "bench.run_batch",
                                   "bench.decode", "bench.decode"]
    assert tr.program_spans([("jit_a(1)", 5, 6)], [], spans) == [
        ("outside bench spans", 5, 6)]


def test_reduce_programs_and_span_counts():
    ns = 1e9
    chips = {0: [("fusion.1", 0.1 * ns, 0.3 * ns)]}
    spans = [("bench.run_batch", 0.0, 1.0 * ns),
             ("bench.decode", 0.1 * ns, 0.2 * ns),
             ("bench.decode", 0.5 * ns, 0.6 * ns)]
    programs = {0: [("bench.decode", 0.1 * ns, 0.3 * ns),
                    ("bench.decode", 0.5 * ns, 0.6 * ns),
                    ("bench.run_batch", 0.7 * ns, 0.75 * ns),
                    ("bench.decode", 2.0 * ns, 3.0 * ns)]}   # after
    r = tr.reduce(chips, spans, programs)
    assert r["programs"]["bench.decode"] == {"n": 2,
                                             "s": pytest.approx(0.3)}
    assert r["programs"]["bench.run_batch"]["n"] == 1
    assert r["span_counts"] == {"bench.run_batch": 1, "bench.decode": 2}


def test_reduce_without_device_ops_is_empty():
    assert tr.reduce({}, [("bench.step", 0, 1)]) == {}


def test_clock_shift():
    assert tr.clock_shift([10, 20, 30], [8, 19, 25]) == 5
    assert tr.clock_shift([10, 20], [11, 21]) == 0.0
    # counts differ: the median lead, not an off-by-one pairing's
    assert tr.clock_shift([10, 20, 30, 500], [9, 18, 27]) == 2


def test_recorded_v5e_trace():
    chips, spans, programs = tr.load(str(SAMPLE))
    assert list(chips) == [0]
    assert [s[0] for s in spans] == ["bench.run_batch", "bench.next_batch"] * 3
    r = tr.reduce(chips, spans, programs)
    # the matmuls are twelve executions of one program, all launched
    # inside bench.run_batch; a program's time holds its ops' and a little
    # more
    assert r["programs"]["bench.run_batch"]["n"] == 12
    assert list(r["programs"]) == ["bench.run_batch"]
    assert r["busy_s"] <= r["programs"]["bench.run_batch"]["s"] < \
        1.1 * r["busy_s"]
    assert r["span_counts"] == {"bench.run_batch": 3, "bench.next_batch": 3}
    # the spans cover about 10 ms; the matmuls keep the chip busy a
    # fraction of it, the 2 ms sleeps not at all
    assert 0.009 < r["window_s"] < 0.011
    assert 0 < r["busy_s"] < 0.5 * r["window_s"]
    assert r["collective_s"] == 0
    assert "fusion" in r["device_ops"][0][0]
    longest = r["idle_gaps"][:2]
    assert all(name == "bench.next_batch" and sec > 0.0015
               for name, sec in longest)
    # after alignment no op of the first span starts before the span
    first = spans[0]
    assert min(s for _, s, _ in chips[0]) >= first[1]
