"""Device time by block scope and idle gaps by the program's host spans
(``scopes``): scope paths, a synthetic trace of two programs with nested
``while`` ops, the op_name metadata of two traces recorded on a TPU v5e
(the unscoped sample and a scoped hybrid decode), and the
``trace_scopes.py`` tool end to end on the CPU."""

import json
from pathlib import Path

import pytest

import chipbench_util  # noqa: F401  (puts the benchmark on sys.path)
import scopes
import tracereduce as tr

DATA = Path(__file__).parent / "data"


def test_scope_path_strips_wrappers_and_keeps_order():
    assert scopes.scope_path(
        "jit(train_step)/transpose(jvp(checkpoint))/transpose(jvp(ssm))"
        "/ssd/dot_general") == ("ssm", "ssd")
    assert scopes.scope_path("jit(decode_step)/attention/dot_general") == (
        "attention",)
    assert scopes.scope_path("jit(f)/while/body/closed_call/mul") == ()
    assert scopes.scope_path(None) == ()


def _synthetic():
    """Two programs: jit_prefill (id 11) once, jit_decode_step (id 22)
    twice; each decode runs a while loop holding an attention fusion and
    an inner while loop with an SSD fusion."""
    ns = 1e9
    prefill = [("%fusion.1 = f32[8] fusion(x)", 0.10, 0.20),
               ("%copy.2 = f32[8] copy(y)", 0.20, 0.30)]
    decode = [("%while.3 = f32[8] while(x)", 0.40, 0.60),
              ("%fusion.4 = f32[8] fusion(x)", 0.42, 0.50),
              ("%while.5 = f32[8] while(y)", 0.50, 0.58),
              ("%fusion.6 = f32[8] fusion(z)", 0.51, 0.55)]
    ops = prefill + decode + [(n, s + 0.3, e + 0.3) for n, s, e in decode]
    chips = {0: [(n, s * ns, e * ns) for n, s, e in ops]}
    modules = {0: [("jit_prefill(11)", "bench.prefill", 0.10 * ns,
                    0.30 * ns),
                   ("jit_decode_step(22)", "bench.decode", 0.40 * ns,
                    0.60 * ns),
                   ("jit_decode_step(22)", "bench.decode", 0.70 * ns,
                    0.90 * ns)]}
    names = {
        (11, prefill[0][0]): "jit(prefill)/ssm/ssd/dot_general",
        (22, decode[0][0]): "jit(decode_step)/while",
        (22, decode[1][0]): "jit(decode_step)/while/body/attention/dot",
        (22, decode[2][0]): "jit(decode_step)/while/body/ssm/while",
        (22, decode[3][0]):
            "jit(decode_step)/while/body/transpose(jvp(ssm))/ssd/dot"}
    spans = [("bench.run_batch", 0.0, 1.0), ("engine.run_batch", 0.001, 1.0),
             ("bench.prefill", 0.09, 0.10), ("engine.sample", 0.30, 0.39),
             ("bench.decode", 0.39, 0.40), ("engine.decode", 0.39, 0.61),
             ("host.gc", 0.62, 0.68), ("bench.decode", 0.69, 0.70),
             ("bench.stop_trace", 1.0, 3.0)]
    spans = [(n, s * ns, e * ns) for n, s, e in spans]
    return chips, modules, spans, names


def test_reduce_synthetic_scopes_and_gaps():
    chips, modules, spans, names = _synthetic()
    r = scopes.reduce(chips, modules, spans, names)
    approx = pytest.approx
    assert r["scopes"]["bench.prefill"] == {"ssd": approx(0.1),
                                            "unscoped": approx(0.1)}
    # each decode: the outer loop's own 0.04 s, attention 0.08, the inner
    # loop's own 0.04 (ssm) and its SSD fusion 0.04
    assert r["scopes"]["bench.decode"] == {
        "unscoped": approx(0.08), "attention": approx(0.16),
        "ssm": approx(0.08), "ssd": approx(0.08)}
    assert r["scopes_inclusive"]["bench.decode"]["ssm"] == approx(0.16)
    assert r["scopes_inclusive"]["bench.decode"]["ssd"] == approx(0.08)
    # the split is a partition of the programs' op self time
    assert sum(r["scopes"]["bench.decode"].values()) == approx(0.4)
    assert scopes.shares(r["scopes"]["bench.prefill"]) == {
        "ssd": approx(50.0), "unscoped": approx(50.0)}
    assert r["device_ops"][0] == ["%fusion.4 fusion [attention]",
                                  approx(0.16)]
    assert ["%copy.2 copy [unscoped]", approx(0.1)] in r["device_ops"]
    # four gaps of 0.1 s: before the prefill, in the sampling between the
    # programs, in a collection, after the last decode
    assert sorted(name for name, _ in r["idle_gaps"]) == [
        "engine.run_batch", "engine.run_batch", "engine.sample", "host.gc"]
    assert all(s == approx(0.1) for _, s in r["idle_gaps"])
    assert r["span_seconds"]["engine.sample"] == approx(0.09)
    assert "bench.stop_trace" not in r["span_seconds"]


def test_reduce_leaves_tracereduce_numbers_alone():
    """The same gaps as ``tracereduce.reduce`` finds, only labelled by the
    innermost of every host span; its own numbers see bench spans only."""
    chips, modules, spans, names = _synthetic()
    bench = [s for s in spans if s[0].startswith("bench.")]
    programs = {0: [(span, s, e) for _, span, s, e in modules[0]]}
    base = tr.reduce(chips, bench, programs)
    r = scopes.reduce(chips, modules, spans, names)
    assert [g[1] for g in r["idle_gaps"]] == [g[1] for g in
                                              base["idle_gaps"]]
    assert {g[0] for g in base["idle_gaps"]} == {"bench.run_batch"}
    assert base["programs"]["bench.decode"]["n"] == 2
    assert base["span_counts"] == {"bench.run_batch": 1, "bench.prefill": 1,
                                   "bench.decode": 2}
    assert base["window_s"] == pytest.approx(1.0)
    assert base["busy_s"] == pytest.approx(0.6)


def test_reduce_puts_ops_outside_programs_to_outside():
    ns = 1e9
    chips = {0: [("%fusion.9 = f32[] fusion()", 0.1 * ns, 0.2 * ns)]}
    r = scopes.reduce(chips, {0: []}, [("bench.step", 0, ns)], {})
    assert r["scopes"] == {tr.OUTSIDE: {"unscoped": pytest.approx(0.1)}}
    assert scopes.reduce({}, {}, [("bench.step", 0, ns)], {}) == {}


def test_op_names_of_recorded_v5e_trace():
    """The matmul fusion of the unscoped v5e sample carries jax's op_name
    under its program's id; copies XLA added carry none."""
    got = scopes.op_names(str(DATA / "trace_sample.xplane.pb"))
    assert set(got.values()) == {"jit(<lambda>)/dot_general"}
    (pid, text), = got
    assert text.startswith("%fusion = ")
    chips, mods, spans, names = scopes.load(
        str(DATA / "trace_sample.xplane.pb"))
    assert {m[0] for m in mods[0]} == {f"jit__lambda({pid})"}
    r = scopes.reduce(chips, mods, spans, names)
    split = r["scopes"]["bench.run_batch"]
    assert set(split) == {"unscoped"}
    assert sum(split.values()) == pytest.approx(
        tr.reduce(*tr.load(str(DATA / "trace_sample.xplane.pb")))["busy_s"],
        rel=0.05)


def test_recorded_scoped_v5e_trace():
    """A scoped hybrid decode recorded on a TPU v5e: zamba2 cut to 4
    Mamba2 layers of d 64 with the shared attention block after every 2,
    through ``Engine.run_batch``, two batches of 2 requests (one greedy,
    one sampled) with 8-token prompts and 4 new tokens each, in
    ``bench.run_batch`` spans with 2 ms ``bench.next_batch`` sleeps between
    and ``bench.prefill``/``bench.decode`` around the Engine's programs as
    ``harness.annotate`` puts them.  The file keeps the device plane's
    "XLA Modules" and "XLA Ops" lines with only the ``program_id`` and
    ``tf_op`` stats of their event metadata, and on the host only the
    ``bench.*``, ``engine.*`` and launch events: both reductions read it
    as they read the whole trace."""
    path = str(DATA / "trace_scoped.xplane.pb")
    chips, mods, spans, names = scopes.load(path)
    assert {m[0].partition("(")[0] for m in mods[0]} >= {
        "jit_prefill", "jit_decode_step"}
    r = scopes.reduce(chips, mods, spans, names)
    decode = r["scopes_inclusive"]["bench.decode"]
    assert min(decode[k] for k in ("ssm", "ssd", "attention", "mlp",
                                   "unscoped")) > 0
    assert decode["ssd"] < decode["ssm"]
    assert set(r["scopes"]["bench.prefill"]) == {
        "embed", "ssm", "ssd", "attention", "mlp", "lm_head", "unscoped"}
    base = tr.reduce(*tr.load(path))
    # three decode steps a batch; the scope split covers the decode
    # programs' ops, which lie inside their executions
    assert base["programs"]["bench.decode"]["n"] == 6
    assert 0.5 < sum(r["scopes"]["bench.decode"].values()) / \
        base["programs"]["bench.decode"]["s"] <= 1.0
    labels = {name for name, _ in r["idle_gaps"]}
    assert {"engine.sample", "engine.logits_to_host"} <= labels
    assert {name for name, _ in base["idle_gaps"]} <= {
        "bench.run_batch", "bench.next_batch"}
    assert [g[1] for g in r["idle_gaps"]] == [g[1] for g in
                                              base["idle_gaps"]]


def test_trace_scopes_tool_on_cpu(checkout, capsys, tmp_path):
    """The tool's flow at a tiny size: set-up, a traced window with the
    collector hooked, the trace kept.  The CPU trace has no TPU plane, so
    only its host spans are read: the Engine's own spans are there."""
    import trace_scopes
    keep = tmp_path / "kept.xplane.pb"
    rc = trace_scopes.main(["--workload", "zamba2.gen", "--seed",
                            "3000000001", "--keep", str(keep)],
                           **checkout.where())
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["workload"] == "zamba2.gen"
    assert line["spans"]["bench.run_batch"][0] >= 1
    names = {s[0] for s in scopes.load(str(keep))[2]}
    assert {"bench.run_batch", "engine.run_batch", "engine.prefill",
            "engine.decode", "engine.logits_to_host",
            "engine.sample"} <= names
