"""CPU rehearsal of the chip benchmark: every cell end to end at a tiny
size through ``run.main``, the result line's keys, the refusal without a
TPU, a cell added by files alone, and ``BENCHMARK.json`` against the
files it names."""

import json
import re

import pytest

from chipbench_util import BENCH, REPO, TINY_PARAMS

CELLS = sorted(TINY_PARAMS)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end(checkout, capsys, cell):
    rc, line = checkout.run(cell, capsys=capsys)
    assert rc == 0
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "check"]
    assert line["correct"] is True, line["check"]
    assert line["attempted"] > 0 and line["failed"] == 0
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"


def test_window_compiles_nothing(checkout, capsys):
    rc, _ = checkout.run("zamba2.ttft-4k", capsys=None)
    out = capsys.readouterr().out.strip().splitlines()
    window = json.loads(out[-2])["window"]
    assert rc == 0 and window["compiles"] == 0


def test_refuses_without_tpu(capsys):
    import run
    rc = run.main(["--workload", "zamba2.gen", "--seed", "1",
                   "--seconds", "1"])
    assert rc != 0
    assert '"correct"' not in capsys.readouterr().out


def test_unknown_workload_refused(checkout, capsys):
    rc, line = checkout.run("no.such.cell", capsys=capsys)
    assert rc != 0 and line is None


def test_cell_added_by_files_alone(checkout, capsys):
    """A new cell is a data file and entries: no harness file changes."""
    checkout.add_cell("zamba2.gen-short", "zamba2.gen", prompt_len=8,
                      new_tokens=3)
    rc, line = checkout.run("zamba2.gen-short", capsys=capsys)
    assert rc == 0 and line["correct"] is True
    assert "gen_tokens_per_s" in line["metrics"]


def test_traffic_kind_and_metric_added_by_files_alone(checkout):
    """A new traffic kind and a new per-layer reader are new files."""
    import run
    (checkout.bench_dir / "traffic" / "noop.py").write_text(
        "from harness import Check\n"
        "class Run:\n"
        "    attempted = failed = 0\n"
        "    def __init__(self, ctx): self.ctx = ctx\n"
        "    def setup(self): pass\n"
        "    def window(self, s):\n"
        "        self.ctx.rec.open_window(); self.ctx.rec.close_window()\n"
        "        self.ctx.rec.counters['noop'] = 1.0\n"
        "        return {'noop_per_s': 1.0}\n"
        "    def free(self): pass\n"
        "    def check(self): return [Check('x', 0.0, 0.0)]\n")
    (checkout.bench_dir / "layer_metrics" / "noop.share.py").write_text(
        "def read(rec, ctx):\n    return rec.counters.get('noop')\n")
    cell = {"config": "zamba2-2.7b", "traffic": "noop", "chips": 1,
            "params": {}, "limits": {}}
    (checkout.bench_dir / "cells" / "zamba2.noop.json").write_text(
        json.dumps(cell))
    b = checkout.bench
    b["workloads"].append({"name": "zamba2.noop", "config": "zamba2-2.7b",
                           "traffic": "noop", "chips": 1, "why": "test"})
    b["end_to_end"].append({"name": "noop_per_s", "unit": "1/s",
                            "better": "higher", "bound": 0.01,
                            "source": "host_clock",
                            "workloads": ["zamba2.noop"]})
    b["per_layer"].append({"name": "noop.share", "unit": "%",
                           "better": "higher", "source": "program_counter",
                           "layer": "none", "moves": "noop_per_s",
                           "workloads": ["zamba2.noop"]})
    checkout.save()
    _, ctx, r = run.prepare("zamba2.noop", 5, **checkout.where())
    r.setup()
    assert r.window(0.0) == {"noop_per_s": 1.0}
    readers = [m["name"] for m in run.metrics_for(b, "zamba2.noop",
                                                  "per_layer")]
    assert readers == ["noop.share"]
    mod = run.load_module(checkout.bench_dir / "layer_metrics" /
                          "noop.share.py", "m")
    assert mod.read(ctx.rec, ctx) == 1.0


def test_reader_found_by_longest_prefix(tmp_path):
    import run
    d = tmp_path / "layer_metrics"
    d.mkdir()
    for name in ("a.b.py", "a.b.c.py"):
        (d / name).write_text("")
    assert run.reader_path(tmp_path, "a.b.c").name == "a.b.c.py"
    assert run.reader_path(tmp_path, "a.b.d").name == "a.b.py"
    with pytest.raises(FileNotFoundError):
        run.reader_path(tmp_path, "x.y")


def test_benchmark_json_names_its_files():
    import run
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    for p in b["paths"]:
        assert (REPO / p).is_dir()
    for c in b["configs"]:
        assert NAME.match(c["name"])
        assert (REPO / c["file"]).is_file()
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        cell = json.loads((BENCH / "cells" / f"{w['name']}.json").read_text())
        assert [cell[k] for k in ("config", "traffic", "chips")] == \
            [w[k] for k in ("config", "traffic", "chips")]
        assert (BENCH / "traffic" / f"{w['traffic']}.py").is_file()
        assert len(w["why"]) <= 200
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert set(m.get("workloads", [])) <= cells
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert run.reader_path(BENCH, m["name"]).is_file()
        moved = next(e for e in b["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for w in cells:     # each cell reports setup_s, another e2e, a layer
        assert sum(w in m.get("workloads", [w]) for m in b["end_to_end"]) > 1
        assert any(w in m["workloads"] for m in b["per_layer"])
