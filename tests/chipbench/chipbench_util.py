"""Helpers of the chip benchmark's CPU tests: the benchmark's own modules
on ``sys.path``, and a throwaway checkout (``BENCHMARK.json`` and a
benchmark directory) holding the benchmark's cells at a tiny size."""

import copy
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

# widths that a CPU test can hold; every other key of a configuration
# stays as its file has it
TINY_MODEL = {
    "zamba2-2.7b": dict(num_layers=4, attn_every=2, d_model=64, n_heads=4,
                        n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
                        ssm_state=16, ssm_headdim=16),
    "mamba2-1.3b-16l": dict(num_layers=2, d_model=64, vocab_size=256,
                            ssm_state=16, ssm_headdim=16),
}
TINY_PARAMS = {
    "zamba2.gen": dict(batch=4, prompt_len=16, new_tokens=6, cache_len=32,
                       check_per_slot=1),
    "zamba2.ttft-4k": dict(prompt_len=64, cache_len=64, pool=16),
    "mamba2.train-4k": dict(seq_len=64, global_batch=2),
}
# limits at the tiny size, set between the program's readings and the
# fp8 control's there (seeds 5 and 11-15, CPU): the program reads at most
# 0.004 on the gaps of served tokens and 0.0002 on loss_gap, the control
# 0.016-0.034 and 0.0015-0.0024 on seed 5, which the control test uses
TINY_LIMITS = {"served_gap": 0.012, "sampled_gap": 0.012, "loss_gap": 0.001,
               "grad_gap": 0.05, "change_gap": 0.5}


class TinyCheckout:
    """A directory laid out as a checkout: ``BENCHMARK.json`` at its root
    and a copy of the benchmark's data and name-found files, cut to tiny
    sizes.  ``add_cell`` adds a cell the way a later change would: new
    files and a new entry, no edit to the harness."""

    def __init__(self, root: Path):
        self.root = root
        self.bench_dir = root / "benchmarks" / "chip"
        for d in ("traffic", "layer_metrics", "cells", "configs"):
            shutil.copytree(BENCH / d, self.bench_dir / d)
        self.bench = json.loads((REPO / "BENCHMARK.json").read_text())
        for name, over in TINY_MODEL.items():
            path = self.bench_dir / "configs" / f"{name}.json"
            cfg = json.loads(path.read_text())
            cfg["model"].update(over)
            path.write_text(json.dumps(cfg))
        for name, over in TINY_PARAMS.items():
            path = self.bench_dir / "cells" / f"{name}.json"
            cell = json.loads(path.read_text())
            cell["params"].update(over)
            cell["limits"] = {k: TINY_LIMITS[k] for k in cell["limits"]}
            path.write_text(json.dumps(cell))
        peaks = json.loads((BENCH / "peaks.json").read_text())
        peaks["devices"]["cpu"] = dict(peaks["devices"]["TPU v5 lite"])
        self.peaks = root / "peaks.json"
        self.peaks.write_text(json.dumps(peaks))
        self.save()

    def save(self):
        (self.root / "BENCHMARK.json").write_text(json.dumps(self.bench))

    def add_cell(self, name: str, like: str, **params):
        """A new cell: a copy of ``like``'s file with other parameters."""
        cell = json.loads((self.bench_dir / "cells" /
                           f"{like}.json").read_text())
        cell["params"].update(params)
        (self.bench_dir / "cells" / f"{name}.json").write_text(
            json.dumps(cell))
        entry = copy.deepcopy(next(w for w in self.bench["workloads"]
                                   if w["name"] == like))
        entry["name"] = name
        self.bench["workloads"].append(entry)
        for m in self.bench["end_to_end"] + self.bench["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
        self.save()

    def where(self):
        return dict(root=self.root, bench_dir=self.bench_dir,
                    require_tpu=False, peaks_table=self.peaks)

    def run(self, workload: str, seed: int = 3_000_000_001,
            seconds: float = 0.5, capsys=None):
        """run.main on the CPU; returns (exit code, result line or None)."""
        import run
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0"],
                      **self.where())
        out = capsys.readouterr().out.strip().splitlines() if capsys else []
        return rc, (json.loads(out[-1]) if out else None)
