"""Run one cell of the chip benchmark and print its result line.

    python benchmarks/chip/run.py --workload zamba2.gen --seed 7 \\
        --seconds 30 --trace 0

Everything is found by name: the cell in ``BENCHMARK.json`` and
``cells/<cell>.json``, its configuration in ``configs/<config>.json``,
its traffic kind in ``traffic/<kind>.py``, each per-layer metric in
``layer_metrics/<metric>.py``, the model family's work counts and
reference in ``work/<family>.py`` and ``reference/<family>.py``, the
chip's peaks in ``peaks.json``.  A new cell, configuration, traffic kind
or metric is a new file and a new entry, not an edit here.

The run: weights and inputs from ``--seed``; set-up (imports, weights,
compile or cache load, warm-up of the cell's own shapes) ends where the
window of ``--seconds`` begins; nothing compiles inside it.  With
``--trace 1`` the profiler records the window's first units of work and
the result holds the per-layer metrics, else the end-to-end ones.  After
the window the program's state is freed and the plain reference decides
``correct``.  The last stdout line is the result, one JSON object; the
numbers compared are also the last lines of stderr.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
from harness import Check, Recorder, load_json, load_module  # noqa: E402


class SetupError(RuntimeError):
    """The run cannot start: unknown cell, missing file, no chip."""


@dataclasses.dataclass
class Context:
    """What a traffic module and a metric reader are handed."""
    name: str
    cell: Dict[str, Any]
    config: Dict[str, Any]
    seed: int
    rec: Recorder
    devices: List[Any]
    cfg: Any = None                    # the program's ModelConfig
    work: Any = None                   # work/<family>.py
    reference: Any = None              # reference/<family>.py
    peak: Optional[Dict[str, float]] = None
    bench_dir: Optional[Path] = None
    weights_s: float = 0.0             # drawing weights, set-up included

    @property
    def model(self) -> Dict[str, Any]:
        return self.config["model"]

    @property
    def chips(self) -> int:
        return len(self.devices)

    @property
    def seed_31(self) -> int:
        """The seed folded into 31 bits, for APIs that take an int32."""
        return self.seed % 2**31

    def weights(self, out_shardings=None):
        """The cell's weights, drawn from the seed on the device in the
        program's parameter layout (its ``init``'s shapes, nothing made)."""
        import jax
        import jax.numpy as jnp
        import weights
        from repro.models import transformer as tfm
        from repro.models.modules import split
        t0 = time.perf_counter()
        shapes = jax.eval_shape(
            lambda k: split(tfm.init(k, self.cfg, dtype=jnp.bfloat16))[0],
            jax.random.PRNGKey(0))
        w = jax.block_until_ready(weights.make(
            shapes, self.seed, self.config["init"], self.model,
            out_shardings))
        self.weights_s += time.perf_counter() - t0
        return w

    def reference_weights(self, dtype, out_shardings=None):
        """The same weights, drawn by the same compiled program as the
        program's (same ``out_shardings``), then cast to ``dtype``."""
        import jax
        return jax.jit(lambda t: jax.tree.map(lambda x: x.astype(dtype), t),
                       donate_argnums=0)(self.weights(out_shardings))

    def reference_logits(self, sequences, positions, rounding: str):
        """Reference logits at ``positions`` of each token sequence, one
        sequence at a time: [(len(positions), V) float32 numpy]."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        w = self.weights()
        rnd = self.reference.ROUNDING[rounding]
        fwd = jax.jit(lambda w, t: self.reference.forward(
            w, t, self.model, rnd, positions=positions))
        out = []
        with jax.default_matmul_precision("highest"):
            for seq in sequences:
                out.append(np.asarray(fwd(w, jnp.asarray([seq], jnp.int32))
                                      [0]))
        return out


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(name: str, root: Path, bench_dir: Path):
    """(BENCHMARK.json, its workload entry, cell file, config file)."""
    bench = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json")
    cell = load_json(bench_dir / "cells" / f"{name}.json")
    config = load_json(bench_dir / "configs" / f"{entry['config']}.json")
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise SetupError(f"{name}: cell file says {key}={cell[key]!r}, "
                             f"BENCHMARK.json {entry[key]!r}")
    return bench, entry, cell, config


def reader_path(bench_dir: Path, metric: str) -> Path:
    """``layer_metrics/<metric>.py``, or else the reader of the longest
    dotted prefix of the name: ``device.idle_share.py`` reads
    ``device.idle_share.gen`` and ``device.idle_share.train`` alike."""
    parts = metric.split(".")
    for n in range(len(parts), 0, -1):
        path = bench_dir / "layer_metrics" / (".".join(parts[:n]) + ".py")
        if path.is_file():
            return path
    raise FileNotFoundError(f"no reader for {metric!r} in {bench_dir}")


def metrics_for(bench, name: str, kind: str) -> List[Dict[str, Any]]:
    """The cell's metrics of ``kind`` (end_to_end or per_layer)."""
    return [m for m in bench[kind]
            if "workloads" not in m or name in m["workloads"]]


def prepare(name: str, seed: int, *, trace: bool = False, root: Path = ROOT,
            bench_dir: Optional[Path] = None, require_tpu: bool = True,
            peaks_table: Optional[Path] = None):
    """(BENCHMARK.json, the cell's Context, its traffic Run).  Raises
    SetupError where the cell cannot run here."""
    bench_dir = bench_dir or root / "benchmarks" / "chip"
    try:
        bench, entry, cell, config = load_cell(name, root, bench_dir)
    except (FileNotFoundError, KeyError) as e:
        raise SetupError(f"{name}: {e}") from e
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SetupError(f"needs a TPU; JAX's first device is "
                         f"{devices[0].platform!r}")
    if len(devices) < entry["chips"]:
        raise SetupError(f"{name} needs {entry['chips']} chips, JAX sees "
                         f"{len(devices)}")
    devices = devices[:entry["chips"]]
    # a fixed directory in the checkout, unless the environment names one
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from repro.configs.registry import get_config
    rec = Recorder(trace_dir=(tempfile.mkdtemp(prefix="chipbench_trace_")
                              if trace else None),
                   trace_seconds=cell.get("trace_seconds", 5.0))
    family = config["model"]["family"]
    ctx = Context(name=name, cell=cell, config=config, seed=seed, rec=rec,
                  devices=devices,
                  cfg=dataclasses.replace(get_config(config["registry"]),
                                          **config["model"]),
                  work=importlib.import_module(f"work.{family}"),
                  reference=importlib.import_module(f"reference.{family}"),
                  peak=harness.peaks(devices[0].device_kind, peaks_table),
                  bench_dir=bench_dir)
    traffic = load_module(bench_dir / "traffic" / f"{entry['traffic']}.py",
                          f"traffic_{entry['traffic']}")
    return bench, ctx, traffic.Run(ctx)


def main(argv=None, *, t_start: float = T_START, **where) -> int:
    """Run one cell; returns the exit code.  ``where`` (root, bench_dir,
    require_tpu, peaks_table) is for the CPU rehearsal tests only."""
    args = _parse(argv)
    try:
        bench, ctx, run = prepare(args.workload, args.seed,
                                  trace=bool(args.trace), **where)
    except SetupError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    rec, devices, trace_dir = ctx.rec, ctx.devices, ctx.rec.trace_dir
    bench_dir = ctx.bench_dir
    clock = harness.CompileClock()
    start_s = time.perf_counter() - t_start
    try:
        run.setup()
        setup_s = time.perf_counter() - t_start
        weights_s = ctx.weights_s
        before = clock.snapshot()
        e2e = run.window(args.seconds)
        after = clock.snapshot()
        device = harness.device_info(devices)
        if trace_dir:
            rec.trace_reduction = reduce_trace(trace_dir)
            if not rec.trace_reduction.get("busy_s"):
                raise SetupError("the trace holds no device operation")
        run.free()
        gc.collect()
        checks: List[Check] = run.check()
        checks.append(Check("failed_units", run.failed, 0))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    window_line = {"window": {
        "seconds": rec.window_s, "compiles": after["compiles"] -
        before["compiles"], "compile_s": after["compile_s"] -
        before["compile_s"], "setup_compile_s": before["compile_s"],
        "setup_cache_hits": before["cache_hits"],
        "setup_cache_misses": before["cache_misses"],
        # set-up in parts: imports and the runtime's start; drawing the
        # weights; the rest (engine or step, warm-up); compile_s above
        # lies inside the last two
        "setup_parts": {"start_s": start_s, "weights_s": weights_s,
                        "rest_s": setup_s - start_s - weights_s},
        "spans": rec.span_summary()}}
    print(json.dumps(window_line), flush=True)

    result: Dict[str, Any] = {
        "correct": harness.correct(checks),
        "attempted": run.attempted, "failed": run.failed}
    if args.trace:
        t = rec.trace_reduction
        metrics = {}
        for m in metrics_for(bench, args.workload, "per_layer"):
            reader = load_module(reader_path(bench_dir, m["name"]),
                                 "metric_" + m["name"])
            value = reader.read(rec, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    else:
        values = dict(e2e, setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_for(bench, args.workload, "end_to_end")}
        result["device"] = device
    result["check"] = harness.checks_line(checks)
    for c in checks:
        print(f"check {c.name} = {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def reduce_trace(trace_dir: str) -> Dict[str, Any]:
    import tracereduce
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        return {}
    return tracereduce.reduce(*tracereduce.load(str(paths[-1])))


if __name__ == "__main__":
    sys.exit(main())
