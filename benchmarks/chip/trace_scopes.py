"""Trace the first units of one cell's window and print where its device
time and its idle gaps go, by block scope and by host span (``scopes``).

    python3 benchmarks/chip/trace_scopes.py --workload zamba2.gen --seed 7

The cell is set up as ``run.py`` sets it up (weights from the seed,
compile or cache load, warm-up), then its traffic runs a window of the
cell's ``trace_seconds`` with the profiler on and Python's collector
written into the trace as ``host.gc`` spans.  No reference runs and no
``correct`` is decided: this reads a trace, it measures no end-to-end
metric.  The one JSON line printed holds ``tracereduce``'s numbers of the
trace beside the scope split of each ``bench.*`` span's programs (seconds
and shares, summing to the programs' op time), the top ops and the
longest idle gaps with their new labels, the host seconds of each span,
the Engine's sampling share of ``bench.run_batch`` where the cell serves,
and the collector's passes.  ``--keep <file>`` copies the trace there.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, List

import run as runmod
import scopes
import tracereduce

SAMPLE_SPANS = ("engine.logits_to_host", "engine.sample")


class GcSpans:
    """A ``gc.callbacks`` hook: each collection is a ``host.gc`` span in
    the profiler's trace, and its seconds are kept by generation."""

    def __init__(self):
        import jax
        self.passes: Dict[int, List[float]] = {}
        self._annotation = jax.profiler.TraceAnnotation
        self._open = None

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._open = (time.perf_counter(), self._annotation("host.gc"))
            self._open[1].__enter__()
        elif self._open is not None:
            t0, span = self._open
            span.__exit__(None, None, None)
            self._open = None
            self.passes.setdefault(info["generation"], []).append(
                time.perf_counter() - t0)

    def summary(self) -> Dict[str, List[float]]:
        """{generation: [count, total s, longest s]}."""
        return {str(g): [len(v), sum(v), max(v)]
                for g, v in sorted(self.passes.items())}


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--top", type=int, default=10,
                    help="how many ops and idle gaps to list")
    ap.add_argument("--keep", help="copy the .xplane.pb to this file")
    return ap.parse_args(argv)


def main(argv=None, **where) -> int:
    """``where`` (root, bench_dir, require_tpu, peaks_table) is for the CPU
    rehearsal tests only."""
    args = _parse(argv)
    try:
        _, ctx, traffic = runmod.prepare(args.workload, args.seed,
                                         trace=True, **where)
    except runmod.SetupError as e:
        print(f"trace_scopes.py: {e}", file=sys.stderr)
        return 2
    rec = ctx.rec
    try:
        traffic.setup()
        hook = GcSpans()
        gc.callbacks.append(hook)
        try:
            traffic.window(rec.trace_seconds)
        finally:
            gc.callbacks.remove(hook)
        path = str(sorted(Path(rec.trace_dir).rglob("*.xplane.pb"))[-1])
        if args.keep:
            shutil.copyfile(path, args.keep)
        base = tracereduce.reduce(*tracereduce.load(path), n=args.top)
        extra = scopes.reduce(*scopes.load(path), n=args.top)
    finally:
        shutil.rmtree(rec.trace_dir, ignore_errors=True)

    span_s = extra.get("span_seconds", {})
    batch_s = span_s.get("bench.run_batch")
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "device": runmod.harness.device_info(ctx.devices),
        "window_s": base.get("window_s"), "busy_s": base.get("busy_s"),
        "programs": base.get("programs"),
        "scopes": extra.get("scopes"),
        "scope_shares": {span: scopes.shares(split) for span, split in
                         extra.get("scopes", {}).items()},
        "scopes_inclusive": extra.get("scopes_inclusive"),
        "device_ops": extra.get("device_ops"),
        "op_detail": extra.get("op_detail"),
        "idle_gaps": extra.get("idle_gaps"),
        "bench_idle_gaps": base.get("idle_gaps"),
        "span_seconds": span_s,
        "sample_share": (100.0 * sum(span_s.get(k, 0.0)
                                     for k in SAMPLE_SPANS) / batch_s
                         if batch_s else None),
        "gc": hook.summary(),
        "spans": rec.span_summary()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
