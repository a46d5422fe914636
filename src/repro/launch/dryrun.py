import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_FLAGS") or
                           "--xla_force_host_platform_device_count=512")
# ^ MUST be the first statements: jax locks the device count on first init.

"""Multi-pod dry-run driver (deliverable (e)).

For every (architecture × input shape) cell this lowers + compiles the
train/prefill/decode step on the production meshes:

  * single-pod: (data=16, model=16)   — 256 chips
  * multi-pod:  (pod=2, data=16, model=16) — 512 chips

and records ``memory_analysis()`` (proves the cell fits),
``cost_analysis()`` (FLOPs/bytes for §Roofline) and the collective schedule
parsed from optimized HLO (with ``known_trip_count`` scan multipliers).

Because ``cost_analysis`` counts a ``lax.scan`` body ONCE (verified
empirically — see DESIGN.md §7), the driver also compiles a single-layer
**probe** with identical shardings and reports trip-count-corrected totals.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out artifacts/dryrun
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path


def _build_mesh(kind: str):
    from repro.launch.mesh import make_production_mesh
    return make_production_mesh(multi_pod=(kind == "multi"))


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             pcfg_overrides=None, probe: bool = True,
             autostrategy: bool = False) -> dict:
    """Lower + compile one cell; return the roofline record.

    ``autostrategy=True`` lets the FRED simulator sweep pick the cell's
    (mp, dp, pp, wafers) — the chosen strategy and the *why* (candidate /
    infeasible / dominated counts) are recorded under ``"autostrategy"``
    and the strategy is stamped on the recorded pcfg as a
    :class:`~repro.models.config.StrategyDecision` (the artifact's
    ``pcfg.auto_strategy`` is its named-field dict, not the legacy
    positional 5-list).  ``pcfg_overrides`` still win afterwards
    (§Perf hillclimbs)."""
    import jax
    from repro.configs.registry import get_config, shape_applicability
    from repro.models.config import SHAPES_BY_NAME
    from repro.parallel.steps import make_setup
    from repro.launch.roofline import (collect_cost, collective_bytes_from_hlo,
                                       roofline_terms)
    from repro.parallel.policy import cell_policy

    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = shape_applicability(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": why}

    mesh = _build_mesh(mesh_kind)
    auto_rec = None
    decision = None
    if autostrategy:
        from repro.core.autostrategy import choose
        from repro.core.specs import DeploymentRequest
        from repro.parallel.policy import paper_defaults
        pcfg0, ocfg0 = paper_defaults(cfg, shape)
        decision = choose(DeploymentRequest(
            model=cfg, shape=shape, master=ocfg0.master,
            moments_dtype=ocfg0.moments_dtype, remat=pcfg0.remat))
        d = decision
        auto_rec = {
            "chosen": {"mp": d.mp, "dp": d.dp, "pp": d.pp,
                       "wafers": d.wafers, "fabric": d.fabric,
                       "wafer_shape": list(d.wafer_shape),
                       "inter_topology": d.inter_topology,
                       "hierarchy": list(d.hierarchy),
                       "execution": d.execution},
            "time_per_sample_s": d.time_per_sample_s,
            "memory_bytes_per_npu": d.memory_bytes_per_npu,
            "npu_hbm_bytes": d.npu_hbm_bytes,
            "why": {"n_candidates": d.n_candidates,
                    "n_infeasible": d.n_infeasible,
                    "n_dominated": d.n_dominated},
            "sweep_seconds": round(d.sweep_seconds, 3),
        }
    pcfg, ocfg = cell_policy(cfg, shape, mesh, autostrategy=autostrategy,
                             decision=decision)
    if pcfg_overrides:
        pcfg = pcfg.replace(**pcfg_overrides)

    t0 = time.time()
    setup = make_setup(cfg, shape, mesh, pcfg, ocfg)
    with mesh:
        lowered = setup.step_fn.lower(*setup.example_args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = collect_cost(compiled)
    hlo = compiled.as_text()
    colls = collective_bytes_from_hlo(hlo)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "status": "ok",
        "kind": shape.kind,
        "n_devices": mesh.devices.size,
        "seconds": {"lower": round(t_lower, 2), "compile": round(t_compile, 2)},
        "memory_per_device": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "total_bytes": (mem.argument_size_in_bytes +
                            mem.output_size_in_bytes +
                            mem.temp_size_in_bytes -
                            mem.alias_size_in_bytes),
        },
        "cost_analysis": cost,
        "collectives": colls,
        "pcfg": {k: v for k, v in dataclasses.asdict(pcfg).items()},
    }
    if auto_rec is not None:
        rec["autostrategy"] = auto_rec

    if probe:
        rec["probe"] = probe_layer_cost(cfg, shape, mesh, pcfg)
        rec["corrected"] = corrected_totals(rec, cfg)
    rec["roofline"] = roofline_terms(rec, cfg, shape)
    return rec


def probe_configs(cfg) -> dict:
    """The cut copies of ``cfg`` the probe compiles, by name.  "L1" and
    "L2" hold one and two layers (hybrid: periods of ``attn_every``
    layers, each ending in the shared block).  zamba2's applications fall
    at irregular layers: its "L1" and "L2" hold two and three layers with
    one application, at layer 1, and "L2A" adds a second at layer 2, so
    L2 − L1 is a layer and L2A − L2 an application.  (An application at
    layer 0 reads the embedding as its hidden state, and costs less in
    the backward pass than one at any published layer.)"""
    def cut(L, **kw):
        return dataclasses.replace(
            cfg, num_layers=L, n_enc_layers=min(cfg.n_enc_layers, L), **kw)
    if cfg.family == "hybrid":
        return {f"L{L}": cut(cfg.attn_every * L) for L in (1, 2)}
    if cfg.family == "zamba2":
        return {"L1": cut(2, hybrid_layer_ids=(1,)),
                "L2": cut(3, hybrid_layer_ids=(1,)),
                "L2A": cut(3, hybrid_layer_ids=(1, 2))}
    return {f"L{L}": cut(L) for L in (1, 2)}


def probe_layer_cost(cfg, shape, mesh, pcfg) -> dict:
    """Compile the step on the cut copies of ``probe_configs`` with the
    same shardings; per-layer cost = cost(L2) − cost(L1), base = L1 − layer.
    This sidesteps cost_analysis's count-scan-body-once behaviour exactly."""
    from repro.parallel.steps import make_setup
    from repro.launch.roofline import collect_cost, collective_bytes_from_hlo

    out = {}
    for name, c in probe_configs(cfg).items():
        setup = make_setup(c, shape, mesh, pcfg.replace(scan_layers=False))
        with mesh:
            compiled = setup.step_fn.lower(*setup.example_args).compile()
        cost = collect_cost(compiled)
        colls = collective_bytes_from_hlo(compiled.as_text())
        out[name] = {"cost": cost, "collective_bytes": colls["total_bytes"]}
    return out


def corrected_totals(rec, cfg) -> dict:
    """Trip-count-corrected FLOPs/bytes using the probe deltas."""
    p = rec.get("probe")
    if not p:
        return {}
    L = cfg.num_layers

    def total(get):
        # [(cost of one unit, units in the probe "L1", units in the model)]
        if "L2A" in p:          # zamba2: a layer and an application apart
            units = [(get(p["L2"]) - get(p["L1"]), 2, L),
                     (get(p["L2A"]) - get(p["L2"]), 1, cfg.n_applications)]
        else:
            # a hybrid's unit is a period ending in its shared block
            units = [(get(p["L2"]) - get(p["L1"]), 1, cfg.n_applications or L)]
        units = [(max(u, 0), n1, n) for u, n1, n in units]
        base = max(get(p["L1"]) - sum(u * n1 for u, n1, _ in units), 0)
        return base + sum(u * n for u, _, n in units)

    out = {key.replace(" ", "_"): total(lambda r: r["cost"].get(key, 0))
           for key in ("flops", "bytes accessed")}
    out["collective_bytes"] = total(lambda r: r["collective_bytes"])
    return out


def ep_compare(arch: str = "mixtral-8x7b", n_devices: int = 8,
               seq: int = 16, d_model: int = 64, d_ff: int = 128) -> dict:
    """Measure the expert-parallel All-to-All against the analytical model.

    Compiles the explicit shard_map dispatch
    (:func:`repro.models.moe.moe_ffn_ep`) on a reduced copy of an MoE
    arch (host devices; one sequence per EP rank) and parses the
    optimized HLO for all-to-all wire bytes.  The expectation has two
    layers: the *bucket* payload 2·E·C·d (what the dispatch+combine
    exchange physically moves, capacity headroom included) should match
    the HLO exactly, and the cost model's *token* payload 2·T·k·d
    (``Workload.a2a_bytes_per_sample_layer`` per token, dispatch+combine)
    relates to it by the capacity factor — both ratios are recorded, and
    tests/test_multidevice.py pins the bucket ratio at 1."""
    import math as _math
    import numpy as _np
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs.registry import get_config
    from repro.models.moe import init_moe, moe_ffn_ep, _v
    from repro.launch.roofline import collective_bytes_from_hlo

    base = get_config(arch)
    if not base.n_experts:
        raise ValueError(f"{arch} is not an MoE arch")
    cfg = dataclasses.replace(base, d_model=d_model, d_ff=d_ff,
                              moe_dense_ff=0)
    n = min(n_devices, len(jax.devices()), cfg.n_experts)
    mesh = Mesh(_np.array(jax.devices()[:n]), ("data",))
    params = {k: _v(v) for k, v in
              init_moe(jax.random.PRNGKey(0), cfg).items()}
    sharded = {"router": params["router"],
               **{k: jax.device_put(params[k],
                                    NamedSharding(mesh, P("data", None, None)))
                  for k in ("w_gate", "w_up", "w_down")}}
    x = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (n, seq, d_model)),
        NamedSharding(mesh, P("data", None, None)))
    with mesh:
        compiled = jax.jit(
            lambda p, t: moe_ffn_ep(p, t, cfg, mesh=mesh, ep_axis="data")
        ).lower(sharded, x).compile()
    colls = collective_bytes_from_hlo(compiled.as_text())
    measured = colls["per_kind_bytes"].get("all-to-all", 0)

    E, k, cf = cfg.n_experts, cfg.top_k, cfg.capacity_factor
    T_l = seq                                 # tokens per EP rank
    capacity = max(int(_math.ceil(T_l * k * cf / E)), 4)
    capacity = -(-capacity // 4) * 4
    bucket_bytes = 2 * E * capacity * d_model * 4      # dispatch+combine, f32
    token_bytes = 2 * T_l * k * d_model * 4            # the cost-model payload
    return {
        "arch": arch, "n_devices": n, "seq": seq,
        "d_model": d_model, "d_ff": d_ff,
        "n_experts": E, "top_k": k, "capacity_factor": cf,
        "capacity": capacity,
        "measured_a2a_bytes_per_device": measured,
        "expected_bucket_bytes_per_device": bucket_bytes,
        "model_token_bytes_per_device": token_bytes,
        "measured_over_bucket": measured / bucket_bytes,
        "bucket_over_token": bucket_bytes / token_bytes,
        "per_kind_bytes": colls["per_kind_bytes"],
    }


def serving_compare(arch: str = "llama3.2-1b", *, prompt_tokens: int = 16,
                    output_tokens: int = 24, batch: int = 4,
                    d_model: int = 128, num_layers: int = 4,
                    vocab_size: int = 512) -> dict:
    """Measure real per-token decode latency against the analytical
    serving model (the PR-3 "rank-only serving" fix, measurement side).

    Runs the batched :class:`repro.serve.engine.Engine` on a reduced copy
    of ``arch`` (host CPU — absolute times are not comparable to wafer
    NPUs, so the record keeps both columns side by side rather than
    asserting a ratio) and records the measured decode-step latency
    distribution next to the analytical prefill/decode/p50/p99 the
    serving objective would quote for the *full* arch on wafer hardware.
    """
    import jax
    from repro.configs.registry import get_config
    from repro.core.autostrategy import SERVE_OBJECTIVE, \
        choose_serving_strategy
    from repro.core.specs import Objective
    from repro.models import transformer as tfm
    from repro.models.modules import split
    from repro.serve.engine import Engine, EngineConfig, Request

    base = get_config(arch)
    objective = Objective.serving(
        target_p99_ms=SERVE_OBJECTIVE.target_p99_ms,
        concurrent_users=SERVE_OBJECTIVE.concurrent_users,
        think_time_s=SERVE_OBJECTIVE.think_time_s,
        prompt_tokens=prompt_tokens, output_tokens=output_tokens)
    decision = choose_serving_strategy(base, objective)

    cfg = base.reduced(d_model=d_model, num_layers=num_layers,
                       vocab_size=vocab_size)
    params, _ = split(tfm.init(jax.random.PRNGKey(0), cfg))
    ecfg = EngineConfig(max_batch=batch,
                        cache_len=prompt_tokens + output_tokens)
    engine = Engine(params, cfg, ecfg=ecfg)
    reqs = [Request(uid=i, prompt=list(range(1, prompt_tokens + 1)),
                    max_new_tokens=output_tokens) for i in range(batch)]
    engine.run_batch(reqs)
    steps = engine.decode_step_s[1:]       # drop the jit-compile step
    steps_sorted = sorted(steps)

    def _q(p):
        return steps_sorted[min(len(steps_sorted) - 1,
                                int(p * len(steps_sorted)))]

    return {
        "arch": arch, "status": "ok",
        "reduced": {"d_model": d_model, "num_layers": num_layers,
                    "vocab_size": vocab_size, "batch": batch,
                    "prompt_tokens": prompt_tokens,
                    "output_tokens": output_tokens},
        "measured": {
            "backend": jax.default_backend(),
            "n_decode_steps": len(steps),
            "decode_step_mean_s": sum(steps) / len(steps),
            "decode_step_p50_s": _q(0.50),
            "decode_step_p99_s": _q(0.99),
        },
        "analytical": {
            "placement": decision.placement,
            "wafers_per_cell": decision.wafers_per_cell,
            "total_wafers": decision.total_wafers,
            "prefill_s": decision.prefill_s,
            "decode_step_s": decision.decode_step_s,
            "ttft_p50_ms": decision.ttft_p50_ms,
            "ttft_p99_ms": decision.ttft_p99_ms,
            "target_p99_ms": decision.target_p99_ms,
        },
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-probe", action="store_true")
    ap.add_argument("--autostrategy", action="store_true",
                    help="let the FRED simulator sweep pick (mp, dp, pp, "
                         "wafers) per cell; records the decision + "
                         "dominated/infeasible counts in the artifact")
    ap.add_argument("--serving", action="store_true",
                    help="run the batched serving engine on a reduced "
                         "llama3.2-1b and record measured per-token decode "
                         "latency next to the analytical serving-cell "
                         "p50/p99; writes <out>/serving_compare.json and "
                         "exits")
    ap.add_argument("--ep-compare", action="store_true",
                    help="compile the shard_map expert-parallel All-to-All "
                         "on a reduced MoE arch and diff the measured HLO "
                         "wire bytes against the analytical payload; writes "
                         "<out>/ep_compare.json and exits")
    ap.add_argument("--out", type=str, default="artifacts/dryrun")
    args = ap.parse_args(argv)

    if args.serving:
        outdir = Path(args.out)
        outdir.mkdir(parents=True, exist_ok=True)
        rec = serving_compare(args.arch or "llama3.2-1b")
        (outdir / "serving_compare.json").write_text(
            json.dumps(rec, indent=2, default=str))
        m, a = rec["measured"], rec["analytical"]
        print(f"[dryrun] serving {rec['arch']}: measured decode "
              f"p50={m['decode_step_p50_s'] * 1e3:.2f}ms "
              f"p99={m['decode_step_p99_s'] * 1e3:.2f}ms "
              f"({m['backend']}, reduced) | analytical cell "
              f"step={a['decode_step_s'] * 1e3:.3f}ms "
              f"ttft_p99={a['ttft_p99_ms']:.2f}ms "
              f"({a['placement']}, {a['total_wafers']} wafers)", flush=True)
        return 0

    if args.ep_compare:
        outdir = Path(args.out)
        outdir.mkdir(parents=True, exist_ok=True)
        rec = ep_compare(args.arch or "mixtral-8x7b")
        (outdir / "ep_compare.json").write_text(
            json.dumps(rec, indent=2, default=str))
        ok = abs(rec["measured_over_bucket"] - 1.0) < 0.01
        print(f"[dryrun] ep_compare {rec['arch']}: "
              f"measured/bucket={rec['measured_over_bucket']:.3f} "
              f"bucket/token={rec['bucket_over_token']:.3f} "
              f"{'OK' if ok else 'MISMATCH'}", flush=True)
        return 0 if ok else 1

    from repro.configs.registry import ARCH_IDS
    from repro.models.config import SHAPES

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = [s.name for s in SHAPES] if (args.all or not args.shape) \
        else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                name = f"{arch}__{shape}__{mk}"
                path = outdir / f"{name}.json"
                try:
                    rec = run_cell(arch, shape, mk, probe=not args.no_probe,
                                   autostrategy=args.autostrategy)
                except Exception as e:  # a failure here is a bug in the system
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape, "mesh": mk,
                           "status": "error", "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                path.write_text(json.dumps(rec, indent=2, default=str))
                status = rec["status"]
                extra = ""
                if status == "ok":
                    mb = rec["memory_per_device"]["total_bytes"] / 2**30
                    extra = (f" mem/dev={mb:.2f}GiB "
                             f"compile={rec['seconds']['compile']}s")
                    if "autostrategy" in rec:
                        c = rec["autostrategy"]["chosen"]
                        topo = (f"+{c['inter_topology']}"
                                if c.get("inter_topology") else "")
                        extra += (f" auto=MP{c['mp']}-DP{c['dp']}-"
                                  f"PP{c['pp']}-W{c['wafers']}{topo}"
                                  f"@{c['fabric']}/{c['execution']}")
                print(f"[dryrun] {name}: {status}{extra}", flush=True)
    if failures:
        print(f"[dryrun] {failures} FAILURES", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
