"""Sweep-driven auto-strategy (ISSUE 3 tentpole).

Covers: (a) the memory-feasibility model and its monotonicity properties,
(b) the ModelConfig→Workload adapter, (c) choose_strategy returning a
feasible simulator-chosen strategy for every registry model with the
golden strategy-regression gate, (d) cell_policy's frozen paper-faithful
defaults when autostrategy=False, and (e) the canonical-form symmetry
pruning preserving the Pareto front exactly (incl. the numeric
counterexample showing mp↔dp swaps are NOT time-symmetric, which is why
the dedup keys on simulation inputs).
"""

import json
from pathlib import Path

import pytest

from repro.core.autostrategy import (AutoStrategyDecision, check_goldens,
                                     choose_strategy, decision_table)
from repro.core.placement import Strategy
from repro.core.simulator import Simulator
from repro.core.sweep import (CSV_HEADER, sim_signature, strategy_space,
                              sweep, to_csv_rows, transformer_17b,
                              transformer_17b_sweep)
from repro.core.workloads import (DEFAULT_NPU_HBM_BYTES, MemoryModel,
                                  Workload, from_model_config, is_feasible,
                                  memory_bytes_per_npu,
                                  optimizer_bytes_per_param)

GOLDENS = Path(__file__).parent / "goldens" / "autostrategy.json"


def _cfg(arch):
    from repro.configs.registry import get_config
    return get_config(arch)


def _shape(name="train_4k"):
    from repro.models.config import SHAPES_BY_NAME
    return SHAPES_BY_NAME[name]


# --------------------------------------------------------------------------
# (a) memory-feasibility model
# --------------------------------------------------------------------------

def test_optimizer_bytes_per_param_modes():
    # fp32 master + fp32 moments: 4 + 2·4
    assert optimizer_bytes_per_param(True, "float32") == 12.0
    # the arctic-480b mode: no master, int8 moments
    assert optimizer_bytes_per_param(False, "int8") == 2.0
    assert optimizer_bytes_per_param(True, "bfloat16") == 8.0


def _workload(params_per_layer=1e8, n_layers=16, act=4096.0, seq=1024,
              st=Strategy(2, 4, 1), execution="stationary"):
    return Workload(name="synthetic", n_layers=n_layers,
                    params_per_layer=params_per_layer,
                    flops_fwd_per_sample_layer=2 * params_per_layer,
                    act_bytes_per_sample=act, strategy=st,
                    execution=execution, seq=seq)


def test_memory_model_components():
    w = _workload(st=Strategy(1, 1, 1), n_layers=1, seq=1)
    mem = MemoryModel(master=True, moments_dtype="float32", remat="full")
    # 1 layer, no sharding: weights 2B + grads 2B + opt 12B + boundary act
    assert memory_bytes_per_npu(w, mem) == pytest.approx(
        16 * w.params_per_layer + w.act_bytes_per_sample)
    # MP halves every term
    w2 = _workload(st=Strategy(2, 1, 1), n_layers=1, seq=1)
    assert memory_bytes_per_npu(w2, mem) == pytest.approx(
        memory_bytes_per_npu(w, mem) / 2)
    # streaming: only 3 layer buffers, no optimizer state
    ws = _workload(st=Strategy(1, 1, 1), n_layers=64, seq=1,
                   execution="streaming")
    assert memory_bytes_per_npu(ws, mem) == pytest.approx(
        3 * ws.params_per_layer * 2 + 64 * ws.act_bytes_per_sample)


def test_remat_orders_activation_footprint():
    w = _workload()
    mems = [memory_bytes_per_npu(w, MemoryModel(remat=r))
            for r in ("full", "block", "none")]
    assert mems[0] < mems[1] < mems[2]


def test_feasibility_monotone_in_budget_and_model_size():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as hs

    @given(params=hs.floats(1e6, 1e12), layers=hs.integers(1, 200),
           act=hs.floats(1e2, 1e8), seq=hs.integers(1, 65536),
           mp=hs.integers(1, 16), dp=hs.integers(1, 16),
           pp=hs.integers(1, 8),
           budget=hs.floats(1e9, 1e12), extra=hs.floats(0, 1e12),
           scale=hs.floats(1.0, 100.0),
           master=hs.booleans(),
           moments=hs.sampled_from(["float32", "bfloat16", "int8"]),
           remat=hs.sampled_from(["none", "block", "full"]),
           execution=hs.sampled_from(["stationary", "streaming"]))
    @settings(deadline=None)
    def run(params, layers, act, seq, mp, dp, pp, budget, extra, scale,
            master, moments, remat, execution):
        pp = min(pp, layers)
        st = Strategy(mp, dp, pp)
        w = _workload(params, layers, act, seq, st, execution)
        mem = MemoryModel(npu_hbm_bytes=budget, master=master,
                          moments_dtype=moments, remat=remat)
        # more HBM never removes a feasible strategy
        if is_feasible(w, mem):
            assert is_feasible(w, MemoryModel(
                npu_hbm_bytes=budget + extra, master=master,
                moments_dtype=moments, remat=remat))
        # a larger model never adds a feasible strategy
        big = _workload(params * scale, layers, act * scale, seq, st,
                        execution)
        if not is_feasible(w, mem):
            assert not is_feasible(big, mem)
        assert memory_bytes_per_npu(big, mem) >= \
            memory_bytes_per_npu(w, mem) - 1e-9

    run()


# --------------------------------------------------------------------------
# (b) ModelConfig → Workload adapter
# --------------------------------------------------------------------------

def test_adapter_covers_every_registry_family():
    from repro.configs.registry import ARCH_IDS
    shape = _shape()
    for arch in ARCH_IDS:
        cfg = _cfg(arch)
        w = from_model_config(cfg, shape, Strategy(2, 2, 1))
        assert w.params_per_layer > 0 and w.flops_fwd_per_sample_layer > 0
        assert w.n_layers >= cfg.num_layers
        # minibatch ≈ the cell's fixed global token count
        assert w.minibatch == pytest.approx(
            shape.global_batch * shape.seq_len, rel=0.01)


def test_adapter_total_params_sane():
    # llama3.2-1b is ~1.2B params incl. embeddings; first-order accounting
    # must land within 20%
    w = from_model_config(_cfg("llama3.2-1b"), _shape(), Strategy(1, 1, 1))
    assert w.params_total == pytest.approx(1.24e9, rel=0.2)
    # arctic-480b: ~482B resident
    w = from_model_config(_cfg("arctic-480b"), _shape(), Strategy(1, 1, 1))
    assert w.params_total == pytest.approx(480e9, rel=0.2)


def test_adapter_counts_zamba2_applications():
    """zamba2-7b: 81 Mamba2 layers (78.4 M each), two shared blocks held
    once (334 M each), 13 applications each with its adapter and d x d
    linear (17.0 M), the tied embedding (115 M): 7.35 B resident.  Each
    application runs its block again, so the active count exceeds it."""
    w = from_model_config(_cfg("zamba2-7b"), _shape(), Strategy(1, 1, 1))
    assert w.params_total == pytest.approx(7.35e9, rel=0.01)
    active = w.params_total * w.active_param_fraction
    assert active == pytest.approx(7.35e9 + 11 * 334e6, rel=0.01)


def test_adapter_moe_active_fraction():
    w = from_model_config(_cfg("mixtral-8x7b"), _shape(), Strategy(1, 1, 1))
    assert w.active_param_fraction < 0.5          # top-2 of 8 experts
    dense = from_model_config(_cfg("llama3.2-1b"), _shape(),
                              Strategy(1, 1, 1))
    assert dense.active_param_fraction == 1.0


def test_adapter_serving_kv_cache():
    w = from_model_config(_cfg("llama3.2-1b"), _shape("decode_32k"),
                          Strategy(1, 1, 1))
    assert w.kv_bytes_per_sample_layer > 0
    ssm = from_model_config(_cfg("mamba2-1.3b"), _shape("decode_32k"),
                            Strategy(1, 1, 1))
    assert ssm.kv_bytes_per_sample_layer == 0     # attention-free


# --------------------------------------------------------------------------
# (c) choose_strategy + the golden strategy-regression gate
# --------------------------------------------------------------------------

def test_choose_strategy_feasible_for_every_registry_model():
    """Acceptance: a simulator-chosen, memory-feasible (mp, dp, pp,
    wafers) for every model in configs/registry.py."""
    from repro.configs.registry import ARCH_IDS
    shape = _shape()
    from repro.parallel.policy import paper_defaults
    for arch in ARCH_IDS:
        cfg = _cfg(arch)
        _, ocfg = paper_defaults(cfg, shape)
        d = choose_strategy(cfg, shape, master=ocfg.master,
                            moments_dtype=ocfg.moments_dtype,
                            fabrics=("FRED-C",))   # single fabric: fast path
        assert d.memory_bytes_per_npu <= d.npu_hbm_bytes
        assert d.strategy.n_workers >= 1
        assert d.n_candidates > 0
        assert d.n_infeasible + d.n_dominated < d.n_candidates


def test_decision_table_matches_goldens():
    """The CI strategy-regression gate: a cost-model change that silently
    flips a chosen (mp, dp, pp, wafers) fails here (and in the workflow's
    `--goldens` step).  Regenerate with:
      PYTHONPATH=src python -m benchmarks.run --only autostrategy
    then update tests/goldens/autostrategy.json from the printed table."""
    # keep in sync with benchmarks.run.AUTOSTRATEGY_ARCHS (not imported:
    # the benchmarks dir is not on the test path)
    decisions = decision_table(("llama3.2-1b", "mixtral-8x7b",
                                "arctic-480b"))
    errors = check_goldens(decisions, str(GOLDENS))
    new = {f"{d.arch}/{d.shape}": d.golden() for d in decisions}
    assert not errors, (
        "chosen strategies diverge from goldens:\n  " + "\n  ".join(errors)
        + "\nnew table (update tests/goldens/autostrategy.json if "
        f"intended):\n{json.dumps(new, indent=2)}")


def test_check_goldens_flags_divergence(tmp_path):
    d = decision_table(["llama3.2-1b"])[0]
    bad = {f"{d.arch}/{d.shape}": dict(d.golden(), mp=d.mp + 1)}
    p = tmp_path / "g.json"
    p.write_text(json.dumps(bad))
    assert check_goldens([d], str(p))
    missing = tmp_path / "m.json"
    missing.write_text("{}")
    assert check_goldens([d], str(missing))
    # a golden whose model vanished from the decision list must fail too
    # (otherwise dropping a model from the bench silently weakens the gate)
    stale = tmp_path / "s.json"
    stale.write_text(json.dumps({f"{d.arch}/{d.shape}": d.golden(),
                                 "ghost-arch/train_4k": d.golden()}))
    errs = check_goldens([d], str(stale))
    assert errs and "ghost-arch" in errs[0]


def test_moe_archs_elect_expert_parallelism():
    """ISSUE 8 acceptance: with the ep/sp axes searchable, both MoE
    registry models choose ep > 1 (the epsweep CI gate pins the full
    decisions in tests/goldens/epsweep.json; this is the tier-1 view)."""
    from repro.core.autostrategy import EP_SWEEP_KW, MOE_ARCHS
    decisions = decision_table(MOE_ARCHS, **EP_SWEEP_KW)
    assert [d.arch for d in decisions] == list(MOE_ARCHS)
    for d in decisions:
        assert d.ep > 1, d.arch
        assert d.strategy.ep == d.ep and d.strategy.sp == d.sp
        assert d.golden()["ep"] == d.ep


def test_golden_dict_adds_ep_sp_keys_only_when_set():
    """Dense-model goldens must stay byte-identical across the EP PR:
    ``golden()`` emits the new axes only at non-default values."""
    from repro.core.autostrategy import EP_SWEEP_KW
    plain = decision_table(["llama3.2-1b"])[0]
    assert plain.ep == 1 and plain.sp == 1
    assert "ep" not in plain.golden() and "sp" not in plain.golden()
    # a dense model never elects ep, but may take the free sp sharding
    searched = decision_table(["llama3.2-1b"], **EP_SWEEP_KW)[0]
    assert searched.ep == 1 and "ep" not in searched.golden()
    if searched.sp > 1:
        assert searched.golden()["sp"] == searched.sp


def test_decision_csv_rows_carry_ep_sp():
    from repro.core.autostrategy import (DECISION_CSV_HEADER,
                                         decision_csv_rows)
    assert ",ep,sp," in DECISION_CSV_HEADER
    ds = decision_table(["llama3.2-1b"])
    n = len(DECISION_CSV_HEADER.split(","))
    rows = decision_csv_rows(ds)
    assert rows and all(len(r.split(",")) == n for r in rows)


def test_streaming_fallback_for_480b():
    """arctic-480b cannot hold 482B params weight-stationary on ≤128
    16-GiB NPUs — the decision must fall back to weight streaming
    (Sec. III-A), the paper's own answer for Transformer-1T."""
    d = choose_strategy(_cfg("arctic-480b"), _shape(),
                        master=False, moments_dtype="int8",
                        fabrics=("FRED-C",))
    assert d.execution == "streaming"
    assert d.memory_bytes_per_npu <= d.npu_hbm_bytes


def test_infeasible_raises():
    from repro.core.autostrategy import InfeasibleModelError
    with pytest.raises(InfeasibleModelError):
        choose_strategy(_cfg("arctic-480b"), _shape(),
                        npu_hbm_bytes=2**20,     # 1 MiB: nothing fits
                        fabrics=("FRED-C",))


# --------------------------------------------------------------------------
# (d) cell_policy: frozen defaults vs sweep-driven selection
# --------------------------------------------------------------------------

def test_cell_policy_defaults_frozen():
    """autostrategy=False returns the paper-faithful defaults bit-for-bit
    (the pre-autostrategy behavior the dry-run artifacts recorded)."""
    from repro.parallel.policy import cell_policy
    cases = {
        ("arctic-480b", "train_4k"): dict(master=False,
                                          moments_dtype="int8",
                                          remat="full"),
        ("qwen3-32b", "train_4k"): dict(master=True,
                                        moments_dtype="bfloat16",
                                        remat="full"),
        ("llama3.2-1b", "train_4k"): dict(master=True,
                                          moments_dtype="float32",
                                          remat="full"),
        ("llama3.2-1b", "prefill_32k"): dict(master=True,
                                             moments_dtype="float32",
                                             remat="block"),
    }
    for (arch, shape_name), want in cases.items():
        pcfg, ocfg = cell_policy(_cfg(arch), _shape(shape_name), mesh=None)
        assert ocfg.master is want["master"], arch
        assert ocfg.moments_dtype == want["moments_dtype"], arch
        assert pcfg.remat == want["remat"], (arch, shape_name)
        assert pcfg.auto_strategy == (0, 0, 0, 0, "")
    # long-context chunking default unchanged
    pcfg, _ = cell_policy(_cfg("llama3.2-1b"), _shape("prefill_32k"), None)
    assert (pcfg.attn_q_chunk, pcfg.attn_k_chunk) == (512, 1024)


def test_cell_policy_autostrategy_stamps_strategy():
    from repro.parallel.policy import cell_policy
    pcfg, ocfg = cell_policy(
        _cfg("llama3.2-1b"), _shape(), mesh=None, autostrategy=True,
        sweep_kw=dict(fabrics=("FRED-C",), max_wafers=2))
    mp, dp, pp, wf, topo = pcfg.auto_strategy
    assert mp * dp * pp >= 1 and wf >= 1
    if wf > 1:
        assert topo in ("ring", "fully_connected", "switch")
    else:
        assert topo == ""
    # the frozen optimizer mode is unchanged by strategy selection
    assert ocfg.master is True and ocfg.moments_dtype == "float32"


def test_cell_policy_accepts_precomputed_decision():
    from repro.parallel.policy import cell_policy
    d = choose_strategy(_cfg("llama3.2-1b"), _shape(),
                        fabrics=("FRED-C",))
    pcfg, _ = cell_policy(_cfg("llama3.2-1b"), _shape(), None,
                          autostrategy=True, decision=d)
    assert pcfg.auto_strategy == (d.mp, d.dp, d.pp, d.wafers,
                                  d.inter_topology)


# --------------------------------------------------------------------------
# (e) canonical-form symmetry pruning
# --------------------------------------------------------------------------

def test_mp_dp_swap_is_not_time_symmetric():
    """The counterexample motivating signature-keyed (not sorted-triple)
    canonicalization: swapping mp↔dp changes BOTH objectives, so a
    syntactic dedup would corrupt the Pareto front."""
    sim = Simulator("FRED-C")
    a, b = Strategy(9, 2, 1), Strategy(2, 9, 1)
    wa, wb = transformer_17b(a), transformer_17b(b)
    ta = sim.run(wa).total / wa.minibatch
    tb = sim.run(wb).total / wb.minibatch
    assert ta != pytest.approx(tb, rel=1e-6)
    assert sim_signature(a, wa) != sim_signature(b, wb)


def test_pruned_sweep_preserves_pareto_front_20_npus():
    """Satellite acceptance: pruned and unpruned Pareto fronts identical
    on the 20-NPU reference (by construction — the signature captures
    exactly the simulator's inputs — and checked here point-for-point)."""
    plain = transformer_17b_sweep(20)
    pruned = transformer_17b_sweep(20, prune_symmetric=True)
    key = lambda r: (r.fabric, r.shape, r.strategy, r.n_wafers)
    assert [key(r) for r in plain] == [key(r) for r in pruned]
    assert [r.time_per_sample for r in plain] == \
        [r.time_per_sample for r in pruned]
    assert {key(r) for r in plain if r.pareto} == \
        {key(r) for r in pruned if r.pareto}


def test_signature_injective_on_divisor_triples():
    # every divisor triple is objective-distinct for this workload (see
    # the swap counterexample above), so the canonical map is injective
    sts = strategy_space(20, n_layers=78)
    sigs = {sim_signature(st, transformer_17b(st)) for st in sts}
    assert len(sigs) == len(sts)


def test_sweep_dedup_shares_breakdown_for_identical_signatures():
    # a signature-equal duplicate IS collapsed to a single simulator call:
    # its sweep row replicates the representative's breakdown object
    dup = [Strategy(3, 3, 2), Strategy(3, 3, 2)]
    res = sweep(transformer_17b, 20, fabrics=("FRED-C",), n_layers=78,
                strategies=dup, prune_symmetric=True)
    by_shape = {}
    for r in res:
        by_shape.setdefault(r.shape, []).append(r)
    for rows in by_shape.values():
        assert len(rows) == 2
        assert rows[0].breakdown is rows[1].breakdown      # memo hit
    # and without pruning the values are identical anyway
    res0 = sweep(transformer_17b, 20, fabrics=("FRED-C",), n_layers=78,
                 strategies=dup)
    assert [r.time_per_sample for r in res] == \
        [r.time_per_sample for r in res0]


def test_64_npu_sweep_under_two_seconds():
    """Acceptance: a 64-NPU sweep with pruning completes in < 2 s."""
    import time
    t0 = time.perf_counter()
    res = transformer_17b_sweep(64, prune_symmetric=True)
    dt = time.perf_counter() - t0
    assert res and dt < 2.0, f"64-NPU sweep took {dt:.2f}s"


# --------------------------------------------------------------------------
# sweep memory objective / CSV schema
# --------------------------------------------------------------------------

def test_sweep_memory_objective_and_csv():
    mem = MemoryModel(npu_hbm_bytes=DEFAULT_NPU_HBM_BYTES)
    res = sweep(transformer_17b, 20, fabrics=("FRED-C",), n_layers=78,
                memory=mem)
    assert all(r.feasible is not None for r in res)
    assert all(r.memory_bytes_per_npu > 0 for r in res)
    # infeasible points are never Pareto members
    assert not any(r.pareto and not r.feasible for r in res)
    # memory strictly exceeds the weight-only proxy (grads + opt + acts)
    assert all(r.memory_bytes_per_npu > r.param_bytes_per_npu
               for r in res if r.strategy.wafers == 1)
    rows = to_csv_rows(res)
    n_fields = len(CSV_HEADER.split(","))
    assert all(len(r.split(",")) == n_fields for r in rows)
    # without a memory model the new columns stay empty/zero
    res0 = sweep(transformer_17b, 16, fabrics=("FRED-C",), n_layers=78)
    assert all(r.feasible is None and r.memory_bytes_per_npu == 0.0
               for r in res0)
