"""Per-cell parallelization policy (the "compiler" of this framework).

The paper's point is that the fabric should let the compiler pick whatever
parallelization strategy compute/memory prefers (Sec. I, Fig. 2).  This
module is that policy layer for the JAX runtime: given (arch × shape × mesh)
it returns the ParallelConfig/OptimConfig the step builders use.

Two modes:

* ``autostrategy=False`` (default) — the frozen *paper-faithful* schedule:
  hand-set optimizer memory modes, remat, and attention chunking, exactly
  as recorded by the dry-runs (pinned in tests/test_autostrategy.py).
* ``autostrategy=True`` — sweep-driven: the analytical FRED simulator
  (``core.sweep`` via ``core.autostrategy.choose``) picks the
  memory-feasible Pareto-optimal (mp, dp, pp, wafers) — and, for
  cross-wafer DP, the inter-wafer topology (ring / fully_connected /
  switch, ``core.cluster``) — for the cell under the frozen defaults'
  OptimConfig/remat settings, and the decision lands in
  ``ParallelConfig.auto_strategy``.  The JAX mesh itself is built by the
  launcher — the recorded strategy is what the dry-run logs and what
  wafer-side placement (``core.placement``) executes.

§Perf hillclimbs still override via ``pcfg_overrides`` after either mode.
"""

from __future__ import annotations

from typing import Optional, Tuple

from repro.models.config import (ModelConfig, ParallelConfig, ShapeConfig,
                                 StrategyDecision)
from repro.train.optim import OptimConfig


def paper_defaults(cfg: ModelConfig, shape: ShapeConfig
                   ) -> Tuple[ParallelConfig, OptimConfig]:
    """The frozen paper-faithful hierarchical schedule (pre-autostrategy
    behavior, bit-identical; pinned in tests/test_autostrategy.py)."""
    pcfg = ParallelConfig()
    ocfg = OptimConfig()

    # --- optimizer memory modes ---------------------------------------------
    # arctic-480b: 469B expert params; fp32 master+moments (12B/param) cannot
    # fit 256×16GB.  8-bit moments + no master (6B/param incl. grads) fits.
    if cfg.name == "arctic-480b":
        ocfg = OptimConfig(master=False, moments_dtype="int8")
    elif cfg.name in ("qwen3-32b", "llava-next-34b", "mixtral-8x7b"):
        # 30-50B: master fp32 is fine, keep moments bf16 to halve opt state
        ocfg = OptimConfig(master=True, moments_dtype="bfloat16")

    # --- remat ---------------------------------------------------------------
    # full remat for all train cells: at 1M tokens/step the saved-dot memory
    # of 'block' exceeds HBM for most archs; the recompute shows up honestly
    # in the HLO-vs-model FLOP ratio of §Roofline.
    if shape.kind == "train":
        pcfg = pcfg.replace(remat="full")

    # --- attention chunking ---------------------------------------------------
    if shape.seq_len >= 32_768:
        pcfg = pcfg.replace(attn_q_chunk=512, attn_k_chunk=1024)

    return pcfg, ocfg


def cell_policy(cfg: ModelConfig, shape: ShapeConfig, mesh,
                autostrategy: bool = False,
                sweep_kw: Optional[dict] = None,
                decision=None) -> Tuple[ParallelConfig, OptimConfig]:
    """Policy for one (arch × shape × mesh) cell.

    ``autostrategy=True`` runs the simulator sweep (``sweep_kw`` holds
    the :class:`~repro.core.specs.DeploymentRequest` axes: n_npus,
    fabrics, max_wafers, npu_hbm_bytes, ...) and stamps the chosen
    strategy on the
    returned ``ParallelConfig``; the frozen defaults are returned
    unchanged when ``False``.  A precomputed
    :class:`~repro.core.autostrategy.AutoStrategyDecision` can be passed
    as ``decision`` to skip the sweep (the dry-run records it anyway)."""
    pcfg, ocfg = paper_defaults(cfg, shape)
    if not autostrategy:
        return pcfg, ocfg

    if decision is None:
        from repro.core.autostrategy import _build_request, choose
        decision = choose(_build_request(
            cfg, shape, master=ocfg.master, moments_dtype=ocfg.moments_dtype,
            remat=pcfg.remat, **(sweep_kw or {})))
    st = decision.strategy
    pcfg = pcfg.replace(auto_strategy=StrategyDecision(
        mp=st.mp, dp=st.dp, pp=st.pp, wafers=st.wafers,
        ep=st.ep, sp=st.sp,
        inter_topology=decision.inter_topology,
        defect_seed=getattr(decision, "defect_seed", None)))
    return pcfg, ocfg
