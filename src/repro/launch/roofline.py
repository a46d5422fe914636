"""Roofline-term extraction from compiled XLA artifacts.

Hardware model (task spec; TPU v5e-class chip):
  * 197 TFLOP/s bf16 peak per chip
  * 819 GB/s HBM bandwidth per chip
  * ~50 GB/s per ICI link

Terms (per the task spec, all in seconds):
  compute    = HLO_FLOPs  / (chips × peak)
  memory     = HLO_bytes  / (chips × HBM_bw)
  collective = coll_bytes / (chips × link_bw)

``cost_analysis()`` on an SPMD executable reports *per-partition* numbers,
so per-chip terms divide by the per-chip rate directly.

Collective bytes are NOT in cost_analysis; ``collective_bytes_from_hlo``
parses the optimized per-partition HLO, sums operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
(sync or async-start), and multiplies ops inside ``while`` bodies by the
``known_trip_count`` XLA annotates — this is how per-layer collectives
inside the layer scan are counted L times.
"""

from __future__ import annotations

import re
from typing import Dict, List, Tuple

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s / chip
LINK_BW = 50e9               # B/s / ICI link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s4": 1, "u4": 1,
}

_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_CALLEE_RE = re.compile(r"(?:body|condition|calls|to_apply)=([%\w.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*(\d+)')


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _split_computations(hlo: str) -> Dict[str, List[str]]:
    """computation name → its instruction lines."""
    comps: Dict[str, List[str]] = {}
    cur = None
    for line in hlo.splitlines():
        stripped = line.strip()
        if not line.startswith(" ") and "{" in line and ("(" in line):
            m = re.match(r"(?:ENTRY\s+)?(%?[\w.\-]+)\s*(?:\([^)]*\))?", stripped)
            if m:
                cur = m.group(1).lstrip("%")
                comps[cur] = []
                if "ENTRY" in line:
                    comps["__entry__"] = comps[cur]
            continue
        if stripped.startswith("}"):
            cur = None
            continue
        if cur is not None:
            comps[cur].append(stripped)
    return comps


def _multipliers(comps: Dict[str, List[str]]) -> Dict[str, int]:
    """Execution-count multiplier per computation (while-body trip counts,
    propagated through nested calls).  Unknown trip counts default to 1."""
    edges: Dict[str, List[Tuple[str, int]]] = {k: [] for k in comps}
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        for ln in lines:
            trip = 1
            tm = _TRIP_RE.search(ln)
            if tm and " while(" in ln:
                trip = int(tm.group(1))
            for callee in _CALLEE_RE.findall(ln):
                callee = callee.lstrip("%")
                if callee in comps:
                    edges[name].append((callee, trip if "body=" in ln else 1))
    mult: Dict[str, int] = {}
    entry = comps.get("__entry__")
    entry_name = None
    for k, v in comps.items():
        if v is entry and k != "__entry__":
            entry_name = k
    if entry_name is None:  # fall back: treat every computation once
        return {k: 1 for k in comps}

    import collections
    mult = collections.defaultdict(int)
    stack = [(entry_name, 1)]
    seen_depth = 0
    while stack and seen_depth < 100000:
        seen_depth += 1
        name, m = stack.pop()
        mult[name] += m
        for callee, trip in edges.get(name, []):
            stack.append((callee, m * trip))
    return dict(mult)


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def collective_op_bytes(ln: str) -> Tuple[str, int]:
    """(kind, per-device wire bytes) for one collective instruction line.

    Optimized HLO prints operands as bare ``%name`` references, so sizes
    come from the *output* shape(s) on the LHS (including tuple elements).
    Per-device wire-byte model:
      all-gather          → output size (each chip receives all shards)
      all-reduce          → output size (ring ≈ 2·(n-1)/n·size; we follow
                            the task-spec "operand size" convention)
      reduce-scatter      → output × group size (input operand size)
      all-to-all          → output size
      collective-permute  → output size
    Returns ("", 0) if the line is not a (start of a) collective.
    """
    cm = _COLL_RE.search(ln)
    if not cm:
        return "", 0
    lhs, _, rhs = ln.partition("=")
    if "-done" in rhs[:60]:
        return "", 0
    kind = cm.group(1)
    # output shapes: between '=' and the op name occurrence
    out_region = rhs[:rhs.find(kind)]
    shapes = _SHAPE_RE.findall(out_region)
    nbytes = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
    if kind == "reduce-scatter":
        gm = _GROUPS_RE.search(ln)
        if gm:
            nbytes *= int(gm.group(2))
    return kind, nbytes


def collective_bytes_from_hlo(hlo: str) -> dict:
    comps = _split_computations(hlo)
    mult = _multipliers(comps)
    per_kind: Dict[str, int] = {}
    count = 0
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        m = mult.get(name, 1)
        for ln in lines:
            kind, nbytes = collective_op_bytes(ln)
            if not kind:
                continue
            per_kind[kind] = per_kind.get(kind, 0) + nbytes * m
            count += m
    return {"per_kind_bytes": per_kind,
            "total_bytes": sum(per_kind.values()),
            "op_count": count}


def collect_cost(compiled) -> dict:
    ca = compiled.cost_analysis()
    keep = {}
    for k in ("flops", "bytes accessed", "transcendentals", "optimal_seconds"):
        if k in ca:
            keep[k] = float(ca[k])
    # per-operand bytes keys are noisy; keep the aggregate only
    return keep


# --------------------------------------------------------------------------
# model FLOPs & terms
# --------------------------------------------------------------------------

def param_counts(cfg) -> Tuple[int, int]:
    """(total, active) parameter counts, computed analytically."""
    d, f, V = cfg.d_model, cfg.d_ff, cfg.padded_vocab
    L = cfg.num_layers

    def attn_params():
        return d * (cfg.n_heads * cfg.head_dim) * 2 + \
            d * (cfg.n_kv_heads * cfg.head_dim) * 2

    def mlp_params(ff):
        return 3 * d * ff

    total = active = 2 * V * d if not cfg.tie_embeddings else V * d
    if cfg.family in ("ssm", "hybrid", "zamba2"):
        di = cfg.d_inner
        per = d * (2 * di + 2 * cfg.ssm_groups * cfg.ssm_state + cfg.ssm_heads) \
            + di * d + 4 * (di + 2 * cfg.ssm_groups * cfg.ssm_state)
        total += per * L
        active += per * L
        if cfg.family == "hybrid":
            shared = attn_params() + mlp_params(f)
            total += shared
            active += shared * cfg.n_applications   # applied per token
        if cfg.family == "zamba2":
            # q, k, v read 2·d; each application owns its adapter and L_j
            hd = cfg.head_dim
            shared = (2 * d * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd
                      + cfg.n_heads * hd * d + mlp_params(f))
            own = d * cfg.adapter_rank + cfg.adapter_rank * 2 * f + d * d
            total += cfg.n_shared_blocks * shared + cfg.n_applications * own
            active += cfg.n_applications * (shared + own)
    elif cfg.n_experts:
        per_expert = mlp_params(f)
        per_layer = attn_params() + cfg.n_experts * per_expert + d * cfg.n_experts
        per_layer_active = attn_params() + cfg.top_k * per_expert + d * cfg.n_experts
        if cfg.moe_dense_ff:
            per_layer += mlp_params(cfg.moe_dense_ff)
            per_layer_active += mlp_params(cfg.moe_dense_ff)
        total += per_layer * L
        active += per_layer_active * L
    else:
        per = attn_params() + mlp_params(f)
        total += per * L
        active += per * L
    if cfg.family == "audio":
        enc = (attn_params() + mlp_params(f)) * cfg.n_enc_layers
        # decoder cross-attention
        total += enc + attn_params() * L
        active += enc + attn_params() * L
    return int(total), int(active)


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS per the task spec: 6·N·D train (N=active params,
    D=tokens), 2·N·D for single forward (prefill/decode)."""
    _, active = param_counts(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * active * tokens
    tokens = shape.global_batch  # one token per sequence
    return 2.0 * active * tokens


def exposed_comm_s(comm_s: float, overlappable_compute_s: float) -> float:
    """Exposed (non-hidden) communication time under an overlap budget.

    The schedulable model: communication hides behind up to
    ``overlappable_compute_s`` of independent compute, and only the
    excess lands on the critical path.  This is the same
    ``max(0, comm − overlappable)`` identity the analytical cost model
    applies per phase (core/simulator.py ``comm_overlap_fraction``) —
    tests/test_fabric_sim.py pins the two implementations equal so the
    XLA-side roofline and the simulator cannot drift."""
    return max(0.0, comm_s - overlappable_compute_s)


def roofline_terms(rec: dict, cfg, shape,
                   comm_overlap_fraction: float = 0.0) -> dict:
    chips = rec.get("n_devices", 1)
    corrected = rec.get("corrected") or {}
    flops_pd = corrected.get("flops") or rec["cost_analysis"].get("flops", 0.0)
    bytes_pd = corrected.get("bytes_accessed") or \
        rec["cost_analysis"].get("bytes accessed", 0.0)
    coll_pd = corrected.get("collective_bytes") or \
        rec["collectives"]["total_bytes"]

    t_compute = flops_pd / PEAK_FLOPS
    t_memory = bytes_pd / HBM_BW
    t_collective = coll_pd / LINK_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_collective}
    dominant = max(terms, key=terms.get)

    mf = model_flops(cfg, shape)
    hlo_total = flops_pd * chips
    useful = mf / hlo_total if hlo_total else 0.0
    bound = max(terms.values())
    # roofline fraction: useful model FLOPs over the time the dominant
    # term implies, relative to the all-chips peak
    frac = (mf / (chips * PEAK_FLOPS)) / bound if bound else 0.0
    return {**terms,
            "exposed_comm_s": exposed_comm_s(
                t_collective, comm_overlap_fraction * t_compute),
            "dominant": dominant.replace("_s", ""),
            "model_flops_total": mf,
            "hlo_flops_total": hlo_total,
            "useful_flops_ratio": useful,
            "roofline_fraction": frac}
