"""Logical-axis → mesh-axis sharding rules.

This is the single place where parallelization policy becomes concrete
PartitionSpecs.  The model code only names *logical* axes (see
``models.modules``); the mesh only has *physical* axes (pod/data/model).
``Ruleset.spec(axes)`` translates.

Divisibility-aware policy (documented in DESIGN.md §6):

* TP axes (vocab/heads/kv/mlp/ssm_in/qkv) map to ``model``.  Query heads
  that don't divide the TP degree (llava 56H, qwen1.5 20H, arctic 56H over
  16) still shard — GSPMD pads the ragged tail — unless the arch opts into
  ``attn_sharding='context'``.
* KV heads shard over ``model`` only when divisible; otherwise the KV cache
  shards its *sequence* dim over ``model`` instead (flash-decoding layout)
  and kv projections stay replicated (they are tiny for strong-GQA archs).
* ``embed`` (d_model) shards over ``data`` when ``param_sharding='fsdp'``
  (ZeRO-3 style; GSPMD inserts the per-layer all-gathers); under ``zero1``
  only optimizer state takes the data sharding; under ``replicated``
  neither does.
* zamba2's new leaves reuse these names: its shared blocks' q, k and v
  read a 2·d input named ``embed`` (FSDP over ``data``) and write heads
  (``qkv``, TP); the MLP adapter's ``in_proj`` is (``embed``, r) and its
  gate and up halves are ``mlp`` as ``w_gate``/``w_up`` are; L_j is
  (``embed``, ``embed_out``).  The 112 SSM heads take ``model``, and the
  per-group gated norm of its two B/C groups then stays on one chip.
* MoE ``expert`` shards over ``model`` when divisible (arctic 128/16),
  otherwise experts stay replicated and their ``mlp`` hidden dim takes the
  TP sharding (mixtral 8e over 16-way TP).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig, ParallelConfig
from repro.models.modules import AxisNames

# the mesh's tensor-parallel axis and its batch-sharded axes ("pod" joins
# them in front where the mesh has it)
TP_AXIS = "model"
DP_AXES = ("data",)


def _axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        return int(np.prod([mesh.shape[n] for n in name]))
    return mesh.shape[name]


@dataclasses.dataclass
class Ruleset:
    mesh: Mesh
    cfg: ModelConfig
    pcfg: ParallelConfig

    def __post_init__(self):
        mesh, cfg, pcfg = self.mesh, self.cfg, self.pcfg
        tp = TP_AXIS if TP_AXIS in mesh.shape else None
        dp: Tuple[str, ...] = tuple(a for a in DP_AXES if a in mesh.shape)
        if "pod" in mesh.shape:
            dp = ("pod",) + dp
        tp_size = _axis_size(mesh, tp)
        self.dp = dp
        self.tp = tp
        self.tp_size = tp_size
        fsdp = pcfg.param_sharding == "fsdp"
        # without TP, FSDP shards over every data axis (divisibility of
        # d_model by the full 256 holds for all assigned archs)
        fsdp_axis = (dp if tp is None else dp[-1]) if (fsdp and dp) else None

        kv_div = cfg.n_kv_heads > 0 and cfg.n_kv_heads % max(tp_size, 1) == 0
        heads_ok = cfg.n_heads > 0 and pcfg.attn_sharding != "context"
        exp_div = cfg.n_experts > 0 and cfg.n_experts % max(tp_size, 1) == 0
        # EP mode: experts shard over a *data* axis (all-to-all dispatch),
        # their hidden dim takes the TP sharding
        ep_axis = (pcfg.moe_ep_axis if pcfg.moe_ep_axis in mesh.shape and
                   cfg.n_experts and
                   cfg.n_experts % mesh.shape.get(pcfg.moe_ep_axis, 1) == 0
                   else None)
        self.ep_axis = ep_axis
        if ep_axis:
            exp_div = False

        self.kv_head_sharded = kv_div
        self.expert_sharded = exp_div

        rules = {
            "layers": None,
            "null": None,
            "embed": fsdp_axis,
            "embed_out": None,
            "vocab": tp if tp is not None else
            (tuple(dp) if fsdp else None),
            "qkv": tp,
            "heads": tp if heads_ok else None,
            "kv": tp,   # flattened Hkv·hd dim — always divisible
            "mlp": None if exp_div else tp,
            "expert": ep_axis if ep_axis else (tp if exp_div else None),
            "expert_router": None,
            "ssm_in": tp,
            "embed_unsharded": None,
            "mlp_dense": tp if tp is not None else
            (dp[-1] if (fsdp and dp) else None),
            "ssm_head": tp if (cfg.ssm_heads and cfg.ssm_heads % max(tp_size, 1) == 0) else None,
        }
        # Expert weights: never FSDP the d_model *contraction* dim (a
        # data-sharded contraction forces partial-sum all-reduces of the
        # (G,E,C,f) bucket tensor).  Put FSDP on the f dim instead —
        # combined with TP when experts aren't TP-sharded.
        if cfg.n_experts:
            if ep_axis:
                self.expert_mlp_axis = tp                 # (data, None, model)
            elif exp_div:
                self.expert_mlp_axis = fsdp_axis          # (model, None, data)
            else:
                self.expert_mlp_axis = ((tp, fsdp_axis) if (tp and fsdp_axis)
                                        else (tp or fsdp_axis))
        self.rules = rules

    # ---- parameters --------------------------------------------------------
    def spec(self, axes: AxisNames) -> P:
        names = tuple(axes)
        if "vocab" in names:
            # embedding/lm_head: never FSDP the d_model dim — a data-sharded
            # contraction dim would force logits partial-sums over the data
            # axis (measured: tens of GB of all-reduce per step).  The vocab
            # dim carries the TP sharding; ZeRO still shards the optimizer.
            return P(*(self.rules.get(a) if a == "vocab" else None
                       for a in names))
        if "expert" in names:
            # (expert, embed, mlp): FSDP lives on the mlp dim (see __post_init__)
            table = dict(self.rules)
            table["embed"] = None
            table["mlp"] = self.expert_mlp_axis
            return P(*(table.get(a, None) for a in names))
        return P(*(self.rules.get(a, None) for a in names))

    def param_shardings(self, axes_tree):
        return jax.tree.map(
            lambda a: NamedSharding(self.mesh, self.spec(a)), axes_tree,
            is_leaf=lambda x: isinstance(x, AxisNames))

    def opt_spec(self, axes: AxisNames) -> P:
        """Optimizer-state sharding: like params, but ZeRO-1 additionally
        shards over data on the 'embed' dim even when params are replicated."""
        if self.pcfg.param_sharding != "zero1":
            return self.spec(axes)
        dp_last = self.dp[-1] if self.dp else None
        names = []
        for a in axes:
            r = self.rules.get(a, None)
            if a == "embed" and r is None:
                r = dp_last
            names.append(r)
        return P(*names)

    # ---- activations ---------------------------------------------------------
    def batch_axes(self, global_batch: int) -> Optional[Tuple[str, ...]]:
        """Shard batch over as many dp axes as divide it (outermost first)."""
        axes = []
        rem = global_batch
        for a in self.dp:
            s = self.mesh.shape[a]
            if rem % s == 0 and rem >= s:
                axes.append(a)
                rem //= s
        return tuple(axes) or None

    def act_spec(self, kind: str, global_batch: int, *, ndim: int = 3) -> P:
        b = self.batch_axes(global_batch)
        seq = self.tp if kind == "residual" else None
        if kind == "residual":
            return P(b, seq, None)
        if kind == "logits":
            return P(b, None, self.tp)
        if kind == "tokens":
            return P(b, None)
        if kind == "q_heads":
            # uneven head counts (56, 20) shard with GSPMD padding
            hs = self.tp if self.rules.get("heads") else None
            return P(b, None, hs, None)
        if kind == "kv_heads":
            # replicate KV heads when they don't divide TP — they are tiny
            # for strong-GQA archs and replication avoids resharding storms
            return P(b, None, self.tp if self.kv_head_sharded else None, None)
        if kind == "moe_buckets":
            # (G, E, C, d/f): groups over data; experts over model when
            # expert-sharded; the expert hidden dim otherwise.
            # EP: experts carry the data axis (all-to-all dispatch), so the
            # group dim stays unsharded
            if getattr(self, "ep_axis", None):
                return P(None, self.ep_axis, None, None)
            e_ax = self.tp if self.expert_sharded else None
            f_ax = None if self.expert_sharded else self.tp
            return P(b, e_ax, None, f_ax)
        raise KeyError(kind)

    def constrain_fn(self, global_batch: int):
        mesh = self.mesh
        tp_size = max(self.tp_size, 1)

        def constrain(x, kind: str = "residual"):
            spec = list(self.act_spec(kind, global_batch))
            if x.ndim != len(spec):
                return x
            if kind == "moe_buckets" and spec[3] is not None and \
                    x.shape[3] % tp_size != 0:
                spec[3] = None   # bucket d dim: only the f-projection splits
            # drop the SP seq sharding when the seq dim doesn't divide TP
            if kind == "residual" and spec[1] is not None and \
                    x.shape[1] % tp_size != 0:
                spec[1] = None
            if kind == "q_heads" and x.shape[1] == 1:
                spec[1] = None  # decode: no seq to shard
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*spec)))
        return constrain

    # ---- decode state --------------------------------------------------------
    def kv_cache_spec(self, global_batch: int) -> P:
        """(L, B, S, Hkv, hd)."""
        b = self.batch_axes(global_batch)
        if b is None:
            # long-context single-sequence: spread the cache seq dim over
            # every mesh axis (flash-decode combines partial softmax stats)
            axes = tuple(a for a in (*self.dp, self.tp) if a)
            return P(None, None, axes or None, None, None)
        if self.kv_head_sharded:
            return P(None, b, None, self.tp, None)
        return P(None, b, self.tp, None, None)

    def ssm_state_spec(self, global_batch: int):
        """SSMState: h (L,B,H,hd,N), conv (L,B,K-1,C)."""
        b = self.batch_axes(global_batch)
        h_heads = self.rules["ssm_head"]
        return (P(None, b, h_heads, None, None), P(None, b, None, self.tp))

    def decode_state_shardings(self, cfg: ModelConfig, global_batch: int):
        """Shardings pytree matching transformer.DecodeState."""
        from repro.models.layers import KVCache
        from repro.models.transformer import DecodeState
        mesh = self.mesh
        ns = lambda spec: NamedSharding(mesh, spec)
        kv = ssm = shared = cross = None
        if cfg.family in ("ssm", "hybrid", "zamba2"):
            from repro.models.ssm import SSMState
            hspec, cspec = self.ssm_state_spec(global_batch)
            ssm = SSMState(h=ns(hspec), conv=ns(cspec))
            if cfg.family in ("hybrid", "zamba2"):
                shared = KVCache(ns(self.kv_cache_spec(global_batch)),
                                 ns(self.kv_cache_spec(global_batch)))
        else:
            kv = KVCache(ns(self.kv_cache_spec(global_batch)),
                         ns(self.kv_cache_spec(global_batch)))
            if cfg.family == "audio":
                # cross cache seq = enc_seq (1500, not TP-divisible): rely on
                # head sharding (whisper kv=16 divides) and keep seq whole
                xspec = P(None, self.batch_axes(global_batch),
                          None, self.tp if self.kv_head_sharded else None, None)
                cross = KVCache(ns(xspec), ns(xspec))
        return DecodeState(kv=kv, ssm=ssm, shared_kv=shared, cross_kv=cross,
                           index=ns(P()))
