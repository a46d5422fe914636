"""Mixture-of-Experts FFN (mixtral / arctic style).

Dispatch is *sort-based* (dropless-up-to-capacity, MegaBlocks-lite): tokens
are argsorted by expert id, each token's position inside its expert bucket
falls out of the sorted order, and tokens are gathered/scattered through
dense (E, C, d) buffers.  Everything is static-shaped and jit/pjit friendly.

**Grouped for the partitioner** (GShard-style): tokens are reshaped to
(G, T_g, d) groups with G sharded over the data axes, and the whole
route→dispatch→combine pipeline is ``vmap``-ed over G.  Batched scatters /
gathers whose batch dim is sharded stay local to the shard — without the
grouping, GSPMD replicated the (tokens·k·cf, d) bucket tensor on every
device (measured: +170 GiB/device on arctic-480b train).

Parallelism modes
-----------------
* **TP (default):** expert weights shard over ``model`` on the expert dim
  when divisible (arctic 128/16) else on the FFN hidden dim (mixtral 8e).
* **EP (optional, ``moe_ep_axis``):** shard_map all-to-all dispatch across
  the data axis — the paper's All-to-All collective pattern (Sec. II-C);
  exercised by tests/benchmarks.

The router aux loss follows Switch Transformer (fraction·probability).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from .modules import Box, dense_init, swiglu


def init_moe(key, cfg, dtype=jnp.float32):
    """Expert-parallel SwiGLU FFN params (+ optional arctic dense residual)."""
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 5)
    params = {
        "router": dense_init(ks[0], (d, E), ("embed", "expert_router"),
                             scale=0.02, dtype=dtype),
        "w_gate": dense_init(ks[1], (E, d, f), ("expert", "embed", "mlp"), dtype=dtype),
        "w_up": dense_init(ks[2], (E, d, f), ("expert", "embed", "mlp"), dtype=dtype),
        "w_down": dense_init(ks[3], (E, f, d), ("expert", "mlp", "embed"), dtype=dtype),
    }
    if cfg.moe_dense_ff:
        # dedicated logical axis: the dense-residual FFN must be Megatron
        # column/row TP-sharded with an UNSHARDED contraction dim — FSDP on
        # d here produced ~800 GiB/dev of partial-sum all-reduce (measured,
        # arctic-480b; see EXPERIMENTS.md §Perf)
        kd = jax.random.split(ks[4], 3)
        params["dense"] = {
            "w_gate": dense_init(kd[0], (d, cfg.moe_dense_ff),
                                 ("embed_unsharded", "mlp_dense"), dtype=dtype),
            "w_up": dense_init(kd[1], (d, cfg.moe_dense_ff),
                               ("embed_unsharded", "mlp_dense"), dtype=dtype),
            "w_down": dense_init(kd[2], (cfg.moe_dense_ff, d),
                                 ("mlp_dense", "embed_unsharded"), dtype=dtype),
        }
    return params


def _route(x2d, router_w, n_experts: int, top_k: int):
    """(T,d) tokens → (expert_idx (T,k), combine_w (T,k), aux scalar)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    combine_w, expert_idx = jax.lax.top_k(probs, top_k)
    combine_w = combine_w / jnp.sum(combine_w, axis=-1, keepdims=True)
    T = x2d.shape[0]
    frac_tokens = jnp.zeros(n_experts).at[expert_idx.reshape(-1)].add(1.0) / (T * top_k)
    frac_probs = jnp.mean(probs, axis=0)
    aux = n_experts * jnp.sum(frac_tokens * frac_probs)
    return expert_idx, combine_w, aux


def _dispatch_indices(expert_idx, n_experts: int, capacity: int):
    """Sort-based bucket slots.  expert_idx: (T, k) → slot (T, k) in the
    flat (E·C) buffer, or -1 when the bucket overflowed (token dropped)."""
    T, k = expert_idx.shape
    flat_e = expert_idx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, jnp.arange(n_experts), side="left")
    pos_sorted = jnp.arange(T * k) - first[sorted_e]
    pos = jnp.zeros(T * k, jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
    keep = pos < capacity
    slot = jnp.where(keep, flat_e * capacity + pos, -1)
    return slot.reshape(T, k)


def _group_dispatch(x_g, router_w, E: int, k: int, capacity: int):
    """Per-group: (T_g, d) → dispatched buckets (E, C, d) + combine info."""
    expert_idx, combine_w, aux = _route(x_g, router_w, E, k)
    slot = _dispatch_indices(expert_idx, E, capacity)            # (T,k)
    flat_slot = slot.reshape(-1)
    src = jnp.repeat(x_g, k, axis=0)
    scatter_idx = jnp.where(flat_slot >= 0, flat_slot, E * capacity)
    buckets = jnp.zeros((E * capacity, x_g.shape[-1]), x_g.dtype)
    buckets = buckets.at[scatter_idx].set(src, mode="drop")
    return buckets.reshape(E, capacity, x_g.shape[-1]), flat_slot, combine_w, aux


def _group_combine(y_e, flat_slot, combine_w, T: int, k: int):
    """Per-group inverse: (E·C, d) expert outputs → (T, d) tokens."""
    safe = jnp.maximum(flat_slot, 0)
    w = jnp.where(flat_slot >= 0, combine_w.reshape(-1), 0.0)
    gathered = y_e[safe] * w[:, None].astype(y_e.dtype)
    return jnp.sum(gathered.reshape(T, k, -1), axis=1)


def moe_ffn(params, x, cfg, *, capacity_factor: float | None = None,
            n_groups: int | None = None,
            constrain=lambda t, kind="residual": t):
    """Apply the MoE FFN.  x: (B, S, d) → ((B, S, d), aux scalar).

    ``constrain`` pins the (G, E, C, d) bucket tensor's sharding (G over
    data, E over model when experts are TP-sharded) so the dispatch→expert
    boundary reshards with one all-to-all-class transfer instead of
    gathering every token onto every expert shard."""
    with jax.named_scope("moe"):
        B, S, d = x.shape
        E, k = cfg.n_experts, cfg.top_k
        cf = capacity_factor or cfg.capacity_factor
        G = n_groups or B                      # per-sequence groups by default
        T_g = B * S // G
        xg = x.reshape(G, T_g, d)
        capacity = max(int(math.ceil(T_g * k * cf / E)), 4)
        capacity = -(-capacity // 4) * 4

        buckets, flat_slot, combine_w, aux = jax.vmap(
            lambda t: _group_dispatch(t, params["router"], E, k, capacity))(xg)
        # buckets: (G, E, C, d) — G carries the data sharding end to end
        buckets = constrain(buckets, "moe_buckets")

        g = jnp.einsum("gecd,edf->gecf", buckets, _v(params["w_gate"]))
        u = jnp.einsum("gecd,edf->gecf", buckets, _v(params["w_up"]))
        h = swiglu(g, u)
        y = jnp.einsum("gecf,efd->gecd", h, _v(params["w_down"]))
        y = constrain(y, "moe_buckets")

        out = jax.vmap(lambda ye, fs, cw: _group_combine(
            ye.reshape(E * capacity, d), fs, cw, T_g, k))(y, flat_slot,
                                                           combine_w)
        out = out.reshape(B, S, d)

        if cfg.moe_dense_ff:
            dn = params["dense"]
            x2d = x.reshape(-1, d)
            dense = swiglu(x2d @ _v(dn["w_gate"]),
                           x2d @ _v(dn["w_up"])) @ _v(dn["w_down"])
            out = out + dense.reshape(B, S, d)
        return out, jnp.mean(aux)


def moe_ffn_ep(params, x, cfg, *, mesh, ep_axis: str,
               capacity_factor: float | None = None):
    """Expert-parallel MoE FFN: explicit shard_map All-to-All dispatch.

    The paper's All-to-All collective pattern (Sec. II-C), written out
    rather than left to GSPMD: experts shard over ``ep_axis`` (a data
    axis of ``mesh``), each rank routes its local tokens and builds full
    (E, C, d) dispatch buckets, a tiled ``jax.lax.all_to_all`` exchanges
    them so every rank holds only its E/n experts' buckets from all n
    ranks — shape (E/n, n·C, d), the Table-I shard-D/n unicast pattern —
    the expert FFN runs on the local weight shard, and the inverse
    all-to-all returns outputs for the local combine.

    Routing, capacity and combine math are shared with :func:`moe_ffn`,
    so the result matches ``moe_ffn(..., n_groups=n)`` (one dispatch
    group per EP rank) up to float reduction order — pinned by
    tests/test_multidevice.py against the dense-gather reference.

    ``x`` must shard its batch dim over ``ep_axis`` (n | B) and expert
    weights their leading E dim (E % n == 0).
    """
    with jax.named_scope("moe"):
        from jax.sharding import PartitionSpec as P

        B, S, d = x.shape
        E, k = cfg.n_experts, cfg.top_k
        cf = capacity_factor or cfg.capacity_factor
        n = mesh.shape[ep_axis]
        if B % n or E % n:
            raise ValueError(f"moe_ffn_ep: batch {B} and n_experts {E} must "
                             f"both divide over ep_axis {ep_axis!r} "
                             f"(size {n})")
        T_l = B * S // n                       # tokens per EP rank
        capacity = max(int(math.ceil(T_l * k * cf / E)), 4)
        capacity = -(-capacity // 4) * 4

        def shard_fn(router_w, wg, wu, wd, x_l):
            T = x_l.shape[0] * x_l.shape[1]
            x2d = x_l.reshape(T, d)
            buckets, flat_slot, combine_w, aux = _group_dispatch(
                x2d, router_w, E, k, capacity)
            # dispatch A2A: keep E/n experts, gather every rank's C slots
            b = jax.lax.all_to_all(buckets, ep_axis, split_axis=0,
                                   concat_axis=1, tiled=True)
            g = jnp.einsum("ecd,edf->ecf", b, wg)
            u = jnp.einsum("ecd,edf->ecf", b, wu)
            h = swiglu(g, u)
            y = jnp.einsum("ecf,efd->ecd", h, wd)
            # combine A2A: the exact inverse exchange
            y = jax.lax.all_to_all(y, ep_axis, split_axis=1,
                                   concat_axis=0, tiled=True)
            out = _group_combine(y.reshape(E * capacity, d), flat_slot,
                                 combine_w, T, k)
            return out.reshape(x_l.shape), jax.lax.pmean(aux, ep_axis)

        fn = jax.shard_map(shard_fn, mesh=mesh,
                           in_specs=(P(), P(ep_axis), P(ep_axis), P(ep_axis),
                                     P(ep_axis)),
                           out_specs=(P(ep_axis), P()),
                           check_vma=False)
        out, aux = fn(_v(params["router"]), _v(params["w_gate"]),
                      _v(params["w_up"]), _v(params["w_down"]), x)

        if cfg.moe_dense_ff:
            dn = params["dense"]
            x2d = x.reshape(-1, d)
            dense = swiglu(x2d @ _v(dn["w_gate"]),
                           x2d @ _v(dn["w_up"])) @ _v(dn["w_down"])
            out = out + dense.reshape(B, S, d)
        return out, aux


def _v(p):
    return p.value if isinstance(p, Box) else p
