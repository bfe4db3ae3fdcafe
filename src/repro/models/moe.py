"""Mixture-of-Experts FFN with capacity-factor dense dispatch.

TPU-native formulation (no torch-style all_to_all): top-k routing, position-
in-expert via cumsum, scatter into a per-expert (E, C, d) buffer, grouped
expert GEMMs, gather+combine. Experts shard over the ``model`` mesh axis
(expert parallelism); the capacity dim shards over ``data``. Token-overflow
beyond capacity is dropped (standard Switch/GShard semantics).

Arctic-style ``dense_residual`` adds a small always-on MLP in parallel.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import init_mlp, mlp_block

Params = Dict[str, jax.Array]


def init_moe(cfg: ArchConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    moe = cfg.moe
    d, ff, e = cfg.d_model, cfg.d_ff, moe.num_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "w_router": jax.random.normal(k1, (d, e), jnp.float32) * d ** -0.5,
        "we_gate": jax.random.normal(k2, (e, d, ff), dtype) * d ** -0.5,
        "we_up": jax.random.normal(k3, (e, d, ff), dtype) * d ** -0.5,
        "we_down": jax.random.normal(k4, (e, ff, d), dtype) * ff ** -0.5,
    }
    if moe.dense_residual:
        p["residual"] = init_mlp(d, moe.dense_residual_ff, k5, dtype)
    return p


def _local_expert_pass(cfg: ArchConfig, x: jax.Array, router: jax.Array,
                       we_gate: jax.Array, we_up: jax.Array,
                       we_down: jax.Array, e0: jax.Array,
                       n_experts: int) -> jax.Array:
    """Single-device expert pass: route ALL local tokens, process the
    experts owned by this shard ([e0, e0+e_loc)), return this shard's
    partial output (T, d). Pure local ops — no collectives."""
    moe = cfg.moe
    k = moe.top_k
    t, d = x.shape
    e_loc = we_gate.shape[0]

    logits = x.astype(jnp.float32) @ router                    # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    gvals, gidx = jax.lax.top_k(gates, k)                      # (T, K)
    gvals = gvals / jnp.maximum(gvals.sum(-1, keepdims=True), 1e-9)

    rel = gidx - e0                                            # (T, K)
    mine = (rel >= 0) & (rel < e_loc)
    rel_flat = jnp.where(mine, rel, e_loc).reshape(t * k)      # overflow row
    onehot = jax.nn.one_hot(rel_flat, e_loc + 1, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1    # (T*K,)
    cap = min(max(1, int(k * t * moe.capacity_factor / n_experts)), t)
    keep = mine.reshape(t * k) & (pos < cap)
    safe_pos = jnp.where(keep, pos, cap)

    xrep = jnp.repeat(x, k, axis=0)                            # (T*K, d)
    buf = jnp.zeros((e_loc + 1, cap + 1, d), x.dtype)
    buf = buf.at[rel_flat, safe_pos].add(xrep)
    buf = buf[:e_loc, :cap]                                    # (E_loc, C, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, we_gate))
    h = h * jnp.einsum("ecd,edf->ecf", buf, we_up)
    h = jnp.einsum("ecf,efd->ecd", h, we_down)                 # (E_loc, C, d)

    hpad = jnp.pad(h, ((0, 1), (0, 1), (0, 0)))
    out = hpad[jnp.minimum(rel_flat, e_loc), safe_pos]         # (T*K, d)
    out = out * (gvals.reshape(t * k, 1).astype(out.dtype)
                 * keep[:, None].astype(out.dtype))
    return out.reshape(t, k, d).sum(1)                         # (T, d) partial


def _moe_shardmap(cfg: ArchConfig, p: Params, x: jax.Array,
                  rules) -> jax.Array:
    """Expert parallelism via shard_map: every (data, model) shard routes
    its model-replicated token block against ALL experts but processes only
    its local experts; partial outputs psum over `model` (one bf16
    stream-sized all-reduce per layer — the EP combine). Expert weights are
    ZeRO-3 sharded over the data axes and all-gathered per layer.

    Pure-pjit formulations of the dispatch scatter degenerate under GSPMD
    (multi-index scatter onto a sharded expert dim -> replication storms,
    EXPERIMENTS.md §Perf iteration 3); local scatter under shard_map is the
    production formulation (cf. MaxText/praxis).
    """
    mesh = rules.mesh
    moe = cfg.moe
    b, s, d = x.shape
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    # specs must MATCH the actual param shardings (incl. serve-mode fsdp
    # overrides), else pjit inserts reshards at the shard_map boundary
    fsdp_gate = rules.resolve("fsdp", p["we_gate"].shape[1],
                              allow_uneven=False)
    fsdp_down = rules.resolve("fsdp", p["we_down"].shape[2],
                              allow_uneven=False)
    gate_spec = P("model", fsdp_gate, None)
    down_spec = P("model", None, fsdp_down)
    batch_axes = (data_axes
                  if b % max(rules._axes_size(data_axes), 1) == 0 else None)

    def local_fn(xb, router, wg, wu, wd):
        # xb: (B_loc, S, d); weights: local expert blocks (ZeRO-sharded)
        if fsdp_gate is not None:
            wg = jax.lax.all_gather(wg, fsdp_gate, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_gate, axis=1, tiled=True)
        if fsdp_down is not None:
            wd = jax.lax.all_gather(wd, fsdp_down, axis=2, tiled=True)
        e_loc = wg.shape[0]
        e0 = jax.lax.axis_index("model") * e_loc
        t_loc = xb.shape[0] * xb.shape[1]
        out = _local_expert_pass(cfg, xb.reshape(t_loc, d), router,
                                 wg, wu, wd, e0, moe.num_experts)
        out = jax.lax.psum(out.astype(jnp.bfloat16), "model")
        return out.reshape(xb.shape).astype(xb.dtype)

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(batch_axes, None, None), P(), gate_spec, gate_spec,
                  down_spec),
        out_specs=P(batch_axes, None, None),
        check_vma=False)
    out = fn(x, p["w_router"], p["we_gate"], p["we_up"], p["we_down"])
    if moe.dense_residual:
        out = out + mlp_block(p["residual"], x.reshape(b * s, d),
                              cfg.bf16_reduce).reshape(b, s, d)
    return out


def _dispatch_groups(t: int) -> int:
    """Number of independent dispatch groups = data-shard count (GShard's
    G dim): position-in-expert and scatter/gather stay shard-local, so the
    only cross-device MoE traffic is the expert GEMM itself."""
    from repro.distributed.sharding import current_rules
    rules = current_rules()
    if rules is None:
        return 1
    g = rules._axes_size(rules._present(("pod", "data")))
    return g if g > 1 and t % g == 0 else 1


def moe_block(cfg: ArchConfig, p: Params, x: jax.Array) -> jax.Array:
    """x: (B, S, d) -> (B, S, d)."""
    from repro.distributed.sharding import constrain, current_rules
    rules = current_rules()
    if (rules is not None and rules.mesh.shape.get("model", 1) > 1
            and cfg.moe.num_experts % rules.mesh.shape["model"] == 0
            # pure-DP rules disable expert parallelism -> local path
            and rules.resolve("experts", cfg.moe.num_experts,
                              allow_uneven=False) is not None):
        return _moe_shardmap(cfg, p, x, rules)
    moe = cfg.moe
    e, k = moe.num_experts, moe.top_k
    b, s, d = x.shape
    t = b * s
    grp = _dispatch_groups(t)
    tg = t // grp                                              # tokens/group
    xf = x.reshape(grp, tg, d)
    xf = constrain(xf, "expert_groups", None, None)

    # --- route ---
    logits = (xf.astype(jnp.float32) @ p["w_router"])          # (G, Tg, E)
    gates = jax.nn.softmax(logits, axis=-1)
    gvals, gidx = jax.lax.top_k(gates, k)                      # (G, Tg, K)
    gvals = gvals / jnp.maximum(gvals.sum(-1, keepdims=True), 1e-9)

    # --- position-in-expert: group-local cumsum (no cross-shard prefix) ---
    flat_e = gidx.reshape(grp, tg * k)                         # (G, Tg*K)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)        # (G, Tg*K, E)
    pos = (jnp.cumsum(onehot, axis=1) * onehot).sum(-1) - 1    # (G, Tg*K)
    cap = min(max(1, int(k * tg * moe.capacity_factor / e)), tg)
    keep = pos < cap
    safe_pos = jnp.where(keep, pos, cap)                       # overflow slot

    # --- dispatch: (G, E, C+1, d) buffer. The scatter output stays
    # model-REPLICATED (each model shard redundantly builds its data
    # group's buffer — scatter onto a model-sharded expert dim would make
    # GSPMD replicate the whole dispatch with giant gathers); the GEMM
    # input is then a local slice of it.
    xrep = jnp.repeat(xf, k, axis=1)                           # (G, Tg*K, d)
    gi = jnp.arange(grp)[:, None] * jnp.ones((1, tg * k), jnp.int32)
    buf = jnp.zeros((grp, e, cap + 1, d), x.dtype)
    buf = buf.at[gi, flat_e, safe_pos].add(xrep)
    buf = constrain(buf, "expert_groups", None, None, None)
    buf = buf[:, :, :cap]                                      # (G, E, C, d)
    buf = constrain(buf, "expert_groups", "experts", None, None)  # local slice

    # --- expert GEMMs (experts over `model`, groups over `data`) ---
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["we_gate"]))
    h = h * jnp.einsum("gecd,edf->gecf", buf, p["we_up"])
    h = jnp.einsum("gecf,efd->gecd", h, p["we_down"])          # (G, E, C, d)
    # combine gathers over the expert dim -> bring results model-replicated
    # (one (E,C,d)-sized all-gather per layer: the EP "combine" collective)
    h = constrain(h, "expert_groups", None, None, None)

    # --- combine (group-local gather) ---
    hpad = jnp.concatenate([h, jnp.zeros((grp, e, 1, d), h.dtype)], axis=2)
    out = hpad[gi, flat_e, safe_pos]                           # (G, Tg*K, d)
    out = out * (gvals.reshape(grp, tg * k, 1).astype(out.dtype)
                 * keep[..., None].astype(out.dtype))
    out = out.reshape(grp, tg, k, d).sum(2)                    # (G, Tg, d)

    if moe.dense_residual:
        out = out + mlp_block(p["residual"], xf, cfg.bf16_reduce)
    return out.reshape(b, s, d)
