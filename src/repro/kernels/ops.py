"""Public entry point for the FULL-W2V kernel family (engine API).

One function — :func:`step` — trains one batch against a
:class:`~repro.kernels.tables.Tables` pytree and resolves *everything*
from its :class:`~repro.kernels.tables.TableSpec`: replicated vs
vocab-sharded dispatch, the exchange flavor (request-exact ``all_to_all``
buckets vs the dense reference), and the storage precision of every
table. Backend selection is data driven: every kernel variant registers a
capability descriptor in ``repro.kernels.registry`` and an
``update(w_in, w_out, step, static)`` implementation; resolution
("auto", tiled mapping, dtype capability, invalid combinations) happens
once against those descriptors.

Registered backends:

* ``jnp`` / ``jnp_tiled`` — the pure-jnp oracles (``kernels.ref``). Fully
  compiled, so also the fastest option on CPU.
* ``pallas`` / ``pallas_pipelined`` — the sequential Pallas kernel
  (``kernels.fullw2v``), the pipelined form adding §3.1 prefetch (window
  t+1's rows DMA while window t computes). TPU-native only.
* ``pallas_tiled`` — the window-tiled Pallas kernel (T windows fused per
  step, DESIGN.md §4). Consumes the host tile schedule carried in
  ``StepInputs.plan_*``. TPU-native only.
* ``pallas_interpret`` / ``pallas_tiled_interpret`` — the same kernels
  under ``interpret=True``: the kernel body executes in Python — identical
  semantics, correctness-only speed. What CI runs in this container.

Mixed-precision storage (DESIGN.md §11): tables stored in ``bfloat16`` /
``int8`` dequantize to f32 at the working-set boundary (VMEM on
hardware), the window-tile update math runs unchanged in f32, and results
store back with *keyed stochastic rounding* (``kernels.quant`` — keys are
pure functions of ``(seed, epoch, batch_index)``, so runs stay
bit-deterministic at any worker count and through chaos recovery). In the
vocab-sharded exchange the cold rows travel *quantized* — int8 payload +
per-row f32 scale, or bf16 — which is where the 2×/4× §8 exchange-byte
reduction comes from. Backends whose kernels can't consume a storage
dtype (``supports_dtypes``) still run it under the f32 master-copy
fallback (``TableSpec.master_copy``): decode → unmodified f32 step →
stochastic re-encode, correct but without the transport win.

``sgns_update`` / ``vocab_sharded_update`` remain as deprecated shims
that warn and forward.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.w2v import W2VConfig, resolve_gemm_windows
from repro.kernels import quant
from repro.kernels import ref as _ref
from repro.kernels import registry
from repro.kernels.fullw2v import (fullw2v_pallas, fullw2v_pallas_tiled,
                                   fullw2v_pallas_tiled_fused)
from repro.kernels.registry import (KernelBackend, KernelStatic, StepInputs,
                                    register)
from repro.kernels.tables import Tables, TableSpec


# ---------------------------------------------------------------------------
# Backend update() implementations (traceable; jit applied by the engine)
# ---------------------------------------------------------------------------

def _seq_args(step: StepInputs):
    return (step.tokens, step.negs, step.lengths,
            jnp.asarray(step.lr, jnp.float32))


def _tiled_args(step: StepInputs, static: KernelStatic):
    assert step.has_plan, "tiled backend requires StepInputs.plan_*"
    return (*_seq_args(step), static.w_f, static.tile, step.plan_uniq,
            step.plan_scatter, step.plan_ucount, step.plan_strict)


def _update_jnp(w_in, w_out, step, static):
    return _ref.batch_sgns_ref(w_in, w_out, *_seq_args(step), static.w_f,
                               static_ids=step.static_ctx, bags=step.bags)


def _update_pallas(w_in, w_out, step, static):
    return fullw2v_pallas(w_in, w_out, *_seq_args(step), static.w_f)


def _update_pallas_pipelined(w_in, w_out, step, static):
    return fullw2v_pallas(w_in, w_out, *_seq_args(step), static.w_f,
                          pipeline=True)


def _update_pallas_interpret(w_in, w_out, step, static):
    return fullw2v_pallas(w_in, w_out, *_seq_args(step), static.w_f,
                          interpret=True)


def _update_jnp_tiled(w_in, w_out, step, static):
    return _ref.batch_sgns_tiled_ref(w_in, w_out,
                                     *_tiled_args(step, static),
                                     gemm_windows=static.gemm_windows,
                                     static_ids=step.static_ctx,
                                     bags=step.bags)


def _update_pallas_tiled(w_in, w_out, step, static):
    return fullw2v_pallas_tiled(w_in, w_out, *_tiled_args(step, static),
                                gemm_windows=static.gemm_windows)


def _update_pallas_tiled_interpret(w_in, w_out, step, static):
    return fullw2v_pallas_tiled(w_in, w_out, *_tiled_args(step, static),
                                gemm_windows=static.gemm_windows,
                                interpret=True)


def _update_fused_pallas_tiled(hot_in, hot_out, got_in, got_out, step, static):
    return fullw2v_pallas_tiled_fused(hot_in, hot_out, got_in, got_out,
                                      *_tiled_args(step, static),
                                      gemm_windows=static.gemm_windows)


def _update_fused_pallas_tiled_interpret(hot_in, hot_out, got_in, got_out,
                                         step, static):
    return fullw2v_pallas_tiled_fused(hot_in, hot_out, got_in, got_out,
                                      *_tiled_args(step, static),
                                      gemm_windows=static.gemm_windows,
                                      interpret=True)


# storage-dtype capability (DESIGN.md §11): the engine dequantizes at the
# working-set boundary, so capability is about what the *kernel's* DMA
# stream can consume on hardware — the native Pallas kernels take bf16
# rows (VMEM converts on load), int8 row+scale decode is implemented in
# the jnp/interpret engines only; native-TPU int8 needs the master-copy
# fallback until a dequantizing DMA path lands (ROADMAP item 2's lane).
_ALL_DTYPES = ("float32", "bfloat16", "int8")
_NATIVE_DTYPES = ("float32", "bfloat16")

# only the jnp oracles consume the workload-frontend StepInputs extensions
# (DESIGN.md §12) so far; the Pallas kernels' DMA schedules don't route
# bag members or the static doc row yet (ROADMAP)
_FRONTENDS = ("static_ctx", "bags")

register(KernelBackend(
    name="jnp", update=_update_jnp,
    description="compiled jnp oracle (kernels.ref.batch_sgns_ref)",
    supports_tiling=True, supports_vocab_shard=True,
    supports_dtypes=_ALL_DTYPES,
    supports_frontends=_FRONTENDS,
    tiled_variant="jnp_tiled"))
register(KernelBackend(
    name="pallas", update=_update_pallas,
    description="sequential Pallas kernel (TPU-native)",
    requires_tpu=True, supports_tiling=True, supports_vocab_shard=True,
    supports_dtypes=_NATIVE_DTYPES,
    tiled_variant="pallas_tiled", interpret_variant="pallas_interpret"))
# pallas_pipelined opts OUT of vocab sharding: its §3.1 prefetch exists to
# hide HBM row latency, but a vocab-sharded step hands the kernel a compact
# VMEM-sized working table — prefetch buys nothing there, so the capable
# variant is plain `pallas` (and "auto" resolves to it).
register(KernelBackend(
    name="pallas_pipelined", update=_update_pallas_pipelined,
    description="sequential Pallas kernel with §3.1 prefetch (TPU-native)",
    requires_tpu=True, supports_pipeline=True, supports_tiling=True,
    supports_dtypes=_NATIVE_DTYPES,
    tiled_variant="pallas_tiled", interpret_variant="pallas_interpret"))
register(KernelBackend(
    name="pallas_interpret", update=_update_pallas_interpret,
    description="sequential Pallas kernel, interpret mode (any platform)",
    supports_tiling=True, supports_vocab_shard=True,
    supports_dtypes=_ALL_DTYPES,
    tiled_variant="pallas_tiled_interpret"))
register(KernelBackend(
    name="jnp_tiled", update=_update_jnp_tiled,
    description="window-tiled jnp oracle (kernels.ref.batch_sgns_tiled_ref)",
    needs_plan=True, supports_vocab_shard=True,
    supports_dtypes=_ALL_DTYPES,
    supports_frontends=_FRONTENDS))
register(KernelBackend(
    name="pallas_tiled", update=_update_pallas_tiled,
    description="window-tiled Pallas kernel (TPU-native, DESIGN.md §4)",
    needs_plan=True, requires_tpu=True, supports_vocab_shard=True,
    supports_dtypes=_NATIVE_DTYPES,
    interpret_variant="pallas_tiled_interpret",
    update_fused=_update_fused_pallas_tiled))
register(KernelBackend(
    name="pallas_tiled_interpret", update=_update_pallas_tiled_interpret,
    description="window-tiled Pallas kernel, interpret mode (any platform)",
    needs_plan=True, supports_vocab_shard=True,
    supports_dtypes=_ALL_DTYPES,
    update_fused=_update_fused_pallas_tiled_interpret))


# ---------------------------------------------------------------------------
# The single dispatch entry point
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _jitted_update(name: str, static: KernelStatic):
    return jax.jit(traceable_update(name, static), donate_argnums=(0, 1))


def static_for(cfg: W2VConfig, tile: int = 1) -> KernelStatic:
    """The static kernel parameters for this config at tile size T."""
    return KernelStatic(
        w_f=cfg.fixed_window, tile=tile,
        gemm_windows=(resolve_gemm_windows(tile, cfg.tile_gemm_windows)
                      if tile > 1 else 0))


def traceable_update(backend: str, static: KernelStatic):
    """The resolved backend's raw traceable ``(w_in, w_out, step) ->
    (w_in, w_out)`` update — for callers that embed it in their own jit or
    shard_map."""
    be = registry.get(backend)

    def run(w_in: jax.Array, w_out: jax.Array, step: StepInputs):
        return be.update(w_in, w_out, step, static)

    return run


@functools.lru_cache(maxsize=None)
def _jitted_mixed_update(name: str, static: KernelStatic, dtype: str):
    """Replicated full-table step for sub-f32 storage: decode → unchanged
    f32 update → keyed stochastic re-encode. Values already representable
    in the storage dtype round-trip exactly, so rows the batch never
    touches do not drift."""
    be = registry.get(name)

    def run(w_in, w_out, step: StepInputs):
        new_in, new_out = be.update(w_in.astype(jnp.float32),
                                    w_out.astype(jnp.float32), step, static)
        k = step.round_key
        new_in, _ = quant.encode_stochastic(new_in, dtype, k,
                                            quant.TAG_FULL_IN)
        new_out, _ = quant.encode_stochastic(new_out, dtype, k,
                                             quant.TAG_FULL_OUT)
        return new_in, new_out

    return jax.jit(run, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def _jitted_dp_update(name: str, static: KernelStatic, dtype: str,
                      mesh: Mesh, axis_name: str, has_plan: bool,
                      has_key: bool, has_doc: bool = False,
                      has_bags: bool = False):
    """The Hogwild data-parallel step: sentences (and tile-plan rows)
    shard over ``axis_name``, each shard updates a local replica, replicas
    pmean-average. Sub-f32 storage decodes before and stochastically
    re-encodes after the average — the key is replicated, so every shard
    rounds the identical averaged values to the identical storage bits."""
    local = traceable_update(name, static)
    mixed = dtype != "float32"

    def local_update(w_in, w_out, step: StepInputs):
        if mixed:
            w_in = w_in.astype(jnp.float32)
            w_out = w_out.astype(jnp.float32)
        new_in, new_out = local(w_in, w_out, step)
        new_in = jax.lax.pmean(new_in, axis_name)
        new_out = jax.lax.pmean(new_out, axis_name)
        if mixed:
            k = step.round_key
            new_in, _ = quant.encode_stochastic(new_in, dtype, k,
                                                quant.TAG_FULL_IN)
            new_out, _ = quant.encode_stochastic(new_out, dtype, k,
                                                 quant.TAG_FULL_OUT)
        return new_in, new_out

    plan_spec = P(axis_name) if has_plan else None
    step_specs = StepInputs(
        tokens=P(axis_name), negs=P(axis_name), lengths=P(axis_name), lr=P(),
        plan_uniq=plan_spec, plan_scatter=plan_spec,
        plan_ucount=plan_spec, plan_strict=plan_spec,
        round_key=P() if has_key else None,
        static_ctx=P(axis_name) if has_doc else None,
        bags=P(axis_name) if has_bags else None)
    sharded = jax.shard_map(
        local_update, mesh=mesh,
        in_specs=(P(), P(), step_specs),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def _jitted_vs_update(name: str, static: KernelStatic, spec: TableSpec,
                      placement, mesh: Mesh, axis_name: str,
                      has_doc: bool = False, has_bags: bool = False):
    """The vocab-sharded step under shard_map: hot replicas P(), cold
    tables (and int8 scales) row-sharded over ``axis_name``, the exchange
    plan sharded by requester."""
    run = _vocab_sharded_run(name, static, placement, axis_name=axis_name,
                             exchange=spec.exchange, spec=spec)
    plan_spec = P(axis_name) if static.tile > 1 else None
    step_specs = StepInputs(
        tokens=P(axis_name), negs=P(axis_name), lengths=P(axis_name), lr=P(),
        plan_uniq=plan_spec, plan_scatter=plan_spec,
        plan_ucount=plan_spec, plan_strict=plan_spec,
        cold_ids=P(axis_name), bucket_ids=P(axis_name),
        bucket_pos=P(axis_name),
        round_key=P() if spec.is_mixed else None,
        static_ctx=P(axis_name) if has_doc else None,
        bags=P(axis_name) if has_bags else None)
    scale_spec = P(axis_name) if spec.needs_scales else None
    sharded = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(), P(), P(axis_name), P(axis_name),
                  scale_spec, scale_spec, step_specs),
        out_specs=(P(), P(), P(axis_name), P(axis_name),
                   scale_spec, scale_spec),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(0, 1, 2, 3, 4, 5))


def step(tables: Tables, step: StepInputs, cfg: W2VConfig,
         backend: str = "auto", mesh=None, axis_name: str = "data"
         ) -> Tables:
    """Train one batch of sentences with FULL-W2V semantics.

    The one engine entry point: everything the old ``sgns_update`` /
    ``vocab_sharded_update`` pair resolved by call-site choice now comes
    from ``tables.spec`` (a :class:`~repro.kernels.tables.TableSpec`) and
    the step's shape:

    * ``tables.placement`` set → the vocab-sharded path (DESIGN.md §8):
      the step must carry an exchange plan (``step.cold_ids`` /
      ``bucket_*`` from ``distributed.vocab_placement.plan_exchange``) and
      a ``mesh``; ``spec.exchange`` picks request-exact ``all_to_all``
      buckets or the dense reference collectives.
    * no placement, ``mesh`` given → Hogwild data parallelism (sentences
      shard, replicas pmean-average).
    * neither → the plain single-replica jit.

    ``step.has_plan`` selects the window-tiled kernel family in every
    case (bit-identical to sequential at T=1). Sub-f32 storage dtypes in
    the spec run decode → f32 update → keyed stochastic re-encode
    (``step.round_key`` required; see ``kernels.quant``); the backend must
    support the dtypes (``registry.resolve(dtypes=...)`` — actionable
    error otherwise) unless ``spec.master_copy`` opts into the f32
    fallback. Returns a new :class:`Tables` with the same spec/placement.
    """
    spec = tables.spec
    if spec.is_mixed and step.round_key is None:
        raise ValueError(
            "TableSpec stores a table below f32 but StepInputs.round_key "
            "is None; attach quant.round_key(cfg.seed, epoch, batch_index) "
            "so stochastic rounding stays bit-deterministic")
    dtypes = () if spec.master_copy else spec.dtypes
    frontends = ((("static_ctx",) if step.has_static_ctx else ())
                 + (("bags",) if step.has_bags else ()))
    if tables.placement is not None:
        if not step.has_vocab_shard:
            raise ValueError(
                "Tables carry a VocabPlacement but StepInputs has no "
                "exchange plan (cold_ids); build the step via "
                "distributed.vocab_placement.plan_exchange")
        if mesh is None:
            raise ValueError(
                "vocab-sharded Tables run under shard_map; pass the "
                "session mesh (a 1-device Mesh works for one shard)")
        be = registry.resolve(backend, tiled=step.has_plan,
                              vocab_shard=True, dtypes=dtypes,
                              frontends=frontends)
        fn = _jitted_vs_update(be.name, static_for(cfg, step.tile), spec,
                               tables.placement, mesh, axis_name,
                               step.has_static_ctx, step.has_bags)
        w_in, w_out, cold_in, cold_out, scale_in, scale_out = fn(
            tables.w_in, tables.w_out, tables.cold_in, tables.cold_out,
            tables.scale_in, tables.scale_out, step)
        return dataclasses.replace(
            tables, w_in=w_in, w_out=w_out, cold_in=cold_in,
            cold_out=cold_out, scale_in=scale_in, scale_out=scale_out)
    if step.has_vocab_shard:
        raise ValueError(
            "StepInputs carries a vocab-sharding exchange plan (cold_ids); "
            "this is the single-replica entry point. Run the step "
            "through a mesh TrainSession with cfg.vocab_shard=True, or "
            "build the step without plan_exchange.")
    be = registry.resolve(backend, tiled=step.has_plan, dtypes=dtypes,
                          frontends=frontends)
    static = static_for(cfg, step.tile)
    if mesh is not None:
        fn = _jitted_dp_update(be.name, static, spec.hot_dtype, mesh,
                               axis_name, step.has_plan,
                               step.round_key is not None,
                               step.has_static_ctx, step.has_bags)
        w_in, w_out = fn(tables.w_in, tables.w_out, step)
    elif spec.hot_dtype == "float32":
        w_in, w_out = _jitted_update(be.name, static)(
            tables.w_in, tables.w_out, step)
    else:
        w_in, w_out = _jitted_mixed_update(be.name, static, spec.hot_dtype)(
            tables.w_in, tables.w_out, step)
    return dataclasses.replace(tables, w_in=w_in, w_out=w_out)


_step = step   # module-level alias: the shims' `step` parameter shadows it


# ---------------------------------------------------------------------------
# Vocab-sharded runner (DESIGN.md §8): hot replica + cold shard exchange
# ---------------------------------------------------------------------------

def _vocab_sharded_run(backend: str, static: KernelStatic, placement,
                       axis_name: str = "data", exchange: str = "exact",
                       spec: TableSpec = TableSpec()):
    """The per-shard update for vocab-sharded tables, to run under
    ``shard_map`` over ``axis_name``.

    Signature of the returned function (all arguments are the *local*
    blocks shard_map hands each device):

        run(hot_in, hot_out, cold_in, cold_out, scale_in, scale_out, step)
            -> (hot_in', hot_out', cold_in', cold_out',
                scale_in', scale_out')

    where ``hot_*`` are the replicated ``(hot, d)`` head tables, ``cold_*``
    the local ``(cold_per_shard, d)`` shard of the striped cold tail
    (stored in ``spec.cold_dtype``), ``scale_*`` the per-row int8 scales
    (``None`` unless ``spec.needs_scales``), and ``step`` a
    :class:`~repro.kernels.registry.StepInputs` built by
    ``distributed.vocab_placement.plan_exchange``.

    One step does, entirely on-device (DESIGN.md §8 exchange math):

    1. **Gather** (``exchange="exact"``, the default) — ``all_to_all`` the
       per-owner request buckets (ints, O(n·C) ≈ O(R)), serve the rows
       this shard owns *in storage precision* (int8 payload + per-row
       scale, bf16, or f32), ``all_to_all`` the values back, decode to
       f32, and scatter into request order via the host-planned bucket
       positions: every shard sends and receives O(R·d·itemsize) bytes —
       request-exact and precision-proportional. ``exchange="dense"``
       keeps the PR 5 all_gather + ``psum_scatter`` path (f32, O(n·R·d)
       per device) as the parity reference.
    2. **Compute** — run the resolved backend on the compact f32 working
       table of ``hot + R`` rows: backends declaring
       ``supports_fused_gather`` are handed the hot replica and the
       gathered block as *separate* buffers; the rest run unchanged on
       ``concat(hot, gathered)``.
    3. **Write back** — pmean the hot head across shards (Hogwild
       averaging; bf16 heads then stochastic-round identically on every
       shard — the round key is replicated). Updated request rows route
       back to their owners (transport-quantized round-to-nearest on the
       exact path), are decoded and scatter-added, and each touched row
       averages over all ``n`` replicas' contributions before
       re-encoding to storage with keyed stochastic rounding (key folded
       with the owner's axis index). Untouched rows keep their exact
       storage bytes (``where`` on the touched mask).

    With ``spec.master_copy`` and a backend that lacks the storage
    dtypes, the whole f32 path runs between a full decode and a full
    stochastic re-encode instead — correct everywhere, no transport win.
    """
    be = registry.get(backend)
    if not be.supports_vocab_shard:
        raise ValueError(
            f"backend {backend!r} does not support vocab-sharded tables; "
            f"resolve with vocab_shard=True to get an actionable choice")
    if exchange not in ("exact", "dense"):
        raise ValueError(f"exchange must be 'exact' or 'dense', "
                         f"got {exchange!r}")
    hot = placement.hot
    cps = placement.cold_per_shard
    n = placement.n_shards
    hot_dt, cold_dt = spec.hot_dtype, spec.cold_dtype
    native = all(d in be.supports_dtypes for d in spec.dtypes)

    def compute(hot_in, hot_out, got_in, got_out, step):
        """Run the backend on the working table; return (new_hot_in,
        new_hot_out, new_got_in, new_got_out)."""
        if be.supports_fused_gather:
            return be.update_fused(hot_in, hot_out, got_in, got_out,
                                   step, static)
        w_in_work = jnp.concatenate([hot_in, got_in], axis=0)
        w_out_work = jnp.concatenate([hot_out, got_out], axis=0)
        new_in, new_out = be.update(w_in_work, w_out_work, step, static)
        return new_in[:hot], new_out[:hot], new_in[hot:], new_out[hot:]

    def hogwild_mean(cold, acc, kcnt):
        """Owner-side merge: sum of the k updated replicas of each touched
        row plus (n - k) copies of the pre-step value, divided by n."""
        touched = kcnt[:, None] > 0
        return jnp.where(touched, (acc + (n - kcnt)[:, None] * cold) / n,
                         cold)

    # -- the f32 paths (bit-identical to the pre-TableSpec engine) ----------
    def run_dense_f32(hot_in, hot_out, cold_in, cold_out, step: StepInputs):
        me = jax.lax.axis_index(axis_name)
        ids_all = jax.lax.all_gather(step.cold_ids[0], axis_name)  # (n, R)
        valid = ids_all >= 0
        ci = jnp.where(valid, ids_all - hot, 0)
        mine = valid & (ci % n == me)
        lidx = jnp.where(mine, ci // n, 0)                         # (n, R)

        def gather(cold):
            served = jnp.where(mine[..., None], cold[lidx], 0.0)   # (n,R,d)
            return jax.lax.psum_scatter(
                served, axis_name, scatter_dimension=0, tiled=True)[0]

        hot_in_new, hot_out_new, new_got_in, new_got_out = compute(
            hot_in, hot_out, gather(cold_in), gather(cold_out), step)
        hot_in_new = jax.lax.pmean(hot_in_new, axis_name)
        hot_out_new = jax.lax.pmean(hot_out_new, axis_name)

        tgt = jnp.where(mine, lidx, cps).reshape(-1)     # cps -> dropped
        kcnt = jnp.zeros((cps,), jnp.float32).at[tgt].add(
            mine.reshape(-1).astype(jnp.float32), mode="drop")

        def write_back(cold, new_rows):
            upd_all = jax.lax.all_gather(new_rows, axis_name)      # (n,R,d)
            contrib = jnp.where(mine[..., None], upd_all, 0.0)
            acc = jnp.zeros_like(cold).at[tgt].add(
                contrib.reshape(-1, contrib.shape[-1]), mode="drop")
            return hogwild_mean(cold, acc, kcnt)

        cold_in_new = write_back(cold_in, new_got_in)
        cold_out_new = write_back(cold_out, new_got_out)
        return hot_in_new, hot_out_new, cold_in_new, cold_out_new

    def run_exact_f32(hot_in, hot_out, cold_in, cold_out, step: StepInputs):
        r_width = step.cold_ids.shape[-1]                # R (static)
        req = step.bucket_ids[0]                         # (n, C) by owner
        pos = step.bucket_pos[0]                         # (n, C), pad = R
        # swap requester<->owner axes: got_req[s] = the bucket shard s
        # addressed to me — the only rows I must serve
        got_req = jax.lax.all_to_all(req, axis_name, 0, 0)
        serve = got_req >= 0
        lrow = jnp.where(serve, (got_req - hot) // n, 0)  # local rows

        def gather(cold):
            served = jnp.where(serve[..., None], cold[lrow], 0.0)  # (n,C,d)
            vals = jax.lax.all_to_all(served, axis_name, 0, 0)
            # vals[o, c] is the value of req[o, c]; land it at its first-
            # seen position in the gathered working block (pads drop)
            return jnp.zeros((r_width, cold.shape[-1]), cold.dtype).at[
                pos.reshape(-1)].set(
                    vals.reshape(-1, vals.shape[-1]), mode="drop")

        hot_in_new, hot_out_new, new_got_in, new_got_out = compute(
            hot_in, hot_out, gather(cold_in), gather(cold_out), step)
        hot_in_new = jax.lax.pmean(hot_in_new, axis_name)
        hot_out_new = jax.lax.pmean(hot_out_new, axis_name)

        tgt = jnp.where(serve, lrow, cps).reshape(-1)    # cps -> dropped
        kcnt = jnp.zeros((cps,), jnp.float32).at[tgt].add(
            serve.reshape(-1).astype(jnp.float32), mode="drop")
        reqv = req >= 0
        pos_c = jnp.where(reqv, pos, 0)

        def write_back(cold, new_rows):
            upd = jnp.where(reqv[..., None], new_rows[pos_c], 0.0)  # (n,C,d)
            back = jax.lax.all_to_all(upd, axis_name, 0, 0)
            # back[s] holds shard s's updated replicas of rows I own, in
            # the same slots as got_req[s]
            acc = jnp.zeros_like(cold).at[tgt].add(
                back.reshape(-1, back.shape[-1]), mode="drop")
            return hogwild_mean(cold, acc, kcnt)

        cold_in_new = write_back(cold_in, new_got_in)
        cold_out_new = write_back(cold_out, new_got_out)
        return hot_in_new, hot_out_new, cold_in_new, cold_out_new

    run_f32 = run_exact_f32 if exchange == "exact" else run_dense_f32

    if not spec.is_mixed:
        def run_plain(hot_in, hot_out, cold_in, cold_out, scale_in,
                      scale_out, step):
            return (*run_f32(hot_in, hot_out, cold_in, cold_out, step),
                    None, None)
        return run_plain

    def requant_cold(merged, cold, scale, touched, key, tag):
        """Touched rows re-encode to storage with the keyed stochastic
        round (key folded with owner shard index so shards draw distinct
        noise); untouched rows keep their exact storage bytes."""
        k = jax.random.fold_in(jax.random.fold_in(key, tag),
                               jax.lax.axis_index(axis_name))
        if cold_dt == "int8":
            qn, sn = quant.int8_stochastic(merged, k)
            return (jnp.where(touched[:, None], qn, cold),
                    jnp.where(touched, sn, scale))
        if cold_dt == "bfloat16":
            bn = quant.bf16_stochastic(merged, k)
            return jnp.where(touched[:, None], bn, cold), None
        return merged, None

    def requant_hot(hot_in_new, hot_out_new, key):
        if hot_dt == "bfloat16":
            hot_in_new = quant.bf16_stochastic(
                hot_in_new, jax.random.fold_in(key, quant.TAG_HOT_IN))
            hot_out_new = quant.bf16_stochastic(
                hot_out_new, jax.random.fold_in(key, quant.TAG_HOT_OUT))
        return hot_in_new, hot_out_new

    if not native:
        # f32 master-copy fallback: full decode -> unmodified f32 path ->
        # full stochastic re-encode (whole blocks: correct and
        # deterministic, but cold rows re-encode every step and the
        # transport stays f32)
        def run_master(hot_in, hot_out, cold_in, cold_out, scale_in,
                       scale_out, step):
            k = step.round_key
            nhi, nho, nci, nco = run_f32(
                quant.decode(hot_in, None, hot_dt),
                quant.decode(hot_out, None, hot_dt),
                quant.decode(cold_in, scale_in, cold_dt),
                quant.decode(cold_out, scale_out, cold_dt), step)
            nhi, nho = requant_hot(nhi, nho, k)
            all_rows = jnp.ones((cps,), bool)
            nci, nsi = requant_cold(nci, cold_in, scale_in, all_rows, k,
                                    quant.TAG_COLD_IN)
            nco, nso = requant_cold(nco, cold_out, scale_out, all_rows, k,
                                    quant.TAG_COLD_OUT)
            return nhi, nho, nci, nco, nsi, nso
        return run_master

    # -- native mixed paths: quantized transport ----------------------------
    def run_dense_mixed(hot_in, hot_out, cold_in, cold_out, scale_in,
                        scale_out, step: StepInputs):
        me = jax.lax.axis_index(axis_name)
        ids_all = jax.lax.all_gather(step.cold_ids[0], axis_name)
        valid = ids_all >= 0
        ci = jnp.where(valid, ids_all - hot, 0)
        mine = valid & (ci % n == me)
        lidx = jnp.where(mine, ci // n, 0)
        k = step.round_key

        def gather(cold, scale):
            cold_f = quant.decode(cold, scale, cold_dt)
            served = jnp.where(mine[..., None], cold_f[lidx], 0.0)
            return jax.lax.psum_scatter(
                served, axis_name, scatter_dimension=0, tiled=True)[0]

        hot_in_f = hot_in.astype(jnp.float32) if hot_dt != "float32" \
            else hot_in
        hot_out_f = hot_out.astype(jnp.float32) if hot_dt != "float32" \
            else hot_out
        hot_in_new, hot_out_new, new_got_in, new_got_out = compute(
            hot_in_f, hot_out_f, gather(cold_in, scale_in),
            gather(cold_out, scale_out), step)
        hot_in_new = jax.lax.pmean(hot_in_new, axis_name)
        hot_out_new = jax.lax.pmean(hot_out_new, axis_name)
        hot_in_new, hot_out_new = requant_hot(hot_in_new, hot_out_new, k)

        tgt = jnp.where(mine, lidx, cps).reshape(-1)
        kcnt = jnp.zeros((cps,), jnp.float32).at[tgt].add(
            mine.reshape(-1).astype(jnp.float32), mode="drop")
        touched = kcnt > 0

        def write_back(cold, scale, new_rows, tag):
            upd_all = jax.lax.all_gather(new_rows, axis_name)
            contrib = jnp.where(mine[..., None], upd_all, 0.0)
            local_f = quant.decode(cold, scale, cold_dt)
            acc = jnp.zeros_like(local_f).at[tgt].add(
                contrib.reshape(-1, contrib.shape[-1]), mode="drop")
            merged = hogwild_mean(local_f, acc, kcnt)
            return requant_cold(merged, cold, scale, touched, k, tag)

        cold_in_new, scale_in_new = write_back(
            cold_in, scale_in, new_got_in, quant.TAG_COLD_IN)
        cold_out_new, scale_out_new = write_back(
            cold_out, scale_out, new_got_out, quant.TAG_COLD_OUT)
        return (hot_in_new, hot_out_new, cold_in_new, cold_out_new,
                scale_in_new, scale_out_new)

    def run_exact_mixed(hot_in, hot_out, cold_in, cold_out, scale_in,
                        scale_out, step: StepInputs):
        d = cold_in.shape[-1]
        r_width = step.cold_ids.shape[-1]
        req = step.bucket_ids[0]
        pos = step.bucket_pos[0]
        got_req = jax.lax.all_to_all(req, axis_name, 0, 0)
        serve = got_req >= 0
        lrow = jnp.where(serve, (got_req - hot) // n, 0)
        k = step.round_key

        def gather(cold, scale):
            # rows travel in storage precision: int8 payload + per-row f32
            # scale (d+4 bytes/row) or bf16 (2d) instead of f32 (4d) — the
            # §11 exchange-byte reduction
            if cold_dt == "int8":
                zero_q = jnp.zeros((), cold.dtype)
                sq = jnp.where(serve[..., None], cold[lrow], zero_q)
                ss = jnp.where(serve, scale[lrow], 0.0)
                vq = jax.lax.all_to_all(sq, axis_name, 0, 0)
                vs = jax.lax.all_to_all(ss, axis_name, 0, 0)
                vals = vq.astype(jnp.float32) * vs[..., None]
            else:
                zero = jnp.zeros((), cold.dtype)
                sv = jnp.where(serve[..., None], cold[lrow], zero)
                vals = jax.lax.all_to_all(
                    sv, axis_name, 0, 0).astype(jnp.float32)
            return jnp.zeros((r_width, d), jnp.float32).at[
                pos.reshape(-1)].set(vals.reshape(-1, d), mode="drop")

        hot_in_f = hot_in.astype(jnp.float32) if hot_dt != "float32" \
            else hot_in
        hot_out_f = hot_out.astype(jnp.float32) if hot_dt != "float32" \
            else hot_out
        hot_in_new, hot_out_new, new_got_in, new_got_out = compute(
            hot_in_f, hot_out_f, gather(cold_in, scale_in),
            gather(cold_out, scale_out), step)
        hot_in_new = jax.lax.pmean(hot_in_new, axis_name)
        hot_out_new = jax.lax.pmean(hot_out_new, axis_name)
        hot_in_new, hot_out_new = requant_hot(hot_in_new, hot_out_new, k)

        tgt = jnp.where(serve, lrow, cps).reshape(-1)
        kcnt = jnp.zeros((cps,), jnp.float32).at[tgt].add(
            serve.reshape(-1).astype(jnp.float32), mode="drop")
        touched = kcnt > 0
        reqv = req >= 0
        pos_c = jnp.where(reqv, pos, 0)

        def write_back(cold, scale, new_rows, tag):
            upd = jnp.where(reqv[..., None], new_rows[pos_c], 0.0)
            # transport quantization is *nearest* (deterministic): the
            # value is re-rounded at the storage seam anyway, stochastic
            # noise here would just widen the hogwild average
            if cold_dt == "int8":
                ts = quant.int8_scale(upd)                      # (n, C)
                tq, _ = quant.int8_nearest(upd, ts)
                bq = jax.lax.all_to_all(tq, axis_name, 0, 0)
                bs = jax.lax.all_to_all(ts, axis_name, 0, 0)
                back = bq.astype(jnp.float32) * bs[..., None]
            elif cold_dt == "bfloat16":
                back = jax.lax.all_to_all(
                    upd.astype(jnp.bfloat16), axis_name, 0, 0
                ).astype(jnp.float32)
            else:
                back = jax.lax.all_to_all(upd, axis_name, 0, 0)
            local_f = quant.decode(cold, scale, cold_dt)
            acc = jnp.zeros((cps, d), jnp.float32).at[tgt].add(
                back.reshape(-1, d), mode="drop")
            merged = hogwild_mean(local_f, acc, kcnt)
            return requant_cold(merged, cold, scale, touched, k, tag)

        cold_in_new, scale_in_new = write_back(
            cold_in, scale_in, new_got_in, quant.TAG_COLD_IN)
        cold_out_new, scale_out_new = write_back(
            cold_out, scale_out, new_got_out, quant.TAG_COLD_OUT)
        return (hot_in_new, hot_out_new, cold_in_new, cold_out_new,
                scale_in_new, scale_out_new)

    return run_exact_mixed if exchange == "exact" else run_dense_mixed


# ---------------------------------------------------------------------------
# Deprecated shims (warn and forward)
# ---------------------------------------------------------------------------

def sgns_update(
    w_in: jax.Array,      # (V, d) f32 — donated
    w_out: jax.Array,     # (V, d) f32 — donated
    step: StepInputs,     # tokens/negs/lengths/lr (+ tile plan if T > 1)
    cfg: W2VConfig,
    backend: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Deprecated: use :func:`step` with a plain f32 ``Tables``.

    Kept as a thin shim — warns and forwards to the unified entry point
    with ``Tables(w_in=w_in, w_out=w_out)`` (an f32 replicated spec), so
    existing callers keep their exact semantics, including the rejection
    of vocab-sharded steps.
    """
    warnings.warn(
        "ops.sgns_update is deprecated; use ops.step(Tables(w_in=..., "
        "w_out=...), step, cfg, backend=...) — the TableSpec-driven entry "
        "point that also covers sharded and mixed-precision tables",
        DeprecationWarning, stacklevel=2)
    out = _step(Tables(w_in=w_in, w_out=w_out), step, cfg, backend=backend)
    return out.w_in, out.w_out


def vocab_sharded_update(backend: str, static: KernelStatic, placement,
                         axis_name: str = "data", exchange: str = "exact"):
    """Deprecated: use :func:`step` with vocab-sharded ``Tables``.

    Kept as a thin shim — warns and returns the f32 per-shard runner with
    the pre-TableSpec ``(hot_in, hot_out, cold_in, cold_out, step) ->
    4-tuple`` signature, for callers that embed it in their own
    ``shard_map``.
    """
    warnings.warn(
        "ops.vocab_sharded_update is deprecated; use ops.step with "
        "vocab-sharded Tables (spec/placement metadata select the "
        "exchange), or _vocab_sharded_run for a raw per-shard runner",
        DeprecationWarning, stacklevel=2)
    run = _vocab_sharded_run(backend, static, placement,
                             axis_name=axis_name, exchange=exchange,
                             spec=TableSpec(vocab_shard=True))

    def run4(hot_in, hot_out, cold_in, cold_out, step):
        return run(hot_in, hot_out, cold_in, cold_out, None, None, step)[:4]

    return run4
