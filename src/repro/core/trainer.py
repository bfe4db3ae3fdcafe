"""W2V training sessions: streaming steps, LR decay, Hogwild data
parallelism, checkpoint/resume, metrics callbacks.

:class:`TrainSession` owns everything around the kernel: the classic
linear LR schedule, the Hogwild mesh averaging of the paper's multi-GPU
future-work, periodic checkpointing with resume (``train.checkpoint`` —
atomic, reshard-on-load), and per-step metrics. The kernel itself is
reached exclusively through the engine API (``kernels.ops.step`` /
``kernels.registry``): the session's :class:`TableSpec` (from
``cfg.tables`` / the legacy knobs) is resolved once against the registry
at construction, so invalid combinations — unknown backend, TPU-only
backend off-TPU, storage dtypes the backend's kernels can't consume —
fail fast with the fix spelled out rather than mid-epoch.

Every trained batch goes through ``ops.step(tables, step, cfg)``: the
replicated single-device jit, the Hogwild data-parallel path (sentences
shard over the ``data`` mesh axis, table replicas pmean-average), and the
vocab-sharded path (DESIGN.md §8: replicated Zipf-hot head, cold tail
striped over ``data``, request-exact cold-row exchange planned host-side
by ``distributed.vocab_placement``) are all dispatch outcomes of the
``Tables`` the session hands it. The window-tiled kernel family
(``cfg.tile_windows > 1``) composes with every path. Mixed-precision
storage (``cfg.tables`` — DESIGN.md §11) stores the hot head in bf16
and/or the cold tail in bf16/int8 with per-row scales; the session
attaches the per-batch rounding key so stochastic storage rounding stays
bit-deterministic across worker counts and chaos recoveries.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable, Dict, Iterator, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import tracing
from repro.configs.w2v import W2VConfig
from repro.data.batching import Batch, BatchingPipeline
from repro.kernels import ops, quant, registry
from repro.kernels import tables as tables_mod
from repro.kernels.registry import StepInputs
from repro.kernels.tables import Tables, TableSpec

log = logging.getLogger("repro.trainer")


@dataclasses.dataclass
class TrainState:
    """Training state: embedding tables + progress counters.

    Replicated sessions hold the full ``(V, d)`` tables in ``w_in`` /
    ``w_out``. Vocab-sharded sessions (``cfg.vocab_shard``) hold the
    replicated hot head there instead, plus the striped cold tail in
    ``cold_in`` / ``cold_out`` (``(cold_pad, d)``, rows over the ``data``
    axis — DESIGN.md §8). Tables live in their *storage* dtypes
    (``TableSpec``): int8 cold tails carry per-row f32 scales in
    ``scale_in`` / ``scale_out``, row-sharded exactly like the cold rows.
    """
    w_in: jax.Array
    w_out: jax.Array
    words_seen: int = 0
    batches_seen: int = 0
    epoch: int = 0
    epoch_batch: int = 0   # batches completed within the current epoch
    cold_in: Optional[jax.Array] = None    # vocab-sharded cold tail
    cold_out: Optional[jax.Array] = None
    scale_in: Optional[jax.Array] = None   # int8 per-row scales (cold)
    scale_out: Optional[jax.Array] = None

    def params(self) -> Dict[str, jax.Array]:
        """Checkpointable table pytree (split names when vocab-sharded;
        int8 cold tails include their per-row scale leaves)."""
        if self.cold_in is not None:
            out = {"hot_in": self.w_in, "hot_out": self.w_out,
                   "cold_in": self.cold_in, "cold_out": self.cold_out}
            if self.scale_in is not None:
                out["scale_in"] = self.scale_in
                out["scale_out"] = self.scale_out
            return out
        return {"w_in": self.w_in, "w_out": self.w_out}


@dataclasses.dataclass
class StepMetrics:
    """Per-batch metrics yielded by :meth:`TrainSession.stream`.

    ``fetch_seconds`` is the time the step loop spent *blocked waiting* for
    this batch from the host pipeline — the overlap-efficiency signal: with
    prefetch on it should collapse toward zero while the device stays busy.
    ``queue_depth`` is the async pipeline's ready-batch depth when this
    batch was taken (-1 for synchronous pipelines). ``skipped`` marks a
    poison batch the supervisor excised (counters advanced, tables
    untouched — DESIGN.md §9).
    """
    epoch: int
    batches_seen: int
    words_seen: int
    batch_words: int
    lr: float
    backend: str
    fetch_seconds: float = 0.0
    queue_depth: int = -1
    skipped: bool = False


def init_state(vocab_size: int, cfg: W2VConfig, seed: int = 0,
               placement=None, mesh: Optional[Mesh] = None,
               spec: Optional[TableSpec] = None) -> TrainState:
    """Mikolov init: w_in ~ U(-0.5/d, 0.5/d), w_out = 0.

    With a ``placement`` (vocab sharding), the *same* full-table init is
    drawn and then split hot/cold — so a sharded session starts from
    exactly the tables a replicated one would (the parity baseline), and
    the cold tail is placed with rows over the ``data`` axis. Sub-f32
    storage dtypes in ``spec`` encode the init round-to-nearest (the
    deterministic seam — see ``kernels.quant``); ``w_out = 0`` is exact
    in every storage dtype, so quantized sessions start from the same
    zero output table.
    """
    spec = spec or TableSpec(vocab_shard=placement is not None)
    key = jax.random.PRNGKey(seed)
    d = cfg.dim
    w_in = (jax.random.uniform(key, (vocab_size, d), jnp.float32) - 0.5) / d
    w_out = jnp.zeros((vocab_size, d), jnp.float32)
    if placement is None:
        w_in, _ = quant.encode_nearest(w_in, spec.hot_dtype)
        w_out, _ = quant.encode_nearest(w_out, spec.hot_dtype)
        return TrainState(w_in=w_in, w_out=w_out)
    hot_in, cold_in = placement.split(np.asarray(w_in))
    hot_out, cold_out = placement.split(np.asarray(w_out))
    h_in, _ = quant.encode_nearest(jnp.asarray(hot_in), spec.hot_dtype)
    h_out, _ = quant.encode_nearest(jnp.asarray(hot_out), spec.hot_dtype)
    c_in, s_in = quant.encode_nearest(jnp.asarray(cold_in), spec.cold_dtype)
    c_out, s_out = quant.encode_nearest(jnp.asarray(cold_out),
                                        spec.cold_dtype)
    put, hot_put = _cold_put(mesh, cold_in.shape[0]), _hot_put(mesh)
    return TrainState(
        w_in=hot_put(h_in), w_out=hot_put(h_out),
        cold_in=put(c_in), cold_out=put(c_out),
        scale_in=None if s_in is None else put(s_in),
        scale_out=None if s_out is None else put(s_out))


def _cold_put(mesh: Optional[Mesh], cold_pad: int) -> Callable:
    """device_put for cold tables under the ``cold_vocab`` sharding rule."""
    if mesh is None:
        return jnp.asarray
    from repro.distributed.sharding import vocab_shard_sharding
    sharding = vocab_shard_sharding(mesh, cold_pad)
    return lambda arr: jax.device_put(jnp.asarray(arr), sharding)


def _hot_put(mesh: Optional[Mesh]) -> Callable:
    """device_put for the hot head: replicated over the mesh, the layout
    the sharded step returns it in, so the first step compiles the same
    program as every later one."""
    if mesh is None:
        return jnp.asarray
    from jax.sharding import NamedSharding
    sharding = NamedSharding(mesh, P())
    return lambda arr: jax.device_put(jnp.asarray(arr), sharding)


class TrainSession:
    """A streaming W2V training session over a batching pipeline.

    Parameters
    ----------
    backend : registry name or ``"auto"``. Resolved once at construction
        (``cfg.tile_windows > 1`` selects the window-tiled family); bad
        names or invalid capability combinations raise immediately.
    mesh : optional device mesh with a ``data`` axis for Hogwild data
        parallelism. Composes with ``cfg.tile_windows > 1`` and with
        ``cfg.vocab_shard`` (which synthesizes a 1-device mesh when none
        is given, so the sharded code path always runs under shard_map).
    ckpt_dir / ckpt_every : when set, checkpoint every N batches (atomic,
        pruned) and — unless ``resume=False`` — restore the latest
        checkpoint at construction, continuing words/batches/epoch counts.
    on_batch / on_metrics : callbacks after every trained batch, receiving
        the :class:`TrainState` / :class:`StepMetrics` respectively.
    """

    def __init__(
        self,
        pipeline: BatchingPipeline,
        cfg: W2VConfig,
        backend: str = "auto",
        mesh: Optional[Mesh] = None,
        sync_every: int = 1,
        on_batch: Optional[Callable[[TrainState], None]] = None,
        on_metrics: Optional[Callable[[StepMetrics], None]] = None,
        ckpt_dir: Optional[str] = None,
        ckpt_every: int = 0,
        resume: bool = True,
        exchange: Optional[str] = None,
    ):
        self.pipeline = pipeline
        self.cfg = cfg
        # the storage spec: cfg.tables when set (dtypes, hot fraction,
        # exchange flavor, sharding), else derived from the legacy
        # vocab_shard/hot_vocab_frac knobs. The explicit `exchange`
        # argument overrides the spec — "exact" (request-exact all_to_all
        # buckets, the default) or "dense" (the all_gather + psum_scatter
        # reference path the parity tests compare against)
        spec = tables_mod.from_config(cfg)
        if exchange is not None:
            spec = dataclasses.replace(spec, exchange=exchange)
        self.spec = spec
        self.exchange = spec.exchange
        # resolve once against the registry: invalid backend/capability
        # combinations (unknown name, TPU-only backend off-TPU, plan
        # mismatch, storage dtypes the kernels can't consume) fail here,
        # not mid-epoch. The *requested* name is kept for dispatch so
        # batches without a plan (T=1) can still resolve their sequential
        # variant
        self._requested_backend = backend
        self.backend = registry.resolve(
            backend, tiled=cfg.tile_windows > 1,
            vocab_shard=spec.vocab_shard,
            dtypes=() if spec.master_copy else spec.dtypes,
            frontends=getattr(pipeline, "frontend_features", ())).name
        if spec.vocab_shard and mesh is None:
            # the sharded step runs under shard_map even for one device, so
            # the 1-shard path exercises the exact N-shard code
            mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        self.mesh = mesh
        self.sync_every = sync_every
        self.on_batch = on_batch
        self.on_metrics = on_metrics
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.placement = None
        # the trainable table covers the vocabulary plus any frontend
        # extras (doc rows, n-gram buckets — DESIGN.md §12); extras carry
        # zero counts so placement planning stripes them into the cold tail
        table_rows = getattr(pipeline, "table_rows", pipeline.vocab.size)
        if spec.vocab_shard:
            from repro.distributed.vocab_placement import VocabPlacement
            counts = (pipeline.table_counts()
                      if hasattr(pipeline, "table_counts")
                      else pipeline.vocab.counts)
            self.placement = VocabPlacement.plan(
                counts, int(mesh.shape["data"]),
                hot_frac=spec.hot_frac)
            # hand the placement to the host pipeline so exchange plans are
            # computed in its finalize workers, off the step critical path
            # (Batch.exchange); _make_step falls back to inline planning
            # for pipelines (or batches) without one
            pipeline.placement = self.placement
        self.state = init_state(table_rows, cfg, cfg.seed,
                                placement=self.placement, mesh=mesh,
                                spec=spec)
        self.total_words = max(1, pipeline.epoch_words * cfg.epochs)
        self.words_per_sec = 0.0
        self.fetch_seconds = 0.0   # cumulative wait on the host pipeline
        self.wall_seconds = 0.0    # last train() wall time
        self.resumed_step: Optional[int] = None
        self._resume_skip = 0
        # poison-batch excision (DESIGN.md §9): stream positions the
        # supervisor decided to skip after a health rollback. Counters
        # still advance (LR schedule + pipeline cursor unchanged); only
        # the table update is excised. Skips are counted, never silent.
        self.poison_skip: Set[Tuple[int, int]] = set()
        self.batches_skipped = 0
        if ckpt_dir and resume:
            self._maybe_resume()
        if mesh is not None and not registry.get(self.backend).supports_mesh:
            raise ValueError(
                f"backend {self.backend!r} does not support mesh sharding")

    # -- learning-rate schedule (classic linear decay) ----------------------
    def _lr_at(self, words_seen: int) -> float:
        frac = 1.0 - words_seen / self.total_words
        return self.cfg.lr * max(frac, self.cfg.min_lr_frac)

    def current_lr(self) -> float:
        return self._lr_at(self.state.words_seen)

    def _tables(self) -> Tables:
        """The state's tables as the ``ops.step`` pytree (spec/placement
        ride as static metadata)."""
        st = self.state
        return Tables(w_in=st.w_in, w_out=st.w_out,
                      cold_in=st.cold_in, cold_out=st.cold_out,
                      scale_in=st.scale_in, scale_out=st.scale_out,
                      spec=self.spec, placement=self.placement)

    def _make_step(self, batch: Batch, lr) -> StepInputs:
        """Device StepInputs for a batch: the vocab-sharded exchange plan
        when the session shards the vocabulary, the plain lift otherwise.
        Batches from a placement-aware pipeline arrive with the exchange
        plan already computed in the finalize workers (``batch.exchange``);
        only placement-less batches pay for inline planning here. With
        sub-f32 storage the step also carries the batch's rounding key —
        a pure function of (seed, epoch, batch index), like the
        subsample/negative draws, so stochastic storage rounding replays
        bit-identically at any worker count. The work, the transfer's
        start included, is one ``repro.session.put`` span keyed by the
        batch's ``(epoch, index)``."""
        with tracing.span("repro.session.put",
                          key=(batch.epoch, batch.index)):
            if self.placement is not None:
                ex = getattr(batch, "exchange", None)
                if ex is None or ex.placement != self.placement:
                    from repro.distributed.vocab_placement import \
                        plan_exchange
                    ex = plan_exchange(batch, self.placement)
                step = ex.step_inputs(lr)
            else:
                step = batch.step_inputs(lr)
            if self.spec.is_mixed:
                key = quant.round_key(self.cfg.seed, batch.epoch,
                                      batch.index)
                step = dataclasses.replace(step, round_key=jnp.asarray(key))
            return step

    # -- train ---------------------------------------------------------------
    def train_batch(self, batch: Batch,
                    step: Optional[StepInputs] = None,
                    fetch_seconds: float = 0.0) -> StepMetrics:
        """Train one batch. ``step`` may be a pre-built (already
        device_put) :class:`StepInputs` from the prefetch path — its lr was
        computed from the projected word count, which equals
        ``current_lr()`` exactly because word counts are known host-side
        ahead of training. The call is one ``repro.session.step`` span
        keyed by the batch's ``(epoch, index)``, the kernel's dispatch a
        ``repro.session.dispatch`` span inside it."""
        with tracing.span("repro.session.step",
                          key=(batch.epoch, batch.index),
                          words=batch.n_words):
            lr = self.current_lr()
            skipped = ((self.state.epoch, self.state.epoch_batch)
                       in self.poison_skip)
            if skipped:
                self.batches_skipped += 1
                log.warning(
                    "skipping poison batch (epoch %d, batch %d) — counters "
                    "advance, tables untouched (%d skipped so far)",
                    self.state.epoch, self.state.epoch_batch,
                    self.batches_skipped)
            elif step is None:
                step = self._make_step(batch, lr)
            elif self.placement is not None and not step.has_vocab_shard:
                # a plain pre-built step carries un-remapped global ids; the
                # sharded path needs the exchange plan, so rebuild from the
                # host batch rather than crash (or silently corrupt) below
                step = self._make_step(batch, lr)
            if not skipped:
                with tracing.span("repro.session.dispatch"):
                    out = ops.step(self._tables(), step, self.cfg,
                                   backend=self._requested_backend,
                                   mesh=self.mesh)
                st = self.state
                st.w_in, st.w_out = out.w_in, out.w_out
                st.cold_in, st.cold_out = out.cold_in, out.cold_out
                st.scale_in, st.scale_out = out.scale_in, out.scale_out
            self.state.words_seen += batch.n_words
            self.state.batches_seen += 1
            self.state.epoch_batch += 1
            self.fetch_seconds += fetch_seconds
            metrics = StepMetrics(
                epoch=self.state.epoch, batches_seen=self.state.batches_seen,
                words_seen=self.state.words_seen, batch_words=batch.n_words,
                lr=lr, backend=self.backend, fetch_seconds=fetch_seconds,
                queue_depth=getattr(self.pipeline, "ready_depth", -1),
                skipped=skipped)
            if (self.ckpt_dir and self.ckpt_every
                    and self.state.batches_seen % self.ckpt_every == 0):
                self.save_checkpoint()
            if self.on_batch is not None:
                self.on_batch(self.state)
            if self.on_metrics is not None:
                self.on_metrics(metrics)
            return metrics

    def _prepared(self, batch_iter: Iterator[Batch]
                  ) -> Iterator[tuple]:
        """Lift host batches onto the device one step ahead (double
        buffering): batch k+1's ``jax.device_put`` is issued while the
        device still computes batch k, so host→device transfer overlaps
        the update. lr for batch k+1 is exact, not estimated — it depends
        only on cumulative host-side word counts."""
        projected = self.state.words_seen
        try:
            for batch in batch_iter:
                lr = self._lr_at(projected)
                step = self._make_step(batch, lr)  # async transfer starts
                projected += batch.n_words
                yield batch, step
        finally:
            close = getattr(batch_iter, "close", None)
            if close is not None:
                close()

    def stream(self, epochs: Optional[int] = None,
               max_batches: Optional[int] = None) -> Iterator[StepMetrics]:
        """Stream the session: train batch by batch, yielding metrics after
        each. Resumed sessions continue from the checkpointed position —
        randomness is keyed by (epoch, batch index), so the pipeline's
        ``skip_batches`` fast-forward reproduces the exact remainder of the
        interrupted epoch without re-finalizing (or re-counting) anything.

        With ``cfg.prefetch_workers > 0`` the loop double-buffers: while
        the device updates batch k, the async pipeline finalizes batches
        k+1.. in its workers and batch k+1's device transfer is in flight.
        """
        epochs = epochs if epochs is not None else self.cfg.epochs
        pad_len = self.cfg.resolved_pad_len
        n_batches = 0
        skip = self._resume_skip  # >0 only right after a mid-epoch restore
        self._resume_skip = 0
        for ep in range(min(self.state.epoch, epochs), epochs):
            self.state.epoch = ep
            if not skip:
                self.state.epoch_batch = 0
            it = self.pipeline.batches(pad_len=pad_len, epoch=ep,
                                       skip_batches=skip)
            skip = 0
            prepared = self._prepared(it)
            try:
                t0 = time.perf_counter()
                cur = next(prepared, None)
                wait = time.perf_counter() - t0
                while cur is not None:
                    batch, step = cur
                    metrics = self.train_batch(batch, step=step,
                                               fetch_seconds=wait)
                    n_batches += 1
                    done = (max_batches is not None
                            and n_batches >= max_batches)
                    if done:
                        yield metrics
                        return
                    # with prefetch, pull batch k+1 *before* yielding: the
                    # update just dispatched is still running on the device
                    # while the host pipeline hands over (or finishes) k+1
                    t0 = time.perf_counter()
                    cur = next(prepared, None)
                    wait = time.perf_counter() - t0
                    yield metrics
            finally:
                prepared.close()

    def train(self, epochs: Optional[int] = None,
              max_batches: Optional[int] = None) -> TrainState:
        """Drain :meth:`stream` to completion; returns the final state."""
        words0 = self.state.words_seen
        self.fetch_seconds = 0.0
        t0 = time.perf_counter()
        for _ in self.stream(epochs=epochs, max_batches=max_batches):
            pass
        jax.block_until_ready(self.state.w_in)
        dt = time.perf_counter() - t0
        self.wall_seconds = dt
        self.words_per_sec = ((self.state.words_seen - words0) / dt
                              if dt else 0.0)
        return self.state

    def train_resilient(self, **kwargs) -> TrainState:
        """Drive :meth:`stream` under the recovery supervisor: restore +
        replay on step failure, health-probe rollback, watchdog timeouts,
        restart budget with refill (``repro.train.supervisor``, DESIGN.md
        §9). Keyword arguments go to :class:`TrainSupervisor`; the
        supervisor's :class:`SupervisorReport` lands on
        ``self.last_report``."""
        from repro.train.supervisor import TrainSupervisor
        sup = TrainSupervisor(self, **kwargs)
        words0 = self.state.words_seen
        self.fetch_seconds = 0.0
        t0 = time.perf_counter()
        state = sup.run()
        jax.block_until_ready(self.state.w_in)
        dt = time.perf_counter() - t0
        self.wall_seconds = dt
        self.words_per_sec = ((self.state.words_seen - words0) / dt
                              if dt else 0.0)
        self.last_report = sup.report
        return state

    def host_report(self, n_steps: int) -> Dict[str, float]:
        """Host numbers of the newest ``n_steps`` trained batches:
        ``host_wait``, the share of the last ``train()`` spent waiting on
        the pipeline; from the ``repro.tracing`` rings, ``finalize_ms``,
        the mean ``repro.pipeline.finalize`` span per batch, and
        ``neg_draws_per_word``, the ``repro.neg.drawn`` count per real
        word (NaN where the batches were finalized in worker processes,
        whose rings stay there)."""
        steps = tracing.recent("repro.session.step", n_steps)
        keys = [r.key for r in steps]
        words = sum(r.attrs["words"] for r in steps)
        fin = tracing.keyed("repro.pipeline.finalize", keys)
        drawn = tracing.keyed("repro.neg.drawn", keys)
        nan = float("nan")
        return {
            "host_wait": (self.fetch_seconds / self.wall_seconds
                          if self.wall_seconds else nan),
            "finalize_ms": (1e3 * sum(fin.values()) / len(fin)
                            if fin else nan),
            "neg_draws_per_word": (sum(drawn.values()) / words
                                   if drawn and words else nan),
        }

    # -- checkpoint / resume --------------------------------------------------
    def save_checkpoint(self) -> str:
        """Atomically checkpoint tables + progress counters + the host
        pipeline cursor (exact mid-epoch resume, prefetch or not)."""
        from repro.train import checkpoint as ckpt
        assert self.ckpt_dir, "TrainSession has no ckpt_dir"
        cursor = ckpt.PipelineCursor(
            epoch=self.state.epoch, epoch_batch=self.state.epoch_batch,
            prefetch_workers=self.cfg.prefetch_workers)
        extra = {"words_seen": self.state.words_seen,
                 "batches_seen": self.state.batches_seen,
                 "backend": self.backend, "tables": self.spec.to_extra(),
                 **cursor.to_extra()}
        if self.placement is not None:
            extra["vocab_shard"] = self.placement.to_extra()
        return ckpt.save(
            self.ckpt_dir, self.state.batches_seen, self.state.params(),
            extra=extra)

    def _restore_tables(self, step: int) -> Dict:
        """Restore embedding tables across table *formats*: split-table
        (vocab-sharded) vs replicated, and any storage-dtype mix — a
        mixed-precision checkpoint restores into an f32 session and vice
        versa. Cross-format restores decode the writing run's storage to
        the full f32 tables (through its placement and TableSpec, both
        recorded in the checkpoint extra) and re-encode round-to-nearest
        through this session's spec. Same-format restores (same leaf set,
        shapes, dtypes, and placement) skip the round trip and keep the
        exact storage bytes."""
        from repro.distributed.vocab_placement import VocabPlacement
        from repro.train import checkpoint as ckpt
        leaves, extra = ckpt.peek(self.ckpt_dir, step=step)
        split_ckpt = "hot_in" in leaves
        like_now = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                    for k, v in self.state.params().items()}
        same_format = (set(leaves) == set(like_now) and all(
            tuple(leaves[k]["shape"]) == tuple(like_now[k].shape)
            and leaves[k]["dtype"] == str(like_now[k].dtype)
            for k in like_now))
        if same_format and split_ckpt:
            # shapes alone can coincide across shard counts (equal
            # cold_pad, different stripe order) — the placements must
            # match exactly or the cold rows land on the wrong shards
            meta = extra.get("vocab_shard")
            same_format = (self.placement is not None and meta is not None
                           and VocabPlacement.from_extra(meta)
                           == self.placement)
        if same_format:
            tree, extra = ckpt.restore(self.ckpt_dir, like_now, step=step)
        else:
            like_ckpt = {
                k: jax.ShapeDtypeStruct(tuple(m["shape"]),
                                        ckpt.np_dtype(m["dtype"]))
                for k, m in leaves.items()}
            tree, extra = ckpt.restore(self.ckpt_dir, like_ckpt, step=step)
            src_spec = TableSpec.from_extra(extra.get("tables", {}))

            def dec_cold(name: str, sname: str) -> np.ndarray:
                cold = np.asarray(tree[name]).astype(np.float32)
                if src_spec.cold_dtype == "int8":
                    cold = cold * np.asarray(tree[sname])[:, None]
                return cold

            if split_ckpt:
                src = VocabPlacement.from_extra(extra["vocab_shard"])
                full_in = src.merge(
                    np.asarray(tree["hot_in"]).astype(np.float32),
                    dec_cold("cold_in", "scale_in"))
                full_out = src.merge(
                    np.asarray(tree["hot_out"]).astype(np.float32),
                    dec_cold("cold_out", "scale_out"))
            else:
                full_in = np.asarray(tree["w_in"]).astype(np.float32)
                full_out = np.asarray(tree["w_out"]).astype(np.float32)
            # restoring through like_ckpt skipped restore()'s shape check
            # against *this* session — validate before training reads rows
            # out of range (jax clamps gathers: silent corruption)
            v_expect = (self.placement.vocab_size
                        if self.placement is not None
                        else int(self.state.w_in.shape[0]))
            want = (v_expect, self.cfg.dim)
            if full_in.shape != want:
                raise ValueError(
                    f"checkpoint tables are {full_in.shape}, session "
                    f"expects {want} (vocabulary or dim mismatch — wrong "
                    f"ckpt_dir?)")
            if self.placement is not None:
                hot_in, cold_in = self.placement.split(full_in)
                hot_out, cold_out = self.placement.split(full_out)
                h_in, _ = quant.encode_nearest(jnp.asarray(hot_in),
                                               self.spec.hot_dtype)
                h_out, _ = quant.encode_nearest(jnp.asarray(hot_out),
                                                self.spec.hot_dtype)
                c_in, s_in = quant.encode_nearest(jnp.asarray(cold_in),
                                                  self.spec.cold_dtype)
                c_out, s_out = quant.encode_nearest(jnp.asarray(cold_out),
                                                    self.spec.cold_dtype)
                put = _cold_put(self.mesh, cold_in.shape[0])
                tree = {"hot_in": h_in, "hot_out": h_out,
                        "cold_in": put(c_in), "cold_out": put(c_out)}
                if s_in is not None:
                    tree["scale_in"] = put(s_in)
                    tree["scale_out"] = put(s_out)
            else:
                w_in, _ = quant.encode_nearest(jnp.asarray(full_in),
                                               self.spec.hot_dtype)
                w_out, _ = quant.encode_nearest(jnp.asarray(full_out),
                                                self.spec.hot_dtype)
                tree = {"w_in": w_in, "w_out": w_out}
        if self.placement is not None:
            hot_put = _hot_put(self.mesh)
            self.state.w_in = hot_put(tree["hot_in"])
            self.state.w_out = hot_put(tree["hot_out"])
            self.state.cold_in = tree["cold_in"]
            self.state.cold_out = tree["cold_out"]
            self.state.scale_in = tree.get("scale_in")
            self.state.scale_out = tree.get("scale_out")
        else:
            self.state.w_in = tree["w_in"]
            self.state.w_out = tree["w_out"]
        return extra

    def restore_latest(self) -> Optional[int]:
        """Roll the session back to the newest *readable* checkpoint.
        Corrupt/partial step directories are quarantined by the checkpoint
        layer and skipped; with no usable checkpoint at all (or no
        ``ckpt_dir``) the session re-initializes from the seed — keyed
        randomness makes replay-from-scratch bit-exact too. Returns the
        restored step, or None when starting over. Sets the pipeline
        fast-forward so the next :meth:`stream` resumes mid-epoch exactly
        where the checkpoint left off."""
        from repro.train import checkpoint as ckpt
        while True:
            step = (ckpt.latest_step(self.ckpt_dir) if self.ckpt_dir
                    else None)
            if step is None:
                log.warning("no usable checkpoint — re-initializing from "
                            "seed %d", self.cfg.seed)
                self.state = init_state(
                    getattr(self.pipeline, "table_rows",
                            self.pipeline.vocab.size),
                    self.cfg, self.cfg.seed, placement=self.placement,
                    mesh=self.mesh, spec=self.spec)
                self._resume_skip = 0
                self.resumed_step = None
                return None
            try:
                extra = self._restore_tables(step)
            except ckpt.CorruptCheckpoint:
                # quarantined inside restore(); the next latest_step no
                # longer sees it — fall back to the one before
                continue
            self.state.words_seen = int(extra.get("words_seen", 0))
            self.state.batches_seen = int(extra.get("batches_seen", step))
            cursor = ckpt.PipelineCursor.from_extra(extra)
            self.state.epoch = cursor.epoch
            self.state.epoch_batch = cursor.epoch_batch
            self._resume_skip = cursor.epoch_batch
            self.resumed_step = step
            return step

    def _maybe_resume(self) -> None:
        from repro.train import checkpoint as ckpt
        if ckpt.latest_step(self.ckpt_dir) is None:
            return   # fresh start: keep the init-state tables as built
        self.restore_latest()

    # -- inference helpers ----------------------------------------------------
    def embeddings(self) -> np.ndarray:
        """The input embedding table ``(V, d)`` as f32 (quantized storage
        decodes once here); vocab-sharded sessions reassemble it from the
        hot replica + cold shards. NOTE: for a sharded session this
        gathers the full table onto one host — fine for examples and
        tests, wrong for serving; the serve path uses
        :meth:`embeddings_sharded` instead."""
        if self.placement is not None:
            hot = np.asarray(self.state.w_in).astype(np.float32)
            cold = np.asarray(quant.decode(self.state.cold_in,
                                           self.state.scale_in,
                                           self.spec.cold_dtype))
            return self.placement.merge(hot, cold)
        return np.asarray(self.state.w_in).astype(np.float32)

    def embeddings_sharded(self):
        """Shard-aware f32 view of the input table — no ``(V, d)`` gather.

        Returns ``(hot, cold, placement)``: for a vocab-sharded session,
        the replicated hot head ``(hot, d)``, the shard-major cold table
        ``(cold_pad, d)`` (still device-resident with its training
        sharding), and the :class:`VocabPlacement` describing the
        layout. For a replicated session, ``(w_in, None, None)`` — the
        caller chooses its own serving split
        (:meth:`repro.serve.index.EmbeddingIndex.from_session`).
        Quantized storage dequantizes here — once, at snapshot time —
        so serving reads plain f32 rows (elementwise decode preserves
        the cold table's device sharding)."""
        if self.placement is not None:
            cold = quant.decode(self.state.cold_in, self.state.scale_in,
                                self.spec.cold_dtype)
            return (self.state.w_in.astype(jnp.float32), cold,
                    self.placement)
        return self.state.w_in.astype(jnp.float32), None, None

    def nearest(self, word_id: int, k: int = 5) -> np.ndarray:
        e = self.embeddings()
        e = e / np.maximum(np.linalg.norm(e, axis=1, keepdims=True), 1e-12)
        sims = e @ e[word_id]
        sims[word_id] = -np.inf
        return np.argsort(-sims)[:k]


# Backwards-compatible name: the session IS the trainer.
W2VTrainer = TrainSession
