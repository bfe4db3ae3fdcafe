"""FULL-W2V Pallas TPU kernel.

The paper's two mechanisms, mapped to TPU (DESIGN.md §2):

* *Lifetime reuse of context words* (§3.2): a VMEM scratch ring buffer of
  ``R = 2*W_f + 1`` embedding rows mirrors the sliding window. Each context
  row is DMA'd HBM→VMEM once when it enters the window, accumulates all its
  updates in VMEM, and is DMA'd back exactly once when it leaves — removing
  2W_f/(2W_f+1) of context-row HBM traffic.

* *Independence of negative samples* (§3.1): the N+1 output rows of a window
  (target + shared negatives) are DMA'd into a VMEM block, used for every
  pairing of the window from that block (the GPU's register caching), and
  written back once per window. Because all pairings commute, the window
  update is expressed as two tiny GEMMs over data already resident in VMEM —
  the MXU-native analogue of the paper's per-negative register loop.

Three kernel variants share the window math (``_window_update``):

* ``_kernel``            — one window per inner step, strict ordering.
* ``_kernel_pipelined``  — same semantics; window t+1's output rows prefetch
  while window t computes (§3.1 "interleaving memory and computation").
* ``_kernel_tiled``      — T consecutive windows fused per inner step
  (DESIGN.md §4): the ring grows to ``T + 2*W_f`` positions, the tile's
  context rows are gathered into one ``(T*K, d)`` block, its output rows are
  deduplicated host-side (`repro.data.batching.plan_tiles`) and fetched as
  one batched multi-row DMA, and the update becomes two large MXU-shaped
  GEMMs — amortizing MXU and DMA-setup latency over T windows. Tiles whose
  output rows collide across windows run the exact sequential path
  (``strict`` bit); collision-free tiles trade strict intra-tile ordering
  for throughput (all T windows read pre-tile values — the HogBatch
  relaxation of Ji et al. 1604.04661; quality impact measured by
  ``benchmarks/bench_tile_sweep.py``). At T=1 the tiled kernel is
  bit-identical to ``_kernel``.

Grid = one step per sentence; the TPU grid is sequential per core, so strict
context-window ordering (required for convergence, paper §3.1) holds by
construction, and batch-level parallelism comes from data parallelism across
cores/chips (Hogwild, as in the paper). The host entry points below are
registered with the engine API (``kernels.registry``) as the ``pallas``,
``pallas_pipelined``, ``pallas_tiled``, and ``*_interpret`` backends;
training code reaches them through ``kernels.ops.step``.

Embedding tables stay in HBM (``memory_space=ANY``); rows move via explicit
``make_async_copy`` — the TPU spelling of the paper's explicit caching.

PRECONDITION (enforced by the host batching pipeline, `repro.data.negatives`,
exactly as the paper performs negative selection on the CPU): within one
window the N negatives are distinct from each other and from the target.
Under this invariant the kernel is bit-identical to `kernels.ref`; with
duplicates the kernel's per-row write-back is last-write-wins while the
oracle scatter-adds (the GPU original has the same benign race).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.configs.w2v import resolve_gemm_windows

LANE = 128     # TPU lane width; embedding dim must be a multiple
SUBLANE = 8    # f32 sublane tile


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def tiled_scratch_rows(tile: int, w_f: int, n_neg: int,
                       gemm_windows: int = 0) -> dict:
    """Padded scratch-row counts of `_kernel_tiled`, keyed like its scratch
    operands (ring/ctx_tile/out_uniq/out_exp/ctx_win/out_win). Single source
    of truth shared with `benchmarks/bench_tile_sweep` so VMEM reporting
    stays in lockstep with the kernel."""
    g = resolve_gemm_windows(tile, gemm_windows)
    m = n_neg + 1
    return {
        "ring": _round_up(tile + 2 * w_f, SUBLANE),
        "ctx_tile": _round_up(g * 2 * w_f, SUBLANE),
        "out_uniq": _round_up(tile * m, SUBLANE),
        "out_exp": _round_up(g * m, SUBLANE),
        "ctx_win": _round_up(2 * w_f, SUBLANE),
        "out_win": _round_up(m, SUBLANE),
    }


def _ctx_offsets(w_f: int) -> list:
    """Window-relative context offsets [-w_f..w_f] \\ {0}."""
    return [o for o in range(-w_f, w_f + 1) if o != 0]


# ---------------------------------------------------------------------------
# Shared building blocks (used by all three kernel variants)
# ---------------------------------------------------------------------------

class _Table:
    """A row-addressed HBM embedding table, optionally *split* (DESIGN.md
    §8 fused gather): rows ``[0, hot)`` live in ``main`` (the replicated
    hot head), rows ``>= hot`` in ``got`` at ``row - hot`` (the gathered
    cold block delivered by the request-exact exchange). The kernel streams
    rows from whichever buffer owns them — both directions of every DMA
    branch on the row index at trace-recomputable scalar cost — so a
    vocab-sharded step never materializes ``concat(hot, gathered)``. With
    ``got=None`` the helpers are exactly the single-table DMA calls."""

    def __init__(self, main, got=None, hot: int = 0):
        self.main, self.got, self.hot = main, got, hot

    def _each(self, row):
        """(predicate, hbm_slice) per buffer; predicate None = always."""
        if self.got is None:
            yield None, self.main.at[pl.ds(row, 1)]
        else:
            lo = jnp.minimum(row, self.hot - 1)
            hi = jnp.maximum(row - self.hot, 0)
            yield row < self.hot, self.main.at[pl.ds(lo, 1)]
            yield row >= self.hot, self.got.at[pl.ds(hi, 1)]

    def _move(self, row, vmem, sem, to_hbm: bool, op: str):
        for pred, hbm in self._each(row):
            src, dst = (vmem, hbm) if to_hbm else (hbm, vmem)
            if pred is None:
                getattr(pltpu.make_async_copy(src, dst, sem), op)()
            else:
                @pl.when(pred)
                def _(src=src, dst=dst):
                    getattr(pltpu.make_async_copy(src, dst, sem), op)()

    # start/wait split so callers can batch DMAs (start all, wait all);
    # the wait call rebuilds the same descriptor under the same predicate
    def start_load(self, row, vmem, sem):
        self._move(row, vmem, sem, to_hbm=False, op="start")

    def wait_load(self, row, vmem, sem):
        self._move(row, vmem, sem, to_hbm=False, op="wait")

    def start_store(self, vmem, row, sem):
        self._move(row, vmem, sem, to_hbm=True, op="start")

    def wait_store(self, vmem, row, sem):
        self._move(row, vmem, sem, to_hbm=True, op="wait")

    def load(self, row, vmem, sem):
        self.start_load(row, vmem, sem)
        self.wait_load(row, vmem, sem)

    def store(self, vmem, row, sem):
        self.start_store(vmem, row, sem)
        self.wait_store(vmem, row, sem)


def _window_update(ctx, out_rows, label, mask, lr):
    """The SGNS window update (DESIGN.md §2) on VMEM-resident blocks.

    ctx      : (K, d) f32 — gathered context rows (zeros where invalid)
    out_rows : (M, d) f32 — target + negative rows
    label    : (K, M) f32 — 1 where the pairing is (context, its target)
    mask     : (K, M) bool — which pairings are real (window membership,
               sentence edges, padding)
    Returns (d_ctx (K, d), d_out (M, d)) gradient blocks.
    """
    # function-level import: repro.core.__init__ pulls in the trainer →
    # ops → this module, so a top-level import would be circular
    from repro.core.sgns import stable_sigmoid

    # f32 contractions on the MXU: Mosaic's default precision rounds f32
    # operands to bf16, which moved the tables ~0.2% of an update away
    # from the f32 oracle on a v5e. The GEMMs are tiny and DMA-bound.
    dot = functools.partial(jax.lax.dot_general,
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    corr = dot(ctx, out_rows, (((1,), (1,)), ((), ())))    # (K, M)
    g = lr * (label - stable_sigmoid(corr))
    g = jnp.where(mask, g, 0.0)
    d_ctx = dot(g, out_rows, (((1,), (0,)), ((), ())))     # (K, d)
    d_out = dot(g, ctx, (((0,), (0,)), ((), ())))          # (M, d)
    return d_ctx, d_out


def _zero_rows(blk, start: int, stop: int):
    """Zero scratch rows [start, stop) (uninitialized VMEM may hold NaNs)."""
    if stop > start:
        blk[pl.ds(start, stop - start), :] = jnp.zeros(
            (stop - start, blk.shape[-1]), blk.dtype)


def _gather_window_ctx(ring, ctx_blk, t, dst0: int, *, w_f: int, r: int,
                       length, L: int):
    """Copy window t's context rows from the VMEM ring (no HBM traffic) into
    ctx_blk rows [dst0, dst0 + 2*w_f); out-of-sentence positions read 0."""
    for j, off in enumerate(_ctx_offsets(w_f)):
        p = t + off
        valid = jnp.logical_and(p >= 0, p < length)
        slot = jnp.clip(p, 0, L - 1) % r
        row = ring[pl.ds(slot, 1), :]
        ctx_blk[pl.ds(dst0 + j, 1), :] = jnp.where(valid, row, 0.0)


def _scatter_window_ctx(ring, d_ctx, t, src0: int, *, w_f: int, r: int,
                        L: int):
    """Accumulate window t's context deltas back into the ring. Invalid
    positions carry zero gradient (masked in `_window_update`), so the
    clipped-slot add is a no-op for them."""
    for j, off in enumerate(_ctx_offsets(w_f)):
        p = t + off
        slot = jnp.clip(p, 0, L - 1) % r
        ring[pl.ds(slot, 1), :] = (ring[pl.ds(slot, 1), :]
                                   + d_ctx[src0 + j:src0 + j + 1, :])


class _Idx:
    """Row/column view of a per-sentence SMEM index block stored lane-dense
    as ``(1, 1, rows * cols)``: ``view[r, c]`` (or ``view[r]`` when
    ``cols == 1``) reads element ``r * cols + c``. Every read is one full
    index tuple into the block — Mosaic refuses sub-ref views of SMEM
    blocks — and the flat layout keeps the block's SMEM footprint at its
    element count (a ``(L, N)`` block would pad N up to 128 lanes)."""

    def __init__(self, ref, cols: int = 1):
        self.ref, self.cols = ref, cols

    def __getitem__(self, rc):
        r, c = rc if isinstance(rc, tuple) else (rc, 0)
        return self.ref[0, 0, r * self.cols + c]


def _window_label_mask(t, k_pad: int, m_pad: int, *, w_f: int, n_neg: int,
                       length):
    """Label + validity mask for a single window's (k_pad, m_pad) update.
    Built directly in 2-D (Mosaic cannot reshape a 1-D bool vector into a
    column); the static offset list is rebuilt from the row iota (no
    captured constants): j < w_f -> j - w_f; j >= w_f -> j - w_f + 1
    (skipping offset 0)."""
    ji = jax.lax.broadcasted_iota(jnp.int32, (k_pad, m_pad), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (k_pad, m_pad), 1)
    label = (ci == 0).astype(jnp.float32)
    p_arr = t + jnp.where(ji < w_f, ji - w_f, ji - w_f + 1)
    mask = ((p_arr >= 0) & (p_arr < length) & (ji < 2 * w_f)
            & (ci < n_neg + 1))
    return label, mask


def _seq_window(t, tok, neg, w_out_tab, ring, ctx_blk, out_blk,
                sem, *, w_f: int, n_neg: int, r: int, length, L: int, lr):
    """One strictly-ordered window update (fetch → GEMMs → apply → write
    back). Shared by `_kernel` and `_kernel_tiled`'s strict fallback; `r` is
    the caller's ring size (2*w_f+1 sequential, T+2*w_f tiled); ``tok`` /
    ``neg`` are :class:`_Idx` views of the sentence's tokens / negatives;
    ``w_out_tab`` is a :class:`_Table` (split under fused gather)."""
    k = 2 * w_f
    m = n_neg + 1
    k_pad = ctx_blk.shape[0]
    m_pad = out_blk.shape[0]

    # ---- gather context rows (from VMEM ring — no HBM traffic) ----
    _gather_window_ctx(ring, ctx_blk, t, 0, w_f=w_f, r=r, length=length, L=L)
    _zero_rows(ctx_blk, k, k_pad)

    # ---- fetch output rows: target + shared negatives (paper §3.1) ----
    tgt = tok[t]
    w_out_tab.load(tgt, out_blk.at[pl.ds(0, 1)], sem)
    for j in range(n_neg):
        w_out_tab.load(neg[t, j], out_blk.at[pl.ds(1 + j, 1)], sem)
    _zero_rows(out_blk, m, m_pad)

    # ---- the window update: two tiny GEMMs on VMEM-resident data ----
    ctx = ctx_blk[...]
    out_rows = out_blk[...]
    label, mask = _window_label_mask(t, k_pad, m_pad, w_f=w_f, n_neg=n_neg,
                                     length=length)
    d_ctx, d_out = _window_update(ctx, out_rows, label, mask, lr)

    # ---- apply: context deltas accumulate in the ring buffer ----
    _scatter_window_ctx(ring, d_ctx, t, 0, w_f=w_f, r=r, L=L)

    # ---- output rows: update in VMEM, write back once per window ----
    out_blk[...] = out_rows + d_out
    w_out_tab.store(out_blk.at[pl.ds(0, 1)], tgt, sem)
    for j in range(n_neg):
        w_out_tab.store(out_blk.at[pl.ds(1 + j, 1)], neg[t, j], sem)


# ---------------------------------------------------------------------------
# Variant 1: sequential (one window per step)
# ---------------------------------------------------------------------------

def _kernel(
    # --- scalar/SMEM inputs (per sentence block, lane-dense) ---
    tokens_ref,    # (1, 1, L) int32 SMEM
    negs_ref,      # (1, 1, L*N) int32 SMEM — window t's j-th at t*N + j
    length_ref,    # (1, 1, 1) int32 SMEM
    lr_ref,        # (1,) f32 SMEM
    # --- HBM (ANY) inputs, aliased to outputs ---
    w_in_hbm,      # (V, d)
    w_out_hbm,     # (V, d)
    # --- outputs (aliased) ---
    w_in_out,      # (V, d)
    w_out_out,     # (V, d)
    # --- scratch ---
    ring,          # (R_pad, d) f32 VMEM — context-row ring buffer
    ctx_blk,       # (K_pad, d) f32 VMEM — gathered window context rows
    out_blk,       # (M_pad, d) f32 VMEM — target + negative output rows
    sem,           # DMA semaphore
    *,
    w_f: int,
    n_neg: int,
):
    """See module docstring; `_kernel_pipelined` adds §3.1-style prefetch."""
    L = tokens_ref.shape[2]
    r = 2 * w_f + 1
    tok, neg = _Idx(tokens_ref), _Idx(negs_ref, n_neg)
    length = length_ref[0, 0, 0]
    lr = lr_ref[0]

    def copy(src, dst):
        cp = pltpu.make_async_copy(src, dst, sem)
        cp.start()
        cp.wait()

    def load_ring(q):
        """HBM w_in row tokens[q] -> ring slot q % r."""
        copy(w_in_out.at[pl.ds(tok[q], 1)], ring.at[pl.ds(q % r, 1)])

    def store_ring(p):
        """ring slot p % r -> HBM w_in row tokens[p] (write-through output)."""
        copy(ring.at[pl.ds(p % r, 1)], w_in_out.at[pl.ds(tok[p], 1)])

    # --- preload positions 0..w_f-1 ---
    def preload(q, _):
        @pl.when(q < length)
        def _():
            load_ring(q)
        return 0

    jax.lax.fori_loop(0, min(w_f, L), preload, 0, unroll=True)

    # --- main sliding-window loop ---
    def step(t, _):
        # evict + load leading edge
        q = t + w_f

        @pl.when(q < length)
        def _():
            @pl.when(q - r >= 0)
            def _():
                store_ring(q - r)
            load_ring(q)

        _seq_window(t, tok, neg, _Table(w_out_out), ring,
                    ctx_blk, out_blk, sem, w_f=w_f, n_neg=n_neg, r=r,
                    length=length, L=L, lr=lr)
        return 0

    def guarded_step(t, c):
        @pl.when(t < length)
        def _():
            step(t, c)
        return 0

    jax.lax.fori_loop(0, L, guarded_step, 0)

    # --- flush surviving ring entries (increasing position order) ---
    def flush(kk, _):
        p = length - r + kk

        @pl.when(jnp.logical_and(p >= 0, p < length))
        def _():
            store_ring(p)
        return 0

    jax.lax.fori_loop(0, r, flush, 0, unroll=True)


# ---------------------------------------------------------------------------
# Variant 2: pipelined (prefetch window t+1's rows while t computes)
# ---------------------------------------------------------------------------

def _kernel_pipelined(
    tokens_ref, negs_ref, length_ref, lr_ref,
    w_in_hbm, w_out_hbm, w_in_out, w_out_out,
    ring, ctx_blk, out_dbl, sem_ring, sem_out,
    *, w_f: int, n_neg: int,
):
    """FULL-W2V kernel with §3.1-style prefetch: window t+1's target +
    negative rows are DMA'd into the other half of a double buffer WHILE
    window t computes — the TPU realization of the paper's "interleaving
    memory demand and computation".

    Correctness: a prefetched row whose index collides with one of window
    t's output rows would read a stale value (window t writes it back after
    compute). Collisions are detected at trace-recomputable scalar cost
    (m×m index compares); colliding rows are NOT prefetched and are loaded
    synchronously after window t's write-back instead — bit-identical
    semantics to the sequential kernel, overlap in the common
    (collision-free) case.
    """
    L = tokens_ref.shape[2]
    d = w_in_hbm.shape[1]
    r = 2 * w_f + 1
    k = 2 * w_f
    m = n_neg + 1
    k_pad = ctx_blk.shape[0]
    m_pad = out_dbl.shape[1]
    tok, neg = _Idx(tokens_ref), _Idx(negs_ref, n_neg)
    length = length_ref[0, 0, 0]
    lr = lr_ref[0]

    def copy(src, dst, sem):
        cp = pltpu.make_async_copy(src, dst, sem)
        cp.start()
        cp.wait()

    def row_idx(t, j):
        """Output row j of window t (static j: 0 = target, else negative)."""
        return tok[t] if j == 0 else neg[t, j - 1]

    def conflicts_prev(t, j):
        """Does row j of window t collide with any output row of window
        t-1? (t >= 1)"""
        idx = row_idx(t, j)
        hit = jnp.bool_(False)
        for i in range(m):
            hit = jnp.logical_or(hit, idx == row_idx(t - 1, i))
        return hit

    def start_prefetch(t, buf):
        """Begin async loads of window t's non-colliding rows into half
        `buf`."""
        for j in range(m):
            idx = row_idx(t, j)

            @pl.when(jnp.logical_or(t == 0, ~conflicts_prev(t, j)))
            def _():
                pltpu.make_async_copy(
                    w_out_out.at[pl.ds(idx, 1)],
                    out_dbl.at[buf, pl.ds(j, 1)],
                    sem_out.at[buf]).start()

    def load_ring(q):
        copy(w_in_out.at[pl.ds(tok[q], 1)],
             ring.at[pl.ds(q % r, 1)], sem_ring)

    def store_ring(p):
        copy(ring.at[pl.ds(p % r, 1)],
             w_in_out.at[pl.ds(tok[p], 1)], sem_ring)

    # --- preload ring positions 0..w_f-1 and prefetch window 0 rows ---
    def preload(q, _):
        @pl.when(q < length)
        def _():
            load_ring(q)
        return 0

    jax.lax.fori_loop(0, min(w_f, L), preload, 0, unroll=True)

    @pl.when(length > 0)
    def _():
        start_prefetch(0, 0)

    def step(t, _):
        buf = jax.lax.rem(t, 2)
        q = t + w_f

        @pl.when(q < length)
        def _():
            @pl.when(q - r >= 0)
            def _():
                store_ring(q - r)
            load_ring(q)

        # ---- wait for this window's prefetched rows / sync-load the
        # colliding ones (window t-1's write-back already happened) ----
        for j in range(m):
            idx = row_idx(t, j)
            prefetched = jnp.logical_or(t == 0, ~conflicts_prev(t, j))

            @pl.when(prefetched)
            def _():
                pltpu.make_async_copy(
                    w_out_out.at[pl.ds(idx, 1)],
                    out_dbl.at[buf, pl.ds(j, 1)],
                    sem_out.at[buf]).wait()

            @pl.when(~prefetched)
            def _():
                copy(w_out_out.at[pl.ds(idx, 1)],
                     out_dbl.at[buf, pl.ds(j, 1)], sem_ring)

        if m_pad > m:
            out_dbl[buf, pl.ds(m, m_pad - m), :] = jnp.zeros(
                (m_pad - m, d), out_dbl.dtype)

        # ---- overlap: begin prefetch of window t+1 into the other half ----
        @pl.when(t + 1 < length)
        def _():
            start_prefetch(t + 1, 1 - buf)

        # ---- gather context + window GEMMs (shared helpers) ----
        _gather_window_ctx(ring, ctx_blk, t, 0, w_f=w_f, r=r, length=length,
                           L=L)
        _zero_rows(ctx_blk, k, k_pad)
        ctx = ctx_blk[...]
        out_rows = out_dbl[buf]
        label, mask = _window_label_mask(t, k_pad, m_pad, w_f=w_f,
                                         n_neg=n_neg, length=length)
        d_ctx, d_out = _window_update(ctx, out_rows, label, mask, lr)

        _scatter_window_ctx(ring, d_ctx, t, 0, w_f=w_f, r=r, L=L)

        out_dbl[buf] = out_rows + d_out
        for j in range(m):
            idx = row_idx(t, j)
            copy(out_dbl.at[buf, pl.ds(j, 1)],
                 w_out_out.at[pl.ds(idx, 1)], sem_ring)
        return 0

    def guarded_step(t, c):
        @pl.when(t < length)
        def _():
            step(t, c)
        return 0

    jax.lax.fori_loop(0, L, guarded_step, 0)

    def flush(kk, _):
        p = length - r + kk

        @pl.when(jnp.logical_and(p >= 0, p < length))
        def _():
            store_ring(p)
        return 0

    jax.lax.fori_loop(0, r, flush, 0, unroll=True)


# ---------------------------------------------------------------------------
# Variant 3: tiled (T windows fused per step, DESIGN.md §4)
# ---------------------------------------------------------------------------

def _kernel_tiled(
    # --- scalar/SMEM inputs (per sentence block, lane-dense) ---
    tokens_ref,    # (1, 1, L) int32 SMEM
    negs_ref,      # (1, 1, L*N) int32 SMEM
    length_ref,    # (1, 1, 1) int32 SMEM
    lr_ref,        # (1,) f32 SMEM
    uniq_ref,      # (1, 1, nt*T*m) int32 SMEM — compacted unique output rows
    scat_ref,      # (1, 1, nt*T*m) int32 SMEM — slot -> uniq column
    ucount_ref,    # (1, 1, nt) int32 SMEM — valid uniq columns per tile
    strict_ref,    # (1, 1, nt) int32 SMEM — 1: sequential fallback tile
    # --- HBM (ANY) inputs + aliased outputs + scratch, layout depends on
    # hot_rows/prefetch (see the unpacking right below):
    #   hot_rows == 0: w_in_hbm, w_out_hbm, w_in_out, w_out_out
    #   hot_rows > 0 : hot/got in/out pairs (8 refs, fused-gather split)
    # then: ring (Rt_pad, d), ctx_tile (GK_pad, d), out_uniq
    # (n_buf, U_pad, d), out_exp (GM_pad, d), ctx_win (k_pad, d), out_win
    # (m_pad, d), sem [, sem_pf (2,) when prefetch]
    *refs,
    w_f: int,
    n_neg: int,
    tile: int,
    gemm_windows: int,
    hot_rows: int = 0,
    prefetch: bool = False,
):
    """T consecutive windows per step. Collision-free tiles (host `strict`
    bit clear) fetch the tile's deduplicated output rows as one batched DMA,
    then update in GEMM groups of ``G = gemm_windows`` windows: each group
    runs two (G*K, G*m, d) MXU-shaped GEMMs and applies its deltas to the
    VMEM ring and out_uniq block before the next group reads them — so DMA
    amortizes over the whole tile while value staleness is bounded by G
    (DESIGN.md §4). Strict tiles replay the exact sequential path.

    With ``hot_rows > 0`` (fused gather, DESIGN.md §8) the working table
    arrives *split* — hot replica + gathered cold block — and every row DMA
    routes through :class:`_Table`. With ``prefetch`` (the fused-gather
    entry point) ``out_uniq`` is double-buffered across tiles: while tile i
    runs its GEMM groups, tile i+1's unique output rows stream HBM→VMEM
    into the other half, and only rows colliding with tile i's write-back
    set (detected by trace-recomputable SMEM compares, like
    ``_kernel_pipelined``) are re-fetched synchronously — so cold-row fetch
    overlaps window compute instead of serializing ahead of it."""
    n_tab = 4 if hot_rows else 2
    outs = refs[n_tab:2 * n_tab]
    scratch = refs[2 * n_tab:]
    if hot_rows:
        w_in_tab = _Table(outs[0], outs[2], hot_rows)
        w_out_tab = _Table(outs[1], outs[3], hot_rows)
    else:
        w_in_tab = _Table(outs[0])
        w_out_tab = _Table(outs[1])
    if prefetch:
        (ring, ctx_tile, out_uniq, out_exp, ctx_win, out_win, sem,
         sem_pf) = scratch
    else:
        ring, ctx_tile, out_uniq, out_exp, ctx_win, out_win, sem = scratch
        sem_pf = None

    L = tokens_ref.shape[2]
    nt = ucount_ref.shape[2]
    rt = tile + 2 * w_f            # ring positions covering the whole tile
    k = 2 * w_f
    m = n_neg + 1
    M = tile * m                   # output slots per tile
    G = gemm_windows
    gk_pad = ctx_tile.shape[0]
    gm_pad = out_exp.shape[0]
    u_pad = out_uniq.shape[1]
    d = ring.shape[-1]
    tok, neg = _Idx(tokens_ref), _Idx(negs_ref, n_neg)
    uniq, scat = _Idx(uniq_ref, M), _Idx(scat_ref, M)
    ucount, strict_bit = _Idx(ucount_ref), _Idx(strict_ref)
    length = length_ref[0, 0, 0]
    lr = lr_ref[0]

    def load_ring(q):
        w_in_tab.load(tok[q], ring.at[pl.ds(q % rt, 1)], sem)

    def store_ring(p):
        w_in_tab.store(ring.at[pl.ds(p % rt, 1)], tok[p], sem)

    def was_prefetched(ti, c):
        """Was uniq column c of tile ti prefetched during tile ti-1? A pure
        function of SMEM state, evaluated identically at the start site
        (tile ti-1) and the wait site (tile ti): both tiles must run the
        fused path, c must be a real column, and the row must not collide
        with tile ti-1's write-back set (a stale prefetch otherwise)."""
        tc = jnp.clip(ti, 0, nt - 1)
        pv = jnp.maximum(tc - 1, 0)
        ok = ((ti > 0) & (ti < nt) & (ti * tile < length)
              & (strict_bit[tc] == 0) & (strict_bit[pv] == 0)
              & (c < ucount[tc]))
        idx = uniq[tc, c]
        hit = jnp.bool_(False)
        for cc in range(M):
            hit = jnp.logical_or(
                hit, jnp.logical_and(cc < ucount[pv],
                                     idx == uniq[pv, cc]))
        return jnp.logical_and(ok, ~hit)

    # --- preload positions 0..w_f-1 ---
    def preload(q, _):
        @pl.when(q < length)
        def _():
            load_ring(q)
        return 0

    jax.lax.fori_loop(0, min(w_f, L), preload, 0, unroll=True)

    def advance_window(t):
        """Seed-kernel ring advance for window t: store the r-distance
        evictee (its updates are complete), then load the leading edge.
        The *slot* modulus is rt (big ring: rows stay resident for context
        reads across the tile) but the *store schedule* is the sequential
        kernel's r-distance one. Strict tiles call this per window, so
        their loads see HBM exactly as fresh as under `_kernel`; in fused
        tiles only group window 0 goes through here — the remaining G-1
        loads run ahead of their evictees' stores, which widens the seed
        kernel's benign duplicate-token race from distance < r to
        < r + G - 1 (DESIGN.md §4)."""
        q = t + w_f

        @pl.when(q < length)
        def _():
            @pl.when(q - r_seq >= 0)
            def _():
                store_ring(q - r_seq)
            load_ring(q)

    r_seq = 2 * w_f + 1            # sequential store distance

    def tile_step(i, _):
        t0 = i * tile
        strict = strict_bit[i] != 0
        # double-buffer parity: tile i's rows live in half i % 2 (half 0
        # always when the prefetch stage is off)
        buf = jax.lax.rem(i, 2) if prefetch else 0

        # ---- strict fallback: bit-identical sequential replay (the ring
        # advance interleaves per window exactly as `_kernel`) ----
        @pl.when(strict)
        def _():
            for w in range(tile):
                t = t0 + w

                @pl.when(t < length)
                def _():
                    advance_window(t)
                    _seq_window(t, tok, neg, w_out_tab, ring,
                                ctx_win, out_win, sem, w_f=w_f, n_neg=n_neg,
                                r=rt, length=length, L=L, lr=lr)

        # ---- fused path: one batched fetch per tile + per-group GEMMs ----
        @pl.when(~strict)
        def _():
            # batched multi-row fetch of the deduplicated output rows:
            # issue every start, then wait — one DMA-latency exposure per
            # tile instead of one per row (paper §3.1 amortization). Rows
            # already in flight from the previous tile's prefetch stage
            # only need their wait.
            u = ucount[i]
            for c in range(M):
                fetch = c < u
                if prefetch:
                    fetch = jnp.logical_and(fetch, ~was_prefetched(i, c))

                @pl.when(fetch)
                def _(c=c):
                    w_out_tab.start_load(uniq[i, c],
                                         out_uniq.at[buf, pl.ds(c, 1)], sem)

                @pl.when(~(c < u))
                def _(c=c):
                    out_uniq[buf, pl.ds(c, 1), :] = jnp.zeros(
                        (1, d), out_uniq.dtype)
            for c in range(M):
                fetch = c < u
                if prefetch:
                    pf = was_prefetched(i, c)
                    fetch = jnp.logical_and(fetch, ~pf)

                    @pl.when(pf)
                    def _(c=c):
                        w_out_tab.wait_load(uniq[i, c],
                                            out_uniq.at[buf, pl.ds(c, 1)],
                                            sem_pf.at[buf])

                @pl.when(fetch)
                def _(c=c):
                    w_out_tab.wait_load(uniq[i, c],
                                        out_uniq.at[buf, pl.ds(c, 1)], sem)
            if u_pad > M:
                out_uniq[buf, pl.ds(M, u_pad - M), :] = jnp.zeros(
                    (u_pad - M, d), out_uniq.dtype)

            # ---- overlap: start streaming tile i+1's unique rows into the
            # other half while this tile's GEMM groups run; rows colliding
            # with this tile's write-back set stay un-prefetched (the wait
            # site recomputes the same predicate and sync-loads them) ----
            if prefetch:
                nxt = jnp.minimum(i + 1, nt - 1)
                for c in range(M):
                    @pl.when(was_prefetched(i + 1, c))
                    def _(c=c):
                        w_out_tab.start_load(
                            uniq[nxt, c],
                            out_uniq.at[1 - buf, pl.ds(c, 1)],
                            sem_pf.at[1 - buf])

            # GEMM groups of G windows: deltas land in the VMEM ring /
            # out_uniq between groups, bounding staleness to G windows
            # while the HBM traffic stays once-per-tile
            def fused_group(base, w0, wn):
                # gather the group's context rows from the (fresh) ring
                for w in range(wn):
                    _gather_window_ctx(ring, ctx_tile, base + w, w * k,
                                       w_f=w_f, r=rt, length=length, L=L)
                _zero_rows(ctx_tile, wn * k, gk_pad)

                # expand the group's slots from the (fresh) compacted rows
                for sj in range(wn * m):
                    col = scat[i, w0 * m + sj]
                    out_exp[pl.ds(sj, 1), :] = out_uniq[buf,
                                                        pl.ds(col, 1), :]
                _zero_rows(out_exp, wn * m, gm_pad)

                # two MXU-shaped GEMMs with a block-diagonal mask (window
                # w's context rows pair only with window w's slots)
                # (built directly in 2-D, like `_window_label_mask`)
                ri = jax.lax.broadcasted_iota(jnp.int32, (gk_pad, gm_pad), 0)
                ci = jax.lax.broadcasted_iota(jnp.int32, (gk_pad, gm_pad), 1)
                jr = jax.lax.rem(ri, k)
                win_r = jax.lax.div(ri, k)
                offs_arr = jnp.where(jr < w_f, jr - w_f, jr - w_f + 1)
                p_arr = base + win_r + offs_arr
                row_valid = ((p_arr >= 0) & (p_arr < length)
                             & (base + win_r < length) & (ri < wn * k))
                win_c = jax.lax.div(ci, m)
                col_valid = (base + win_c < length) & (ci < wn * m)
                label = (jax.lax.rem(ci, m) == 0).astype(jnp.float32)
                mask = row_valid & col_valid & (win_r == win_c)

                d_ctx, d_out = _window_update(ctx_tile[...], out_exp[...],
                                              label, mask, lr)

                # apply context deltas (repeats accumulate in slot order)
                for w in range(wn):
                    _scatter_window_ctx(ring, d_ctx, base + w, w * k,
                                        w_f=w_f, r=rt, L=L)

                # compact output deltas through the scatter map (invalid
                # slots carry zero gradient)
                for sj in range(wn * m):
                    col = scat[i, w0 * m + sj]
                    out_uniq[buf, pl.ds(col, 1), :] = (
                        out_uniq[buf, pl.ds(col, 1), :] + d_out[sj:sj + 1, :])

            for b in range((tile + G - 1) // G):
                w0 = b * G
                wn = min(G, tile - w0)         # windows in this group
                base = t0 + w0

                @pl.when(base < length)
                def _(base=base, w0=w0, wn=wn):
                    # ring advance for the group: window 0 follows the exact
                    # sequential store-then-load order (its evictee is
                    # complete); the remaining loads batch up front and
                    # their evictees are stored after the GEMM below, once
                    # this group's context updates have landed
                    advance_window(base)
                    for w in range(1, wn):
                        q = base + w + w_f

                        @pl.when(q < length)
                        def _(q=q):
                            load_ring(q)

                    fused_group(base, w0, wn)

                    for w in range(1, wn):
                        q = base + w + w_f
                        p = q - r_seq

                        @pl.when(jnp.logical_and(q < length, p >= 0))
                        def _(p=p):
                            store_ring(p)

            # write each unique row back once per tile
            for c in range(M):
                @pl.when(c < u)
                def _(c=c):
                    w_out_tab.start_store(out_uniq.at[buf, pl.ds(c, 1)],
                                          uniq[i, c], sem)
            for c in range(M):
                @pl.when(c < u)
                def _(c=c):
                    w_out_tab.wait_store(out_uniq.at[buf, pl.ds(c, 1)],
                                         uniq[i, c], sem)
        return 0

    def guarded_tile(i, c):
        @pl.when(i * tile < length)
        def _():
            tile_step(i, c)
        return 0

    jax.lax.fori_loop(0, nt, guarded_tile, 0)

    # --- flush surviving ring entries (increasing position order); the
    # r-distance store schedule leaves the same survivors as `_kernel` ---
    def flush(kk, _):
        p = length - r_seq + kk

        @pl.when(jnp.logical_and(p >= 0, p < length))
        def _():
            store_ring(p)
        return 0

    jax.lax.fori_loop(0, r_seq, flush, 0, unroll=True)


# ---------------------------------------------------------------------------
# Host-side entry points
# ---------------------------------------------------------------------------

_HBM = pl.BlockSpec(memory_space=pl.ANY)     # tables: DMA'd by hand
_SCALAR = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole (1,) lr


def _per_sentence(*arrays):
    """Lane-dense per-sentence SMEM operands: each ``(S, ...)`` int array
    becomes ``(S, 1, n)`` (``n`` = elements per sentence) with a ``(1, 1,
    n)`` block per grid step. The unit axis makes the block's last two
    dims equal the array's — the TPU block-shape rule for a block that is
    not (8, 128)-aligned — and the flat minor axis keeps each block's
    SMEM bill at its element count, so the paper's L = 1000 fits (a
    ``(1, L, N)`` block pads N up to 128 lanes: 512 KiB per buffer).
    Returns ``(operands, block_specs)``; kernels read the blocks through
    :class:`_Idx`."""
    ops, specs = [], []
    for x in arrays:
        n = math.prod(x.shape[1:])
        ops.append(x.reshape(x.shape[0], 1, n))
        specs.append(pl.BlockSpec((1, 1, n), lambda s: (s, 0, 0),
                                  memory_space=pltpu.SMEM))
    return ops, specs


def fullw2v_pallas(
    w_in: jax.Array,     # (V, d) f32
    w_out: jax.Array,    # (V, d) f32
    tokens: jax.Array,   # (S, L) int32
    negs: jax.Array,     # (S, L, N) int32
    lengths: jax.Array,  # (S,) int32
    lr: jax.Array,       # scalar f32
    w_f: int,
    interpret: bool = False,
    pipeline: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """One FULL-W2V training pass over a batch of sentences."""
    S = tokens.shape[0]
    n_neg = negs.shape[-1]
    V, d = w_in.shape
    assert d % LANE == 0, f"embedding dim {d} must be a multiple of {LANE}"
    r = 2 * w_f + 1
    r_pad = _round_up(r, SUBLANE)
    k_pad = _round_up(2 * w_f, SUBLANE)
    m_pad = _round_up(n_neg + 1, SUBLANE)

    lr_arr = jnp.asarray(lr, jnp.float32).reshape((1,))

    grid = (S,)
    if pipeline:
        kernel = functools.partial(_kernel_pipelined, w_f=w_f, n_neg=n_neg)
        scratch = [
            pltpu.VMEM((r_pad, d), jnp.float32),
            pltpu.VMEM((k_pad, d), jnp.float32),
            pltpu.VMEM((2, m_pad, d), jnp.float32),   # double buffer
            pltpu.SemaphoreType.DMA,                   # ring/stores
            pltpu.SemaphoreType.DMA((2,)),             # per-half prefetch
        ]
    else:
        kernel = functools.partial(_kernel, w_f=w_f, n_neg=n_neg)
        scratch = [
            pltpu.VMEM((r_pad, d), jnp.float32),
            pltpu.VMEM((k_pad, d), jnp.float32),
            pltpu.VMEM((m_pad, d), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ]
    idx, idx_specs = _per_sentence(tokens, negs, lengths)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[*idx_specs, _SCALAR, _HBM, _HBM],
        out_specs=[_HBM, _HBM],
        out_shape=[
            jax.ShapeDtypeStruct((V, d), w_in.dtype),
            jax.ShapeDtypeStruct((V, d), w_out.dtype),
        ],
        scratch_shapes=scratch,
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(*idx, lr_arr, w_in, w_out)
    return out[0], out[1]


def fullw2v_pallas_tiled(
    w_in: jax.Array,     # (V, d) f32
    w_out: jax.Array,    # (V, d) f32
    tokens: jax.Array,   # (S, L) int32
    negs: jax.Array,     # (S, L, N) int32
    lengths: jax.Array,  # (S,) int32
    lr: jax.Array,       # scalar f32
    w_f: int,
    tile: int,
    uniq: jax.Array,     # (S, nt, T*(N+1)) int32 — from plan_tiles
    scatter: jax.Array,  # (S, nt, T*(N+1)) int32
    ucount: jax.Array,   # (S, nt) int32
    strict: jax.Array,   # (S, nt) int32
    gemm_windows: int = 0,   # windows per GEMM group; 0 -> min(tile, 4)
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Window-tile batched FULL-W2V pass (DESIGN.md §4). The tile schedule
    must come from `repro.data.batching.plan_tiles(tokens, negs, lengths,
    tile)` for the same batch. ``gemm_windows`` bounds intra-tile value
    staleness: output/context deltas are applied in VMEM between GEMM
    groups, so only ~G windows ever read stale values while HBM traffic
    stays once-per-tile."""
    S, L = tokens.shape
    n_neg = negs.shape[-1]
    V, d = w_in.shape
    assert d % LANE == 0, f"embedding dim {d} must be a multiple of {LANE}"
    assert tile >= 1
    G = resolve_gemm_windows(tile, gemm_windows)
    m = n_neg + 1
    nt = uniq.shape[1]
    M = tile * m
    assert uniq.shape == (S, nt, M), (uniq.shape, (S, nt, M))
    assert scatter.shape == (S, nt, M)
    assert nt == -(-L // tile)
    dims = tiled_scratch_rows(tile, w_f, n_neg, G)

    lr_arr = jnp.asarray(lr, jnp.float32).reshape((1,))

    kernel = functools.partial(_kernel_tiled, w_f=w_f, n_neg=n_neg,
                               tile=tile, gemm_windows=G)
    scratch = [
        pltpu.VMEM((dims["ring"], d), jnp.float32),
        pltpu.VMEM((dims["ctx_tile"], d), jnp.float32),  # one GEMM group
        pltpu.VMEM((1, dims["out_uniq"], d), jnp.float32),
        pltpu.VMEM((dims["out_exp"], d), jnp.float32),   # one GEMM group
        pltpu.VMEM((dims["ctx_win"], d), jnp.float32),   # strict path
        pltpu.VMEM((dims["out_win"], d), jnp.float32),   # strict path
        pltpu.SemaphoreType.DMA,
    ]
    (tk, ng, ln, *plan), specs = _per_sentence(
        tokens, negs, lengths, uniq, scatter, ucount, strict)
    out = pl.pallas_call(
        kernel,
        grid=(S,),
        in_specs=[*specs[:3], _SCALAR, *specs[3:], _HBM, _HBM],
        out_specs=[_HBM, _HBM],
        out_shape=[
            jax.ShapeDtypeStruct((V, d), w_in.dtype),
            jax.ShapeDtypeStruct((V, d), w_out.dtype),
        ],
        scratch_shapes=scratch,
        input_output_aliases={8: 0, 9: 1},
        interpret=interpret,
    )(tk, ng, ln, lr_arr, *plan, w_in, w_out)
    return out[0], out[1]


def fullw2v_pallas_tiled_fused(
    hot_in: jax.Array,   # (hot, d) f32 — replicated hot head
    hot_out: jax.Array,  # (hot, d) f32
    got_in: jax.Array,   # (R, d) f32 — gathered cold block (request order)
    got_out: jax.Array,  # (R, d) f32
    tokens: jax.Array,   # (S, L) int32 — working-table ids (< hot + R)
    negs: jax.Array,     # (S, L, N) int32
    lengths: jax.Array,  # (S,) int32
    lr: jax.Array,       # scalar f32
    w_f: int,
    tile: int,
    uniq: jax.Array,     # (S, nt, T*(N+1)) int32 — from plan_tiles
    scatter: jax.Array,  # (S, nt, T*(N+1)) int32
    ucount: jax.Array,   # (S, nt) int32
    strict: jax.Array,   # (S, nt) int32
    gemm_windows: int = 0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The window-tiled pass on a *split* vocab-sharded working table
    (DESIGN.md §8 fused gather): the hot replica and the gathered cold
    block stay separate HBM buffers — every token/negative/plan id below
    ``hot`` streams from ``hot_*``, the rest from ``got_*`` at ``id -
    hot`` — and the tile fetch stage is double-buffered so tile i+1's
    cold-row DMAs overlap tile i's window GEMMs. Semantics are identical
    to running :func:`fullw2v_pallas_tiled` on ``concat(hot, got)`` and
    splitting the result."""
    S, L = tokens.shape
    n_neg = negs.shape[-1]
    hot, d = hot_in.shape
    r_width = got_in.shape[0]
    assert d % LANE == 0, f"embedding dim {d} must be a multiple of {LANE}"
    assert hot >= 1
    assert got_in.shape == got_out.shape == (r_width, d)
    assert hot_out.shape == (hot, d)
    assert tile >= 1
    G = resolve_gemm_windows(tile, gemm_windows)
    m = n_neg + 1
    nt = uniq.shape[1]
    M = tile * m
    assert uniq.shape == (S, nt, M), (uniq.shape, (S, nt, M))
    assert scatter.shape == (S, nt, M)
    assert nt == -(-L // tile)
    dims = tiled_scratch_rows(tile, w_f, n_neg, G)

    lr_arr = jnp.asarray(lr, jnp.float32).reshape((1,))

    kernel = functools.partial(_kernel_tiled, w_f=w_f, n_neg=n_neg,
                               tile=tile, gemm_windows=G, hot_rows=hot,
                               prefetch=True)
    scratch = [
        pltpu.VMEM((dims["ring"], d), jnp.float32),
        pltpu.VMEM((dims["ctx_tile"], d), jnp.float32),
        pltpu.VMEM((2, dims["out_uniq"], d), jnp.float32),  # double buffer
        pltpu.VMEM((dims["out_exp"], d), jnp.float32),
        pltpu.VMEM((dims["ctx_win"], d), jnp.float32),
        pltpu.VMEM((dims["out_win"], d), jnp.float32),
        pltpu.SemaphoreType.DMA,                             # strict/ring
        pltpu.SemaphoreType.DMA((2,)),                       # per-half
    ]
    (tk, ng, ln, *plan), specs = _per_sentence(
        tokens, negs, lengths, uniq, scatter, ucount, strict)
    out = pl.pallas_call(
        kernel,
        grid=(S,),
        in_specs=[*specs[:3], _SCALAR, *specs[3:], *[_HBM] * 4],
        out_specs=[_HBM] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((hot, d), hot_in.dtype),
            jax.ShapeDtypeStruct((hot, d), hot_out.dtype),
            jax.ShapeDtypeStruct((r_width, d), got_in.dtype),
            jax.ShapeDtypeStruct((r_width, d), got_out.dtype),
        ],
        scratch_shapes=scratch,
        input_output_aliases={8: 0, 9: 1, 10: 2, 11: 3},
        interpret=interpret,
    )(tk, ng, ln, lr_arr, *plan, hot_in, hot_out, got_in, got_out)
    return out[0], out[1], out[2], out[3]
