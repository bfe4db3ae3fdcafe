"""Batched nearest-neighbour / analogy top-k over sharded tables.

The sharded path runs under ``shard_map`` on the mesh ``data`` axis
(DESIGN.md §10):

1. **Query-row gather** — hot rows come from the local replica; each
   cold query row is contributed by its owner shard and ``psum``'d, so
   every shard holds the full ``(B, d)`` query block for O(B·d)
   interconnect bytes — the serving analogue of the training exchange's
   O(distinct·d).
2. **Partial top-k** — each shard scores the candidates it is
   responsible for (shard 0 additionally scores the replicated hot head,
   so no candidate is scored twice) and takes a local
   ``jax.lax.top_k``.
3. **Cross-shard merge** — the ``n·k`` per-shard partials are
   ``all_gather``'d and re-ranked by ``(score desc, id asc)``; ties
   break identically to the dense oracle, which ranks with the same
   lexicographic key.

:func:`dense_topk` is the single-host jnp oracle: the same math on the
merged ``(V, d)`` table, kept as the parity reference the tests and the
serve-smoke CI job compare against (ids identical, scores within 1e-6).

Query encodings (ids are global vocabulary ids):

* ``mode="nn"``      — ``ids (B,)``: cosine neighbours of each word;
  the word itself is excluded from its candidates.
* ``mode="analogy"`` — ``ids (B, 3)`` rows ``(a, b, c)``: neighbours of
  the normalized ``a − b + c`` offset vector (3CosAdd); a, b, c are all
  excluded.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.vocab_placement import VocabPlacement

NEG_INF = -jnp.inf


def _rank(scores: jax.Array, ids: jax.Array, k: int
          ) -> Tuple[jax.Array, jax.Array]:
    """Top-k by ``(score desc, id asc)`` — the one ranking rule both the
    sharded merge and the dense oracle use, so ties cannot diverge."""
    order = jnp.lexsort((ids, -scores), axis=-1)[..., :k]
    return (jnp.take_along_axis(ids, order, axis=-1),
            jnp.take_along_axis(scores, order, axis=-1))


def _query_vectors(hot: jax.Array, cold: jax.Array, flat_ids: jax.Array,
                   placement: VocabPlacement, axis_name: str) -> jax.Array:
    """Gather normalized rows for global ids under shard_map: hot rows
    from the local replica, cold rows psum'd from their owner shard."""
    n, hot_n = placement.n_shards, placement.hot
    is_hot = flat_ids < hot_n
    hot_part = jnp.where(
        is_hot[:, None], hot[jnp.clip(flat_ids, 0, hot_n - 1)], 0.0)
    c = flat_ids - hot_n
    mine = (~is_hot) & (c % n == jax.lax.axis_index(axis_name))
    local = jnp.clip(c // n, 0, cold.shape[0] - 1)
    cold_part = jnp.where(mine[:, None], cold[local], 0.0)
    return hot_part + jax.lax.psum(cold_part, axis_name)


def _combine(rows: jax.Array, ids: jax.Array, mode: str
             ) -> Tuple[jax.Array, jax.Array]:
    """(query vectors (B, d), excluded ids (B, E)) for a query batch."""
    if mode == "nn":
        return rows, ids[:, None]
    if mode == "analogy":
        r = rows.reshape(ids.shape[0], 3, -1)
        q = r[:, 0] - r[:, 1] + r[:, 2]
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True),
                            1e-12)
        return q, ids
    raise ValueError(f"unknown query mode {mode!r} (nn | analogy)")


def make_topk_fn(placement: VocabPlacement, mesh, mode: str = "nn",
                 k: int = 5) -> Callable:
    """Build the jitted sharded top-k: ``fn(hot, cold, ids) -> (ids,
    scores)``, both ``(B, k)``. ``ids`` is ``(B,)`` for ``mode="nn"``,
    ``(B, 3)`` for ``mode="analogy"``; out-of-range/padded query slots
    are tolerated (clipped gathers) — callers mask their results.
    """
    from jax.sharding import PartitionSpec as P

    n, hot_n, v = placement.n_shards, placement.hot, placement.vocab_size
    cps = placement.cold_per_shard
    if k > hot_n + cps:
        raise ValueError(
            f"k={k} exceeds per-shard candidate count {hot_n + cps} "
            f"(hot={hot_n} + cold_per_shard={cps})")
    if mode not in ("nn", "analogy"):
        raise ValueError(f"unknown query mode {mode!r} (nn | analogy)")

    def local(hot, cold, ids):
        s = jax.lax.axis_index("data")
        flat = ids.reshape(-1).astype(jnp.int32)
        rows = _query_vectors(hot, cold, flat, placement, "data")
        q, excl = _combine(rows, ids.astype(jnp.int32), mode)
        # candidates this shard is responsible for: the hot head (shard 0
        # only, so replicated rows are scored exactly once) + its cold block
        cand = jnp.concatenate([hot, cold], axis=0)       # (hot_n + cps, d)
        gids = jnp.concatenate([
            jnp.arange(hot_n, dtype=jnp.int32),
            hot_n + s.astype(jnp.int32)
            + jnp.arange(cps, dtype=jnp.int32) * n])
        scores = q @ cand.T                               # (B, hot_n + cps)
        dead = (gids >= v)[None, :]                       # cold padding rows
        dead |= (jnp.arange(hot_n + cps) < hot_n)[None, :] & (s != 0)
        dead |= (gids[None, None, :] == excl[:, :, None]).any(axis=1)
        scores = jnp.where(dead, NEG_INF, scores)
        ids_l, sc_l = _rank(scores, jnp.broadcast_to(gids, scores.shape), k)
        # cross-shard merge: n·k partials, re-ranked by the same rule
        g_sc = jax.lax.all_gather(sc_l, "data")           # (n, B, k)
        g_id = jax.lax.all_gather(ids_l, "data")
        g_sc = jnp.moveaxis(g_sc, 0, 1).reshape(ids.shape[0], n * k)
        g_id = jnp.moveaxis(g_id, 0, 1).reshape(ids.shape[0], n * k)
        return _rank(g_sc, g_id, k)

    sharded = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P("data"), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(sharded)


def dense_topk(emb: np.ndarray, ids: np.ndarray, k: int = 5,
               mode: str = "nn", normalized: bool = True
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Single-host jnp oracle on a merged ``(V, d)`` table — the parity
    reference for the sharded path (same gather math, same exclusions,
    same ``(score desc, id asc)`` ranking). ``normalized=False``
    L2-normalizes rows first (e.g. a raw ``TrainSession.embeddings()``
    table)."""
    emb = jnp.asarray(np.asarray(emb, np.float32))
    if not normalized:
        emb = emb / jnp.maximum(
            jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-12)
    ids = jnp.asarray(np.asarray(ids, np.int32))
    rows = emb[ids.reshape(-1)]
    q, excl = _combine(rows, ids, mode)
    scores = q @ emb.T                                    # (B, V)
    gids = jnp.arange(emb.shape[0], dtype=jnp.int32)
    dead = (gids[None, None, :] == excl[:, :, None]).any(axis=1)
    scores = jnp.where(dead, NEG_INF, scores)
    out_ids, out_sc = _rank(scores, jnp.broadcast_to(gids, scores.shape), k)
    return np.asarray(out_ids), np.asarray(out_sc)
