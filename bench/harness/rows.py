"""The rows of the tables that the training check reads.

The checked steps touch R: the distinct ids of the real tokens of their
batches and of those tokens' negatives. The check reads the program's
tables and the reference's on R alone, gathered on the device, so the
host holds |R| x d floats a state and never V x d. Every other row has
to keep the bits of the initial draw: an exact digest of each row's bit
patterns, taken on the device shard by shard, finds those that did not.

The program's tables are read through ``TrainState.params()``: a
replicated session's ``w_in``/``w_out`` hold every row in id order; a
vocab-sharded one's ``hot_in``/``hot_out`` hold the replicated head, and
``cold_in``/``cold_out`` the tail striped over the mesh, each row on the
shard and at the local row its ``VocabPlacement`` gives.
"""
from __future__ import annotations

import functools
from typing import Dict, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from harness import reference

AXIS = "data"
LEAVES = ("w_in", "w_out")
SPLIT = {"w_in": ("hot_in", "cold_in"), "w_out": ("hot_out", "cold_out")}


def batch_ids(tokens: np.ndarray, negs: np.ndarray,
              lengths: np.ndarray) -> np.ndarray:
    """The sorted distinct ids of a batch's real tokens and of their
    negatives (positions past a row's length do not count)."""
    real = np.arange(tokens.shape[1])[None, :] < lengths[:, None]
    return np.unique(np.concatenate([tokens[real].ravel(),
                                     negs[real].ravel()]))


def union(parts: Iterable[np.ndarray]) -> np.ndarray:
    return functools.reduce(np.union1d, parts, np.zeros(0, np.int64))


def positions(ids: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Where each of ``x`` lies in the sorted ``ids``, as int32; an id
    that is not there maps to 0. The reference reads such ids (padding,
    a row left empty) only where it discards what it read."""
    at = np.minimum(np.searchsorted(ids, x), max(len(ids) - 1, 0))
    hit = ids[at] == x if len(ids) else np.zeros(np.shape(x), bool)
    return np.where(hit, at, 0).astype(np.int32)


def fetch(x) -> np.ndarray:
    """A device array on the host: every table rows the check copies to
    the host pass through here."""
    return np.asarray(x)


@jax.jit
def _take(table, ids):
    return table[ids]


@functools.lru_cache(maxsize=None)
def _striped_take(mesh: Mesh):
    """Rows of a table whose rows are split in blocks over ``mesh``, each
    named by its block (``owner``) and its row there (``local``): each
    shard reads its own, the rest of the rows zero, and the sum over the
    shards (one value and zeros) is replicated."""
    def local_take(block, owner, local):
        mine = owner == jax.lax.axis_index(AXIS)
        got = block[jnp.where(mine, local, 0)]
        return jax.lax.psum(jnp.where(mine[:, None], got, 0.0), AXIS)
    return jax.jit(jax.shard_map(local_take, mesh=mesh,
                                 in_specs=(P(AXIS), P(), P()),
                                 out_specs=P(), check_vma=False))


def _multipliers(dim: int) -> np.ndarray:
    rng = np.random.default_rng(0x6A09E667)
    m = rng.integers(0, 2 ** 32, size=(2, dim), dtype=np.uint64)
    return (m | 1).astype(np.uint32)


@jax.jit
def digest(table):
    """``(rows, 2)`` uint32 for a ``(rows, d)`` f32 table: two sums, mod
    2^32, of each row's bit patterns, every word times an odd constant of
    its column (in the second sum rotated by 13 bits first). Integer sums
    are exact in any order, so shards and backends agree. A row that
    differs in one word always reads another digest (an odd factor is
    invertible mod 2^32); one that differs in more, but for odds of
    about 2^-64."""
    bits = jax.lax.bitcast_convert_type(table, jnp.uint32)
    m = jnp.asarray(_multipliers(table.shape[1]))
    rot = (bits << 13) | (bits >> 19)
    return jnp.stack([jnp.sum(bits * m[0], axis=1, dtype=jnp.uint32),
                      jnp.sum(rot * m[1], axis=1, dtype=jnp.uint32)], axis=1)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _unstripe(hot, cold, n: int, per_shard: int, cold_rows: int):
    """Per-row values of a split table back in id order: id ``hot + c``
    lies on shard ``c % n`` at local row ``c // n``."""
    c = jnp.arange(cold_rows)
    return jnp.concatenate([hot, cold[(c % n) * per_shard + c // n]])


@jax.jit
def _changed_outside(d_in, d_out, init_in, zero, ids):
    outside = jnp.ones(d_in.shape[0], bool).at[ids].set(False)
    changed_in = jnp.any(d_in != init_in, axis=1) & outside
    changed_out = jnp.any(d_out != zero[None, :], axis=1) & outside
    return jnp.sum(changed_in) + jnp.sum(changed_out)


class Taken:
    """Rows of both tables, gathered on the device and on their way to
    the host."""

    def __init__(self, parts: Dict[str, list], hot_mask=None):
        self.parts, self.hot_mask = parts, hot_mask
        for arrays in parts.values():
            for x in arrays:
                x.copy_to_host_async()

    def host(self) -> Dict[str, np.ndarray]:
        if self.hot_mask is None:
            return {leaf: fetch(x) for leaf, (x,) in self.parts.items()}
        out = {}
        for leaf, (hot, cold) in self.parts.items():
            hot, cold = fetch(hot), fetch(cold)
            rows = np.empty((len(self.hot_mask),) + hot.shape[1:],
                            hot.dtype)
            rows[self.hot_mask], rows[~self.hot_mask] = hot, cold
            out[leaf] = rows
        return out


class StateRows:
    """Reads a training session's tables by id, in either layout."""

    def __init__(self, sess):
        self.placement, self.mesh = sess.placement, sess.mesh
        names = set(sess.state.params())
        if not names <= set(LEAVES) | {n for p in SPLIT.values() for n in p}:
            raise ValueError(f"the check reads f32 tables; the session "
                             f"stores {sorted(names)}")

    def take(self, state, ids: np.ndarray) -> Taken:
        params = state.params()
        idx = ids.astype(np.int32)
        pl = self.placement
        if pl is None:
            return Taken({leaf: [_take(params[leaf], idx)]
                          for leaf in LEAVES})
        hot_mask = ids < pl.hot
        cold = ids[~hot_mask]
        owner = pl.owner_of(cold).astype(np.int32)
        local = pl.local_row(cold).astype(np.int32)
        striped = _striped_take(self.mesh)
        return Taken({
            leaf: [_take(params[h], idx[hot_mask]),
                   striped(params[c], owner, local)]
            for leaf, (h, c) in SPLIT.items()}, hot_mask)

    def digests(self, state) -> Dict[str, jax.Array]:
        """Each table's row digests, in the session's layout, on the
        devices that hold the rows."""
        return {name: digest(x) for name, x in state.params().items()}

    def in_id_order(self, digests: Dict[str, jax.Array], device
                    ) -> Dict[str, jax.Array]:
        """:meth:`digests` in id order, on one device."""
        on = {k: jax.device_put(v, device) for k, v in digests.items()}
        pl = self.placement
        if pl is None:
            return {leaf: on[leaf] for leaf in LEAVES}
        return {leaf: _unstripe(on[h], on[c], pl.n_shards,
                                pl.cold_per_shard, pl.cold)
                for leaf, (h, c) in SPLIT.items()}


class Initial:
    """The reference's initial input table (``reference.input_rows``)
    drawn where the program's tables lived: on the cell's one device, or
    in row blocks over its devices, the rows padded to a multiple of
    their number. With the partitionable threefry that JAX uses, a row's
    draw depends on neither the table's length nor its sharding, so the
    rows are those of the one-device draw. The output table starts at
    zero."""

    def __init__(self, key, vocab: int, dim: int, devices):
        self.vocab, self.dim = vocab, dim
        self.mesh = None
        if len(devices) == 1:
            with jax.default_device(devices[0]):
                self.table = jax.jit(reference.input_rows,
                                     static_argnums=(1, 2))(key, vocab, dim)
            self.block = vocab
            return
        n = len(devices)
        self.block = -(-vocab // n)
        self.mesh = Mesh(np.array(devices), (AXIS,))
        self.table = jax.jit(
            reference.input_rows, static_argnums=(1, 2),
            out_shardings=NamedSharding(self.mesh, P(AXIS, None)))(
                key, self.block * n, dim)

    def rows(self, ids: np.ndarray) -> Dict[str, np.ndarray]:
        """Rows ``ids`` of both initial tables, on the host."""
        idx = ids.astype(np.int32)
        if self.mesh is None:
            w_in = _take(self.table, idx)
        else:
            w_in = _striped_take(self.mesh)(
                self.table, (idx // self.block).astype(np.int32),
                (idx % self.block).astype(np.int32))
        return {"w_in": fetch(w_in),
                "w_out": np.zeros((len(ids), self.dim), np.float32)}

    def untouched_changed(self, program: Dict[str, jax.Array],
                          ids: np.ndarray, device) -> int:
        """Rows outside ``ids``, of either table, whose digest in
        ``program`` (id order, on ``device``) differs from the initial
        draw's."""
        init_in = jax.device_put(digest(self.table), device)[:self.vocab]
        zero = digest(jnp.zeros((1, self.dim), jnp.float32))[0]
        return int(_changed_outside(
            program["w_in"], program["w_out"], init_in,
            jax.device_put(zero, device),
            jax.device_put(ids.astype(np.int32), device)))
