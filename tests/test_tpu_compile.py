"""The Pallas kernels compile for a TPU v5e chip at the paper's shapes.

Interpret mode (every other kernel test) runs the kernel body as Python
and cannot see what the TPU compiler refuses: block shapes that break the
(8, 128) rule, vector layouts Mosaic cannot infer, or per-step SMEM
beyond its 1 MiB. These tests compile each entry point with the chip's
own compiler for a *described* ``v5e:2x2`` topology — no chip attached —
at d=128, W_f=3, N=5, L=1000 (``W2VConfig.resolved_pad_len``) and a
400k-row vocabulary.

The vocab-sharded step (``--tables shards=4``: fused-gather kernel plus
the request-exact ``all_to_all`` exchange) is compiled for the whole
described 2x2 host as well.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and pytest-xdist
workers all import this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs.w2v import W2VConfig
from repro.distributed.vocab_placement import VocabPlacement
from repro.kernels import ops
from repro.kernels.fullw2v import (fullw2v_pallas, fullw2v_pallas_tiled,
                                   fullw2v_pallas_tiled_fused)
from repro.kernels.registry import StepInputs
from repro.kernels.tables import TableSpec

CFG = W2VConfig()                        # the paper's widths
V, D, S = 400_000, CFG.dim, 64
L, N, W_F = CFG.resolved_pad_len, CFG.negatives, CFG.fixed_window
T, G = 8, 4
NT, M = -(-L // T), T * (N + 1)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    """The persistent compile cache off: an entry written here could not
    be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def host_2x2(topo, no_cache):
    """The four described chips as the (data=4, model=1) mesh
    ``launch.mesh.make_host_mesh`` builds on a 2x2 host."""
    return Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))


def _shapes(sharding):
    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    table = sds((V, D), jnp.float32)
    seq = (sds((S, L)), sds((S, L, N)), sds((S,)), sds((), jnp.float32))
    plan = (sds((S, NT, M)), sds((S, NT, M)), sds((S, NT)), sds((S, NT)))
    hot = sds((V // 8, D), jnp.float32)
    got = sds((V // 2, D), jnp.float32)
    return table, seq, plan, hot, got


def _sequential(pipeline):
    def build(table, seq, plan, hot, got):
        def f(w_in, w_out, tokens, negs, lengths, lr):
            return fullw2v_pallas(w_in, w_out, tokens, negs, lengths, lr,
                                  W_F, pipeline=pipeline)
        return f, (table, table, *seq)
    return build


def _tiled(table, seq, plan, hot, got):
    def f(w_in, w_out, tokens, negs, lengths, lr, *p):
        return fullw2v_pallas_tiled(w_in, w_out, tokens, negs, lengths, lr,
                                    W_F, T, *p, gemm_windows=G)
    return f, (table, table, *seq, *plan)


def _fused(table, seq, plan, hot, got):
    def f(hot_in, hot_out, got_in, got_out, tokens, negs, lengths, lr, *p):
        return fullw2v_pallas_tiled_fused(hot_in, hot_out, got_in, got_out,
                                          tokens, negs, lengths, lr, W_F, T,
                                          *p, gemm_windows=G)
    return f, (hot, hot, got, got, *seq, *plan)


@pytest.mark.parametrize("build", [
    _sequential(False), _sequential(True), _tiled, _fused,
], ids=["pallas", "pallas_pipelined", "pallas_tiled", "pallas_tiled_fused"])
def test_kernel_compiles_for_v5e(one_chip, build):
    fn, args = build(*_shapes(one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the tables alias in place: the kernel adds no table-sized temporary
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < V * D * 4


def test_vocab_sharded_step_compiles_for_v5e_2x2(host_2x2):
    """``--tables shards=4`` f32 at T=8: the fused-gather kernel inside
    the exact-exchange shard_map, compiled for four chips. The exchange
    widths (R requested rows per shard, C per owner bucket) are those
    ``plan_exchange`` gives a 2048-sentence batch of the paper's
    configuration."""
    n, r_width, cap = 4, 5376, 1344
    placement = VocabPlacement(vocab_size=V, hot=V // 32, n_shards=n)

    def sds(shape, spec, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(host_2x2, spec))

    rows = P("data")
    step = StepInputs(
        tokens=sds((S, L), rows), negs=sds((S, L, N), rows),
        lengths=sds((S,), rows), lr=sds((), P(), jnp.float32),
        plan_uniq=sds((S, NT, M), rows), plan_scatter=sds((S, NT, M), rows),
        plan_ucount=sds((S, NT), rows), plan_strict=sds((S, NT), rows),
        cold_ids=sds((n, r_width), rows),
        bucket_ids=sds((n, n, cap), rows), bucket_pos=sds((n, n, cap), rows))
    hot = sds((placement.hot, D), P(), jnp.float32)
    cold = sds((placement.cold_pad, D), rows, jnp.float32)
    cfg = W2VConfig(tile_windows=T, tile_gemm_windows=G)
    fn = ops._jitted_vs_update("pallas_tiled", ops.static_for(cfg, T),
                               TableSpec(vocab_shard=True, shards=n),
                               placement, host_2x2, "data")
    text = fn.lower(hot, hot, cold, cold, None, None, step).compile().as_text()
    assert "tpu_custom_call" in text and "all-to-all" in text
