"""Sharding rule resolution + param spec validity for every arch."""
import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_arch, list_archs
from repro.distributed.sharding import Rules, param_shardings
from repro.models import lm


def _mesh(multi_pod=False):
    if multi_pod:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


def test_resolve_divisibility():
    r = Rules(_mesh())
    assert r.resolve("heads", 64) == "model"
    assert r.resolve("heads", 24) == "model"      # uneven OK (padded)
    assert r.resolve("heads", 24, allow_uneven=False) is None
    assert r.resolve("kv_heads", 2) is None       # kv: replicate if uneven
    assert r.resolve("kv_heads", 16) == "model"
    assert r.resolve("batch", 256) == ("data",)
    assert r.resolve("experts", 128) == "model"


def test_resolve_multipod_batch():
    r = Rules(_mesh(multi_pod=True))
    assert r.resolve("batch", 256) == ("pod", "data")
    # batch=1 (long-context) cannot shard
    assert r.resolve("batch", 1) is None


def test_spec_no_duplicate_axes():
    r = Rules(_mesh())
    spec = r.spec(("vocab", "ff"), (4096, 4096))
    # 'model' may appear only once
    flat = [a for a in spec if a is not None]
    assert len(flat) == 1


def test_pod_axis_dropped_on_single_pod():
    r = Rules(_mesh())
    assert r._present(("pod", "data")) == ("data",)


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("multi_pod", [False, True])
def test_param_shardings_all_archs(arch, multi_pod):
    """Every param leaf of every arch gets an evenly-divisible spec on both
    production meshes (pjit argument requirement)."""
    cfg = get_arch(arch)
    rules = Rules(_mesh(multi_pod))
    p_abs = lm.abstract_params(cfg)
    shards = param_shardings(p_abs, rules)
    flat = jax.tree_util.tree_flatten_with_path(p_abs)[0]
    shard_flat = jax.tree.leaves(
        shards, is_leaf=lambda x: hasattr(x, "spec"))
    assert len(flat) == len(shard_flat)
    for (path, leaf), sh in zip(flat, shard_flat):
        spec = sh.spec
        for dim, axes in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if axes is None:
                continue
            axes = (axes,) if isinstance(axes, str) else axes
            size = 1
            for a in axes:
                size *= dict(zip(rules.mesh.axis_names,
                                 rules.mesh.axis_sizes))[a]
            assert dim % size == 0, (arch, path, leaf.shape, spec)


def test_opt_role_shards_embed():
    cfg = get_arch("qwen3-8b")
    rules = Rules(_mesh())
    p_abs = lm.abstract_params(cfg)
    p_sh = param_shardings(p_abs, rules)
    o_sh = param_shardings(p_abs, rules, role="opt")
    assert p_sh["embed"].spec == P(None, None)           # replicated param
    assert o_sh["embed"].spec != P(None, None)           # ZeRO-sharded state
