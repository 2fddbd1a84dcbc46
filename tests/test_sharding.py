"""Sharding rules (pure spec logic — no multi-device requirement) plus an
8-device subprocess test of the compressed DP all-reduce."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch
from repro.models import init_params


class FakeMesh:
    """Duck-typed mesh: partitioning only reads .shape and .axis_names."""
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


@pytest.fixture(scope="module")
def yi_params():
    return jax.eval_shape(
        lambda s: init_params(jax.random.key(s), get_arch("yi-9b")), 0)


def _find(specs_tree, params, fragment):
    flat_s = jax.tree_util.tree_flatten_with_path(
        specs_tree, is_leaf=lambda x: isinstance(x, P))[0]
    out = {}
    for path, spec in flat_s:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if fragment in name:
            out[name] = spec
    return out


def test_tp_rules(yi_params):
    from repro.sharding import fwd_param_specs
    mesh = FakeMesh({"data": 16, "model": 16})
    specs = fwd_param_specs(yi_params, mesh)
    assert list(_find(specs, yi_params, "attn_wq").values())[0] \
        == P(None, None, "model")          # [L, D, H*hd] column-parallel
    assert list(_find(specs, yi_params, "attn_wo").values())[0] \
        == P(None, "model", None)          # row-parallel
    assert list(_find(specs, yi_params, "embed_table").values())[0] \
        == P("model", None)                # vocab-parallel
    assert list(_find(specs, yi_params, "norm").values())[0] == P()


def test_kv_divisibility_guard(yi_params):
    """yi-9b kv=4 heads, hd=128 -> wk [D, 512]; 512 % 16 == 0 -> sharded;
    on a model=1024 mesh it would not divide -> replicated."""
    from repro.sharding import fwd_param_specs
    specs = fwd_param_specs(yi_params, FakeMesh({"data": 1, "model": 1024}))
    assert list(_find(specs, yi_params, "attn_wk").values())[0] == P()


def test_ep_rules():
    from repro.sharding import fwd_param_specs
    params = jax.eval_shape(
        lambda s: init_params(jax.random.key(s), get_arch("arctic-480b")), 0)
    specs = fwd_param_specs(params, FakeMesh({"data": 16, "model": 16}))
    assert list(_find(specs, params, "moe_wg").values())[0] \
        == P(None, "model", None, None)    # [L, E, D, F] expert-parallel
    assert list(_find(specs, params, "router_w").values())[0] == P()


def test_zero1_adds_dp_sharding(yi_params):
    from repro.sharding import master_param_specs
    mesh = FakeMesh({"data": 16, "model": 16})
    specs = master_param_specs(yi_params, mesh)
    wq = list(_find(specs, yi_params, "attn_wq").values())[0]
    assert "model" in wq and any(s == ("data",) or s == "data"
                                 for s in wq if s)
    # multi-pod: ZeRO over (pod, data)
    specs3 = master_param_specs(yi_params,
                                FakeMesh({"pod": 2, "data": 16,
                                          "model": 16}))
    wq3 = list(_find(specs3, yi_params, "attn_wq").values())[0]
    assert ("pod", "data") in tuple(wq3)


def test_batch_specs():
    from repro.sharding import batch_specs
    mesh = FakeMesh({"data": 16, "model": 16})
    b = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32),
         "positions": jax.ShapeDtypeStruct((3, 256, 4096), jnp.int32),
         "labels": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    s = batch_specs(b, mesh)
    assert s["tokens"] == P("data", None)
    assert s["positions"] == P(None, "data", None)  # mrope batch at dim 1
    # non-divisible batch stays replicated
    b2 = {"tokens": jax.ShapeDtypeStruct((1, 64), jnp.int32)}
    assert batch_specs(b2, mesh)["tokens"] == P()


def test_cache_specs():
    from repro.sharding import cache_specs
    from repro.models import make_cache
    arch = get_arch("yi-9b")
    cache = jax.eval_shape(
        lambda s: make_cache(init_params(jax.random.key(s), arch), arch,
                             128, 1024), 0)
    mesh = FakeMesh({"data": 16, "model": 16})
    specs = cache_specs(cache, mesh)
    kspec = specs["kv"].k
    assert kspec[1] == "data"              # batch
    assert kspec[2] is None                # kv=4 !% 16 -> not sharded
    s2 = cache_specs(cache, mesh, seq_shard=True)
    assert s2["kv"].k[3] == "model"        # SP fallback over cache length


def test_compressed_psum_multidevice():
    """Run the BFP-compressed gradient all-reduce on 8 host devices."""
    code = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.core.grad_compress import compressed_psum_tree
mesh = jax.make_mesh((8,), ('data',))
smap = partial(jax.shard_map, check_vma=False)
g = {'w': jax.random.normal(jax.random.key(0), (8, 64, 128))}
@partial(smap, mesh=mesh, in_specs=P('data'), out_specs=P(None))
def red(gs):
    gs = jax.tree.map(lambda x: x[0], gs)
    out, _ = compressed_psum_tree(gs, 'data')
    return out
r = jax.jit(red)(g)
ref = g['w'].mean(axis=0)
rel = float(jnp.abs(r['w'] - ref).max() / jnp.abs(ref).max())
assert rel < 0.02, rel
txt = jax.jit(red).lower(g).compile().as_text()
assert 's8[' in txt and 'all-gather' in txt  # int8 wire format
print('OK', rel)
"""
    # inherit the full environment: XLA backend init can hang on a stripped
    # env (observed with --xla_force_host_platform_device_count on CPU)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ,
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "JAX_PLATFORMS": "cpu", "PYTHONPATH": "src"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
