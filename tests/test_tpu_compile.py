"""The Pallas kernels of the main path compile for a TPU v5e at yi-9b widths.

Nothing runs: each test lowers a kernel for one chip of a described (not
attached) `v5e:2x2` topology and has the TPU compiler accept it, which is
what interpret-mode tests cannot show (tile alignment, vector layouts,
scalar bitcasts). The topology is described inside a fixture, never at
import, so only the worker that runs these tests loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import autotune
from repro.kernels.bfp_quantize import bfp_quantize_pallas
from repro.kernels.hbfp_flash_attn import FlashSpec, flash_attention_vjp
from repro.kernels.hbfp_matmul import (hbfp_dgrad_pallas, hbfp_matmul_pallas,
                                       hbfp_wgrad_pallas)

# yi-9b: d_model 4096, d_ff 11008, 32 heads of 128; B=2, S=2048 tokens
M, K, N = 4096, 4096, 11008
BH, S, HD = 64, 2048, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology compile cannot be read back from the persistent
    # cache without a chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(hlo: str, name: str):
    """Exactly one `tpu_custom_call` of the compiled module is `name`."""
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sum(name in line.split("=")[0] for line in calls) == 1, calls


SEED = ((1, 1), jnp.int32)

# weight GEMMs (M, K, N) of the benchmark's configurations at B·S = 4096
# tokens (the head on loss chunks of 2048), compiled at the tiles the shape
# rule picks for them
RULE_GEMMS = {
    "yi.ffn_up": (4096, 4096, 11008), "yi.ffn_down": (4096, 11008, 4096),
    "yi.attn_qo": (4096, 4096, 4096), "yi.attn_kv": (4096, 4096, 512),
    "yi.head": (2048, 4096, 8000),
    "minicpm.attn": (4096, 2304, 2304), "minicpm.ffn_up": (4096, 2304, 5760),
    "minicpm.ffn_down": (4096, 5760, 2304),
    "minicpm.head": (2048, 2304, 15344),
}


def _gemm(op, gemm):
    """(M, K, N) padded to 128 and the tiles to compile at: the kernels'
    default 128 tiles at yi-9b's FFN width (gemm None), else the shape
    rule's for one benchmark GEMM."""
    if gemm is None:
        return (M, K, N), {}
    dims = RULE_GEMMS[gemm]
    bm, bk, bn = autotune.shape_tiles(f"matmul_{op}", *dims)
    return tuple(-(-d // 128) * 128 for d in dims), dict(bm=bm, bk=bk, bn=bn)


def _cases(old_ids):
    """The 128-tile cases under their former ids, then every benchmark
    GEMM at its rule tiles (nearest), and yi's FFN-up rule tiles with
    stochastic rounding and with pre-narrowed weights."""
    out = [pytest.param(None, *args, id=i) for i, args in old_ids]
    out += [pytest.param(g, True, False, id=g) for g in RULE_GEMMS]
    out += [pytest.param("yi.ffn_up", True, True, id="yi.ffn_up-stochastic"),
            pytest.param("yi.ffn_up", False, False, id="yi.ffn_up-narrow_w")]
    return out


@pytest.mark.parametrize("gemm,quantize_w,stochastic", _cases(
    [("False-True", (True, False)), ("False-False", (False, False)),
     ("True-True", (True, True)), ("True-False", (False, True))]))
def test_matmul_fwd_compiles(one_chip, gemm, quantize_w, stochastic):
    (m, k, n), tiles = _gemm("fwd", gemm)
    fn = functools.partial(hbfp_matmul_pallas, mantissa_bits=8,
                           stochastic=stochastic, quantize_w=quantize_w,
                           **tiles)
    hlo = _compile(fn, one_chip, ((m, k), jnp.bfloat16),
                   ((k, n), jnp.bfloat16), SEED)
    _assert_kernel(hlo, "hbfp_matmul_fwd")


@pytest.mark.parametrize("gemm,quantize_w,stochastic", _cases(
    [("True", (True, False)), ("False", (False, False))]))
def test_matmul_dgrad_compiles(one_chip, gemm, quantize_w, stochastic):
    (m, k, n), tiles = _gemm("dgrad", gemm)
    fn = functools.partial(hbfp_dgrad_pallas, mantissa_bits=8,
                           stochastic=stochastic, quantize_w=quantize_w,
                           **tiles)
    hlo = _compile(fn, one_chip, ((m, n), jnp.float32),
                   ((k, n), jnp.bfloat16), SEED)
    _assert_kernel(hlo, "hbfp_matmul_dgrad")


@pytest.mark.parametrize("gemm,quantize_w,stochastic", _cases(
    [("yi.ffn_128", (True, False))])[:-1])
def test_matmul_wgrad_compiles(one_chip, gemm, quantize_w, stochastic):
    (m, k, n), tiles = _gemm("wgrad", gemm)
    fn = functools.partial(hbfp_wgrad_pallas, mantissa_bits=8,
                           stochastic=stochastic, **tiles)
    hlo = _compile(fn, one_chip, ((m, k), jnp.bfloat16),
                   ((m, n), jnp.float32), SEED)
    _assert_kernel(hlo, "hbfp_matmul_wgrad")


@pytest.mark.parametrize("kernel", ["fwd", "dgrad", "wgrad"])
def test_subtile_block_compiles(one_chip, kernel):
    """b=16 exponent groups inside the 128-wide kernel tiles."""
    if kernel == "fwd":
        fn = functools.partial(hbfp_matmul_pallas, mantissa_bits=8, block=16)
        shapes = (((M, K), jnp.bfloat16), ((K, N), jnp.bfloat16))
    elif kernel == "dgrad":
        fn = functools.partial(hbfp_dgrad_pallas, mantissa_bits=8, block=16)
        shapes = (((M, N), jnp.float32), ((K, N), jnp.bfloat16))
    else:
        fn = functools.partial(hbfp_wgrad_pallas, mantissa_bits=8, block=16)
        shapes = (((M, K), jnp.bfloat16), ((M, N), jnp.float32))
    hlo = _compile(fn, one_chip, *shapes, SEED)
    _assert_kernel(hlo, f"hbfp_matmul_{kernel}")


def test_flash_fwd_bwd_compiles(one_chip):
    spec = FlashSpec(m_bits=8, bq=128, bk=128, causal=True, interpret=False)

    def loss(q, k, v):
        return flash_attention_vjp(spec, q, k, v).astype(jnp.float32).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                   *[((BH, S, HD), jnp.bfloat16)] * 3)
    for name in ("hbfp_flash_fwd", "hbfp_flash_dq", "hbfp_flash_dkv"):
        _assert_kernel(hlo, name)


@pytest.mark.parametrize("stochastic", [False, True])
def test_bfp_quantize_with_stats_compiles(one_chip, stochastic):
    fn = functools.partial(bfp_quantize_pallas, mantissa_bits=8,
                           stochastic=stochastic, with_stats=True)
    hlo = _compile(fn, one_chip, ((K, N), jnp.float32), SEED)
    _assert_kernel(hlo, "bfp_quantize")
