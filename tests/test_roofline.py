"""Roofline machinery: HLO collective parsing, per-device accounting,
term arithmetic."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.roofline import (collective_bytes_from_text, model_flops,
                                     roofline_terms)
from repro.configs import get_arch

HLO_SAMPLE = """
ENTRY %main {
  %ag = f32[16,1024]{1,0} all-gather(%p0), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = bf16[8,256]{1,0} all-reduce(%x), channel_id=2, replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
  %rs = f32[4,128]{1,0} reduce-scatter(%y), channel_id=3, replica_groups={{0,1}}, dimensions={0}
  %cp = s8[64]{0} collective-permute(%z), channel_id=4, source_target_pairs={{0,1}}
  %dot = f32[8,8]{1,0} dot(%a, %b)
}
"""


def test_collective_parser():
    r = collective_bytes_from_text(HLO_SAMPLE)
    ag = 16 * 1024 * 4 * 1.0 * (3 / 4)
    ar = 8 * 256 * 2 * 2.0 * (7 / 8)
    rs = 4 * 128 * 4 * 1.0 * (1 / 2)
    assert np.isclose(r["by_kind"]["all-gather"], ag)
    assert np.isclose(r["by_kind"]["all-reduce"], ar)
    assert np.isclose(r["by_kind"]["reduce-scatter"], rs)
    assert r["op_counts"]["collective-permute"] == 1
    assert np.isclose(r["total_bytes"],
                      ag + ar + rs + r["by_kind"]["collective-permute"])


def test_parser_ignores_non_collectives():
    r = collective_bytes_from_text("%d = f32[4,4] dot(%a, %b)\n")
    assert r["total_bytes"] == 0


def test_cost_analysis_is_per_device():
    """Documented invariant: SPMD modules report per-device flops."""
    devs = jax.devices()
    if len(devs) < 1:
        return
    f = lambda x, w: (x @ w).sum()
    x = jax.ShapeDtypeStruct((128, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    c = jax.jit(f).lower(x, w).compile()
    assert abs(c.cost_analysis()["flops"] - 2 * 128 * 64 * 32) \
        < 0.1 * 2 * 128 * 64 * 32


def test_roofline_terms_bottleneck():
    r = roofline_terms(flops=197e12, bytes_hbm=819e9 * 2, bytes_coll=1e6,
                       n_chips=256)
    assert r["bottleneck"] == "memory"
    assert np.isclose(r["memory_s"], 2.0)
    assert np.isclose(r["compute_s"], 1.0)


def test_model_flops_moe_uses_active_params():
    arctic = get_arch("arctic-480b")
    dense_equiv = arctic.n_params()
    active = arctic.n_active_params()
    assert active < dense_equiv / 10  # 2 of 128 experts active
    assert model_flops(arctic, "train_4k") == 6.0 * active * 4096 * 256


def test_n_params_sane():
    """Config param counts within 15% of published sizes."""
    cases = {"yi-9b": 8.8e9, "gemma2-2b": 2.6e9, "phi3-mini-3.8b": 3.8e9,
             "qwen2-vl-72b": 72e9, "arctic-480b": 480e9,
             "musicgen-large": 3.3e9,  # "large" = 3.3B (arXiv:2306.05284)
             "hymba-1.5b": 1.5e9,
             "xlstm-350m": 0.35e9, "minicpm-2b": 2.4e9}
    for name, want in cases.items():
        n = get_arch(name).n_params()
        assert 0.7 * want < n < 1.45 * want, (name, n, want)
