"""Three-term roofline from compiled dry-run artifacts (TPU v5e target).

    compute    = HLO_FLOPs        / (chips × 197e12 FLOP/s  bf16)
    memory     = HLO_bytes        / (chips × 819e9  B/s HBM)
    collective = collective_bytes / (chips × 50e9   B/s/link ICI)

HLO_FLOPs / HLO_bytes come from compiled.cost_analysis() of the *unrolled*
lowering (launch/dryrun.py extrapolates per-layer deltas — XLA counts while
bodies once). collective_bytes is parsed from the compiled HLO text: we sum
the result-shape bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, with ring-algorithm wire multipliers
(all-reduce moves ≈2× its payload; the others ≈1×) and divide by the
participating group size to get *per-device link* bytes.

MODEL_FLOPS = 6·N·D for training (N params, D tokens), 2·N·D for inference
forward passes (2·N_active·D for MoE) — the useful-work yardstick; the
MODEL/HLO ratio exposes remat recompute and quantization overhead.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Optional

# TPU v5e hardware constants (per chip)
PEAK_FLOPS_BF16 = 197e12
PEAK_FLOPS_INT8 = 394e12
HBM_BW = 819e9
ICI_BW_PER_LINK = 50e9

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "s4": 1, "u4": 1,
}

# wire-bytes multiplier per collective kind (ring algorithms):
# all-reduce = reduce-scatter + all-gather ≈ 2× payload over the ring.
_KIND_MULT = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
              "all-to-all": 1.0, "collective-permute": 1.0}

_COLL_RE = re.compile(
    r"^\s*(?:%\S+\s*=\s*)?"                       # %name =
    r"\(?([a-z0-9]+)\[([0-9,]*)\]"                # dtype[shape]
    r".*?\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(", re.M)

_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9, ]+)\}")


def _shape_bytes(dtype: str, dims: str) -> float:
    nbytes = _DTYPE_BYTES.get(dtype, 4)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return float(n * nbytes)


def collective_bytes_from_text(hlo_text: str) -> Dict:
    """Sum per-device collective wire bytes from compiled HLO text."""
    by_kind: Dict[str, float] = defaultdict(float)
    count: Dict[str, int] = defaultdict(int)
    for m in _COLL_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        if kind.endswith("-done"):
            continue
        payload = _shape_bytes(dtype, dims)
        # per-device wire bytes ≈ payload × mult × (g-1)/g
        line = hlo_text[m.start():hlo_text.find("\n", m.start())]
        gm = _GROUPS_RE.search(line)
        if gm:
            g = len(gm.group(1).split(","))
        else:
            g = 2
        frac = (g - 1) / g if g > 1 else 0.0
        by_kind[kind] += payload * _KIND_MULT[kind] * frac
        count[kind] += 1
    return {"total_bytes": float(sum(by_kind.values())),
            "by_kind": dict(by_kind), "op_counts": dict(count)}


def model_flops(arch, shape_name: str) -> float:
    """6·N·D (train) / 2·N·D (inference) with N = active params."""
    n = arch.n_active_params()
    if shape_name.startswith("train"):
        seq, batch = 4096, 256
        return 6.0 * n * seq * batch
    if shape_name.startswith("prefill"):
        seq, batch = 32768, 32
        return 2.0 * n * seq * batch
    if shape_name.startswith("decode"):
        return 2.0 * n * 128          # one token × batch 128
    if shape_name.startswith("long"):
        return 2.0 * n * 1
    return 0.0


def roofline_terms(*, flops: float, bytes_hbm: float, bytes_coll: float,
                   n_chips: int, arch=None, shape_name: str = "",
                   peak_flops: float = PEAK_FLOPS_BF16) -> Dict:
    """All three terms in seconds + bottleneck + useful-work ratio.

    IMPORTANT: `flops`/`bytes_hbm`/`bytes_coll` are PER-DEVICE numbers —
    cost_analysis() of an SPMD-partitioned module describes the per-device
    program (verified in tests/test_roofline.py) — so each term divides by
    a single chip's peak.
    """
    t_compute = flops / peak_flops
    t_memory = bytes_hbm / HBM_BW
    t_coll = bytes_coll / ICI_BW_PER_LINK
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    bottleneck = max(terms, key=terms.get)
    out = {
        **terms,
        "bottleneck": bottleneck.replace("_s", ""),
        "step_time_lower_bound_s": max(terms.values()),
        "hlo_flops_per_device": flops, "hlo_bytes_per_device": bytes_hbm,
        "collective_bytes_per_device": bytes_coll,
        "n_chips": n_chips,
    }
    if arch is not None and shape_name:
        mf = model_flops(arch, shape_name)
        out["model_flops"] = mf
        global_flops = flops * n_chips
        out["useful_flops_ratio"] = (mf / global_flops) if global_flops \
            else 0.0
        # roofline fraction: useful FLOP/s achieved at the bound, vs peak
        bound = max(terms.values())
        out["roofline_fraction"] = \
            (mf / (n_chips * peak_flops)) / bound if bound else 0.0
    return out


def summarize(results: dict, shape_filter: Optional[str] = None):
    """Pretty table from a dryrun.json dict."""
    rows = []
    for cell, rec in sorted(results.items()):
        if rec.get("status") != "ok" or "roofline" not in rec:
            continue
        if shape_filter and rec["shape"] != shape_filter:
            continue
        r = rec["roofline"]
        rows.append((rec["arch"], rec["shape"], rec["mesh"],
                     r["compute_s"], r["memory_s"], r["collective_s"],
                     r["bottleneck"], r.get("useful_flops_ratio", 0.0),
                     r.get("roofline_fraction", 0.0)))
    hdr = (f"{'arch':24s} {'shape':12s} {'mesh':6s} {'compute_s':>11s} "
           f"{'memory_s':>11s} {'collect_s':>11s} {'bound':>10s} "
           f"{'useful':>7s} {'roofline':>8s}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(f"{r[0]:24s} {r[1]:12s} {r[2]:6s} {r[3]:11.4g} "
                     f"{r[4]:11.4g} {r[5]:11.4g} {r[6]:>10s} "
                     f"{r[7]:7.2%} {r[8]:8.2%}")
    return "\n".join(lines)
