"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell with
the production shardings and extract memory / cost / collective statistics.

Two tracks per cell (DESIGN.md §7):
  * memory  — the FULL model with scan-over-layers: proves the sharding
    lowers, compiles, and reports per-device memory (compiled.memory_analysis).
  * roofline — the same program unrolled at 2 and 4 layers (identical
    shardings): XLA cost analysis counts while-bodies once, so per-layer
    costs are extracted exactly by the (c4-c2)/2 delta and extrapolated to
    the full depth; collective bytes are parsed from the compiled HLO.

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun.json
"""
# host-device fanout must be set before jax imports; the real
# imports below this block are therefore intentionally late
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import arch_ids, get_arch
from repro.configs.base import ArchConfig
from repro.core.formats import HBFP8_16, HBFPConfig
from repro.launch.mesh import make_production_mesh
from repro.models import init_params, make_cache
from repro.models.layers import Ctx
from repro.models.transformer import decode_step, prefill
from repro.optim import make_schedule
from repro.sharding.partitioning import (batch_specs, cache_specs,
                                         fwd_param_specs, master_param_specs,
                                         opt_state_specs)
from repro.train import init_train_state, make_train_step
from repro.analysis.roofline import (collective_bytes_from_text,
                                     roofline_terms)

SHAPES = {
    "train_4k":    dict(kind="train",   seq=4096,   batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768,  batch=32),
    "decode_32k":  dict(kind="decode",  ctx=32768,  batch=128),
    "long_500k":   dict(kind="decode",  ctx=524288, batch=1),
}

def _dpa(mesh):
    from repro.sharding.partitioning import dp_axes
    d = dp_axes(mesh)
    return d if len(d) > 1 else d[0]


def _mk_shard_fn(mesh):
    """Logical-axis sharding callback for model-internal layout hints."""
    logical = {"groups": _dpa(mesh), "experts": "model"}

    def f(x, axes):
        spec = P(*[logical.get(a) for a in axes])
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))
    return f


FULL_ATTENTION_SKIP = "long_500k needs sub-quadratic attention; this arch " \
    "has full-attention layers (DESIGN.md §5) — skipped by assignment rule."


def _sds(tree, specs, mesh):
    """ShapeDtypeStructs with NamedShardings attached."""
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tree, specs,
        is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct, jax.Array)))


def _batch_struct(arch: ArchConfig, kind: str, batch: int, seq: int,
                  ctx_len: Optional[int], mesh):
    dt = jnp.dtype(arch.dtype)
    b = {}
    if kind == "decode":
        pos_len = 1
    else:
        pos_len = seq
    if arch.input_kind == "embeddings":
        b["embeds"] = jax.ShapeDtypeStruct((batch, pos_len, arch.d_model), dt)
    elif arch.n_codebooks > 1:
        b["tokens"] = jax.ShapeDtypeStruct(
            (batch, pos_len, arch.n_codebooks), jnp.int32)
    else:
        b["tokens"] = jax.ShapeDtypeStruct((batch, pos_len), jnp.int32)
    if arch.mrope:
        b["positions"] = jax.ShapeDtypeStruct((3, batch, pos_len), jnp.int32)
    else:
        b["positions"] = jax.ShapeDtypeStruct((batch, pos_len), jnp.int32)
    if kind == "train":
        if arch.n_codebooks > 1:
            b["labels"] = jax.ShapeDtypeStruct(
                (batch, pos_len, arch.n_codebooks), jnp.int32)
        else:
            b["labels"] = jax.ShapeDtypeStruct((batch, pos_len), jnp.int32)
    specs = batch_specs(b, mesh)
    return _sds(b, specs, mesh)


def _serving_params_struct(arch: ArchConfig, mesh, ep_only: bool = False):
    dt = jnp.dtype(arch.dtype)
    p = jax.eval_shape(lambda s: init_params(jax.random.key(s), arch), 0)
    p = jax.tree.map(lambda l: jax.ShapeDtypeStruct(
        l.shape, dt if l.ndim >= 2 else l.dtype), p)
    return _sds(p, fwd_param_specs(p, mesh, ep_only=ep_only), mesh)


def build_cell(arch: ArchConfig, shape_name: str, mesh,
               hbfp: Optional[HBFPConfig], opts: Optional[dict] = None):
    """Returns (jitted_fn, args) ready to .lower(*args).

    opts (train cells — the §Perf hillclimb levers):
      grad_accum: int — microbatch accumulation (activation memory / N);
      zero_grads: bool — constrain grads to the ZeRO layout (all-reduce →
        reduce-scatter);
      seq_parallel: bool — sequence-shard the residual stream over `model`
        (Megatron-SP; remat-saved layer inputs shrink by the TP degree).
    """
    opts = opts or {}
    sh = SHAPES[shape_name]
    kind = sh["kind"]

    if kind == "train":
        state = jax.eval_shape(
            lambda s: init_train_state(jax.random.key(s), arch, init_params),
            0)
        pspecs = master_param_specs(state.params, mesh)
        ospecs = opt_state_specs(state.opt, state.params, mesh)
        sspecs = type(state)(params=pspecs, opt=ospecs, step=P())
        state_s = _sds(state, sspecs, mesh)
        accum = int(opts.get("grad_accum", 1))
        batch_s = _batch_struct(arch, kind, sh["batch"], sh["seq"], None,
                                mesh)
        if accum > 1:
            def micro(l):
                # mrope positions carry batch at dim 1 ([3, B, S])
                bdim = 1 if (l.ndim == 3 and l.shape[0] == 3
                             and l.dtype == jnp.int32) else 0
                shape = list(l.shape)
                shape[bdim] //= accum
                spec = list(l.sharding.spec)
                spec += [None] * (l.ndim - len(spec))
                return jax.ShapeDtypeStruct(
                    (accum,) + tuple(shape), l.dtype,
                    sharding=NamedSharding(mesh, P(None, *spec)))
            batch_s = jax.tree.map(micro, batch_s)
        key_s = jax.eval_shape(lambda s: jax.random.key(s), 0)
        fwd_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                              fwd_param_specs(state.params, mesh))
        constraint = lambda p: jax.lax.with_sharding_constraint(p, fwd_sh)
        grad_constraint = None
        if opts.get("zero_grads"):
            zsh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
            grad_constraint = \
                lambda g: jax.lax.with_sharding_constraint(g, zsh)
        act_constraint = None
        if opts.get("seq_parallel"):
            dpa = _dpa(mesh)
            sp = NamedSharding(mesh, P(dpa, "model", None))
            act_constraint = \
                lambda x: jax.lax.with_sharding_constraint(x, sp)
        shard_fn = _mk_shard_fn(mesh) if opts.get("moe_shard") else None
        sched = make_schedule(arch.lr_schedule, base_lr=3e-4,
                              warmup_steps=100, total_steps=10000)
        step = make_train_step(arch, hbfp, sched, grad_accum=accum,
                               fwd_constraint=constraint,
                               grad_constraint=grad_constraint,
                               act_constraint=act_constraint,
                               shard_fn=shard_fn,
                               # roofline track unrolls layers; unroll the
                               # microbatch loop too so costs are exact
                               accum_unroll=not arch.scan_layers)
        fn = jax.jit(step, donate_argnums=(0,))
        return fn, (state_s, batch_s, key_s)

    if kind == "prefill":
        params_s = _serving_params_struct(arch, mesh,
                                          ep_only=opts.get("ep_only", False))
        batch_s = _batch_struct(arch, kind, sh["batch"], sh["seq"], None,
                                mesh)
        cfg = None if hbfp is None else hbfp.with_(requantize_weights=False)
        cdt = jnp.dtype(arch.dtype)
        shard_fn = _mk_shard_fn(mesh) if opts.get("moe_shard") else None
        act_constraint = None
        if opts.get("seq_parallel"):
            sp = NamedSharding(mesh, P(_dpa(mesh), "model", None))
            act_constraint = \
                lambda x: jax.lax.with_sharding_constraint(x, sp)

        def prefill_fn(params, batch):
            return prefill(params, batch, arch,
                           Ctx(cfg, None, cdt, act_constraint, shard_fn))

        return jax.jit(prefill_fn), (params_s, batch_s)

    # decode: KV caches are sequence-sharded over `model` when kv-heads
    # don't divide it (flash-decoding layout, DESIGN.md §2)
    if opts.get("bfp_cache"):
        arch = dataclasses.replace(arch, bfp_kv_cache=True)
    params_s = _serving_params_struct(arch, mesh)
    batch_s = _batch_struct(arch, kind, sh["batch"], 1, sh["ctx"], mesh)
    cache = jax.eval_shape(
        lambda s: make_cache(init_params(jax.random.key(s), arch), arch,
                             sh["batch"], sh["ctx"]), 0)
    cache_s = _sds(cache, cache_specs(cache, mesh, seq_shard=True), mesh)
    cfg = None if hbfp is None else hbfp.with_(requantize_weights=False)
    cdt = jnp.dtype(arch.dtype)
    shard_fn = _mk_shard_fn(mesh) if opts.get("moe_shard") else None

    def decode_fn(params, batch, cache):
        return decode_step(params, batch, cache, arch,
                           Ctx(cfg, None, cdt, shard_fn=shard_fn))

    return jax.jit(decode_fn, donate_argnums=(2,)), \
        (params_s, batch_s, cache_s)


def applicable(arch: ArchConfig, shape_name: str) -> Optional[str]:
    """None if runnable, else skip reason."""
    if shape_name == "long_500k" and not arch.supports_long_context:
        return FULL_ATTENTION_SKIP
    return None


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             hbfp: Optional[HBFPConfig] = HBFP8_16,
             tracks=("memory", "roofline"), roofline_layers=(2, 4),
             opts: Optional[dict] = None):
    arch = get_arch(arch_id)
    skip = applicable(arch, shape_name)
    if skip:
        return {"arch": arch_id, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": skip}
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch_id, "shape": shape_name,
           "mesh": "multi" if multi_pod else "single",
           "hbfp": None if hbfp is None else hbfp.name, "status": "ok",
           "opts": opts or {}}

    if "memory" in tracks:
        t0 = time.time()
        fn, args = build_cell(arch, shape_name, mesh, hbfp, opts)
        lowered = fn.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 1)
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "generated_code_bytes":
                int(getattr(ma, "generated_code_size_in_bytes", 0)),
        }
        rec["memory"]["per_device_total_gib"] = round(
            (rec["memory"]["argument_bytes"] + rec["memory"]["output_bytes"]
             + rec["memory"]["temp_bytes"]) / 2**30, 3)

    if "roofline" in tracks:
        # unrolled lowering with ALL inner scans disabled/unrolled so XLA
        # cost analysis sees every op (while bodies are counted once):
        # q_chunk=0 -> full-matrix attention; loss_chunk=0 -> unchunked CE;
        # ssm_unroll -> python-looped SSD/mLSTM chunks. sLSTM's time scan
        # stays a while loop — its recurrent matmul (~10% of an sLSTM
        # layer, 1/8 of xlstm layers) is undercounted; noted in
        # EXPERIMENTS.md §Roofline caveats.
        costs = {}
        shp = SHAPES[shape_name]
        seq = shp.get("seq", shp.get("ctx", 4096))
        # bound unrolled SSD/mLSTM chunk count at 32 (tracing cost); the
        # chunk size used is recorded so the flops are interpretable
        ssm_chunk = arch.ssm_chunk if shp["kind"] == "decode" \
            else max(arch.ssm_chunk, seq // 32)
        rec["roofline_ssm_chunk"] = ssm_chunk
        for L in roofline_layers:
            a2 = dataclasses.replace(arch, n_layers=L, scan_layers=False,
                                     q_chunk=1 << 30, loss_chunk=0,
                                     ssm_unroll=True, ssm_chunk=ssm_chunk)
            fn, args = build_cell(a2, shape_name, mesh, hbfp, opts)
            compiled = fn.lower(*args).compile()
            ca = compiled.cost_analysis()
            coll = collective_bytes_from_text(compiled.as_text())
            costs[L] = {"flops": float(ca.get("flops", 0.0)),
                        "bytes": float(ca.get("bytes accessed", 0.0)),
                        "collective_bytes": coll["total_bytes"],
                        "collective_detail": coll["by_kind"]}
        L1, L2 = roofline_layers
        per_layer = {k: (costs[L2][k] - costs[L1][k]) / (L2 - L1)
                     for k in ("flops", "bytes", "collective_bytes")}
        fixed = {k: costs[L1][k] - L1 * per_layer[k]
                 for k in per_layer}
        full = {k: fixed[k] + arch.n_layers * per_layer[k] for k in per_layer}
        rec["roofline_raw"] = {"per_layer": per_layer, "fixed": fixed,
                               "full": full,
                               "collective_detail": costs[L2]
                               ["collective_detail"]}
        n_chips = int(np.prod(list(mesh.shape.values())))
        rec["roofline"] = roofline_terms(
            flops=full["flops"], bytes_hbm=full["bytes"],
            bytes_coll=full["collective_bytes"], n_chips=n_chips,
            arch=arch, shape_name=shape_name)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--fp32-baseline", action="store_true",
                    help="disable HBFP (paper's fp32 reference)")
    ap.add_argument("--tracks", default="memory,roofline")
    ap.add_argument("--out", default="results/dryrun.json")
    # §Perf hillclimb levers (train cells)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--zero-grads", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--moe-shard", action="store_true")
    ap.add_argument("--bfp-cache", action="store_true",
                    help="8-bit BFP KV cache (decode cells)")
    ap.add_argument("--ep-only", action="store_true",
                    help="MoE serving: shard only experts, replicate dense")
    ap.add_argument("--tag", default="",
                    help="suffix for the result key (optimized variants)")
    args = ap.parse_args()
    opts = {}
    if args.grad_accum > 1:
        opts["grad_accum"] = args.grad_accum
    if args.zero_grads:
        opts["zero_grads"] = True
    if args.seq_parallel:
        opts["seq_parallel"] = True
    if args.moe_shard:
        opts["moe_shard"] = True
    if args.bfp_cache:
        opts["bfp_cache"] = True
    if args.ep_only:
        opts["ep_only"] = True

    archs = list(arch_ids()) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    hbfp = None if args.fp32_baseline else HBFP8_16
    tracks = tuple(args.tracks.split(","))

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    for arch_id in archs:
        for shape in shapes:
            for mp in meshes:
                cell = f"{arch_id}|{shape}|{'multi' if mp else 'single'}" \
                    + ("|fp32" if hbfp is None else "") \
                    + (f"|{args.tag}" if args.tag else "")
                if results.get(cell, {}).get("status") in ("ok", "skipped"):
                    print(f"[cached] {cell}")
                    continue
                print(f"[run] {cell}", flush=True)
                t0 = time.time()
                try:
                    rec = run_cell(arch_id, shape, mp, hbfp, tracks,
                                   opts=opts)
                except Exception as e:  # record failures, keep going
                    rec = {"arch": arch_id, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "status": "error", "error": f"{type(e).__name__}:"
                           f" {e}", "trace": traceback.format_exc()[-2000:]}
                rec["wall_s"] = round(time.time() - t0, 1)
                results[cell] = rec
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
                print(f"  -> {rec['status']} ({rec['wall_s']}s)", flush=True)

    n_ok = sum(1 for r in results.values() if r["status"] == "ok")
    n_skip = sum(1 for r in results.values() if r["status"] == "skipped")
    n_err = sum(1 for r in results.values() if r["status"] == "error")
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_err} errors")


if __name__ == "__main__":
    main()
