"""HBFP training step.

Exactly the paper's §5.1 loop, distributed:

  1. narrow  = Q_narrow(master)           # 8/12-bit compute copy, cast to
     (cast to arch dtype, TP-only sharding)  # bf16 — exact for m ≤ 8
  2. grads   = ∇ loss(narrow, batch)      # all dot products BFP (custom VJP)
  3. updates = AdamW(grads)  in f32
  4. master  = Q_wide(master + updates)   # 16-bit wide weight storage

Distribution notes (beyond-paper, DESIGN.md §2):
  * master params + moments live ZeRO-1-sharded over (pod, data); step 1's
    sharding constraint makes XLA all-gather the *narrow bf16* copy — a 4×
    cheaper gather than f32 ZeRO, which is the paper's "lower communication
    bandwidth" claim realized for DP training;
  * gradient accumulation via lax.scan over microbatches;
  * optional BFP-compressed gradient all-reduce (grad_compress.py) for the
    shard_map DP path.

Precision (DESIGN.md §11): `make_step(arch, policy, lr_schedule)` is THE
entry point — it coerces any precision spec into a `PrecisionPolicy`,
compiles one jit variant per *distinct* resolved segment, dispatches on
the host step counter, and (optionally) closes the adaptive loop when a
`numerics.PrecisionController` is passed. `make_train_step` builds one
compiled step for one static segment (`precision.ResolvedPolicy`) and is
what `make_step` calls per segment; `make_scheduled_train_step` is the
deprecated pre-policy alias of `make_step`.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.opt_shell import hbfp_apply_updates, narrow_params
from repro.core.schedule_precision import as_schedule
from repro.models.layers import Ctx
from repro.models.transformer import loss_fn
from repro.optim.adamw import OptState, adamw_init, adamw_update
from repro.precision.policy import (PrecisionPolicy, ResolvedPolicy,
                                    as_policy, as_segment)


class TrainState(NamedTuple):
    params: Any          # master weights (wide-BFP values in f32 containers)
    opt: OptState
    step: jax.Array      # i32


def init_train_state(key, arch: ArchConfig, init_params_fn) -> TrainState:
    params = init_params_fn(key, arch)
    # master weights are f32 (wide 16-bit BFP mantissas don't fit bf16)
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    return TrainState(params=params, opt=adamw_init(params),
                      step=jnp.zeros((), jnp.int32))


def make_train_step(arch: ArchConfig, hbfp, schedule, *, grad_accum: int = 1,
                    fwd_constraint=None, grad_constraint=None,
                    act_constraint=None, shard_fn=None,
                    weight_decay: float = 0.1,
                    grad_clip: float = 1.0,
                    accum_unroll: bool = False,
                    taps=None):
    """Returns train_step(state, batch, key) -> (state, metrics).

    hbfp: the precision for this compiled step — a static
    `precision.ResolvedPolicy` segment, or any legacy static state coerced
    into one (None ⇒ fp32; HBFPConfig ⇒ the paper's uniform setting;
    `schedule_precision.ResolvedPrecision` ⇒ per-layer weight overrides).
    All pytree-static under jit; `make_step` builds one of these per
    distinct policy segment. The backend comes from the segment (legacy
    specs pick up `arch.kernel_backend`).
    fwd_constraint: optional fn(params_pytree) -> params_pytree applying
    with_sharding_constraint for the TP-only fwd copy (set by the launcher;
    identity on single device).
    grad_constraint: optional fn(grads)->grads constraining gradients to the
    ZeRO-sharded master layout — turns the DP all-reduce into a
    reduce-scatter (each rank only needs its update shard).
    act_constraint: optional fn(x)->x sequence-parallel residual-stream
    constraint (threaded through Ctx into the layer scan).
    taps: optional `numerics.TapConfig` — THIS compiled step becomes the
    telemetry variant: metrics gains a "numerics" entry, a fixed-size pytree
    of per-parameter `TensorStats` for the weight narrowing and (optionally)
    gradient/activation fidelity (DESIGN.md §9). The main-path computation
    is bit-identical to taps=None (the weight tap reuses the same
    quantization); cadence dispatch lives in `make_step`.

    Each phase runs under a `jax.named_scope`, so the compiled ops' names
    (`op_name` metadata, and a profiler trace of the device) say which
    phase they belong to: "hbfp.narrow" (narrowing, cast, fwd
    constraint), "model" (the loss; its gradient is
    "transpose(jvp(model))"), "optim.adamw" (clip and AdamW) and
    "hbfp.widen" (the wide-BFP update). Scopes change metadata only.
    """
    compute_dtype = jnp.dtype(arch.dtype)
    seg = as_segment(hbfp, backend=arch.kernel_backend)
    backend = seg.backend
    # Split the segment into the in-graph activation config and the
    # weight-tree resolver; both are static under jit.
    if seg.is_fp32:
        act_cfg = param_cfg = None
        stochastic = False
    elif seg.has_overrides or seg.global_cfg is None:
        # per-layer weight widths (schedule overrides / numerics controller
        # decisions) are resolved by the shell's narrowing — the matmuls
        # (sim ops AND the fused kernels' quantize_w) must not re-quantize
        # at the segment's global width and crush a widened layer
        act_cfg = None if seg.global_cfg is None else \
            seg.global_cfg.with_(requantize_weights=False)
        param_cfg = seg
        stochastic = seg.any_stochastic
    else:
        # uniform precision: weights are narrowed once per step by
        # narrow_params below, so per-matmul weight re-quantization is an
        # idempotent no-op. The sim path skips it to save quantize work;
        # the pallas path keeps it (quantize-in-VMEM is fused and free, and
        # integral mantissas are what unlock the int8 MXU path) —
        # DESIGN.md §10.
        act_cfg = seg.global_cfg.with_(
            requantize_weights=(backend == "pallas"))
        param_cfg = seg.global_cfg.with_(requantize_weights=False)
        if seg.role_widths:
            # keep the role table visible to resolve_param_cfg so the
            # numerics grad tap measures at the wgrad width, not the fwd
            # width (weight narrowing itself resolves role "fwd" — values
            # bit-identical to the bare-config path)
            param_cfg = ResolvedPolicy(global_cfg=param_cfg,
                                       role_widths=seg.role_widths,
                                       backend=backend)
        stochastic = seg.global_cfg.rounding == "stochastic"

    # the execution segment the model graph sees: the activation config
    # plus the policy's per-GEMM-role widths and backend (ctx_matmul)
    exec_seg = ResolvedPolicy(global_cfg=act_cfg,
                              role_widths=seg.role_widths, backend=backend)

    if taps is not None and param_cfg is None:
        taps = None  # true fp32 step: nothing to measure (per-layer-only
        # configs — global_cfg None with weight overrides — keep their taps)

    def cast(p):
        def one(x):
            # quantizable matrices run in compute dtype; tiny FP params
            # (norm scales, gates) stay f32
            return x.astype(compute_dtype) if x.ndim >= 2 else x
        return jax.tree.map(one, p)

    # the activation tap measures against the global activation config, so
    # it needs one (weight/grad taps only need per-param configs)
    act_tap = taps is not None and taps.acts and grad_accum == 1 \
        and act_cfg is not None

    def loss_at(narrow, batch, key):
        # the backward pass carries transpose(jvp(model)) in its op names
        with jax.named_scope("model"):
            ctx = Ctx(key=key, compute_dtype=compute_dtype,
                      act_constraint=act_constraint, shard_fn=shard_fn,
                      act_tap=act_tap, policy=exec_seg)
            return loss_fn(narrow, batch, arch, ctx)

    def train_step(state: TrainState, batch, key):
        numerics = {}
        with jax.named_scope("hbfp.narrow"):
            nkey = None
            if stochastic:
                nkey = jax.random.fold_in(key, 0x5EED)
            if taps is not None and taps.weights:
                from repro.numerics.collect import narrow_params_with_stats
                narrow, numerics["weights"] = narrow_params_with_stats(
                    state.params, param_cfg, nkey)
            else:
                narrow = narrow_params(state.params, param_cfg, nkey)
            narrow = cast(narrow)
            if fwd_constraint is not None:
                narrow = fwd_constraint(narrow)

        if grad_accum > 1:
            # batch leaves are [A, ...]; scan accumulates mean grads
            def micro(carry, mb):
                g_acc, l_acc = carry
                (l, m), g = jax.value_and_grad(loss_at, has_aux=True)(
                    narrow, mb, key)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32) / grad_accum,
                    g_acc, g)
                return (g_acc, l_acc + l / grad_accum), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              narrow)
            carry = (g0, jnp.zeros((), jnp.float32))
            if accum_unroll:  # roofline extraction: per-microbatch ops
                for a in range(grad_accum):  # visible to cost analysis
                    carry, _ = micro(carry,
                                     jax.tree.map(lambda t: t[a], batch))
                grads, loss = carry
            else:
                (grads, loss), _ = jax.lax.scan(micro, carry, batch)
            metrics = {"loss": loss}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_at, has_aux=True)(narrow, batch, key)
            if act_tap:
                metrics = dict(metrics)
                numerics["acts"] = metrics.pop("act_stats")

        if taps is not None and taps.grads:
            from repro.numerics.collect import grad_stats
            numerics["grads"] = grad_stats(grads, param_cfg)

        with jax.named_scope("optim.adamw"):
            if grad_constraint is not None:
                grads = grad_constraint(grads)
            updates, opt = adamw_update(grads, state.opt, state.params,
                                        lr=schedule,
                                        weight_decay=weight_decay,
                                        grad_clip=grad_clip)
        with jax.named_scope("hbfp.widen"):
            params = hbfp_apply_updates(state.params, updates, param_cfg,
                                        key)
        metrics = dict(metrics)
        metrics["lr"] = schedule(opt.step) if callable(schedule) \
            else jnp.asarray(schedule)
        if numerics:
            metrics["numerics"] = numerics
        return TrainState(params, opt, state.step + 1), metrics

    return train_step


def _tap_widths(seg: ResolvedPolicy, snapshot: dict) -> dict:
    """Resolved mantissa widths for every tapped tensor — pure host
    metadata attached to telemetry snapshots so per-role policies are
    *observable* in the numerics taps: the weight tap quantizes at the fwd
    width, the gradient tap at the wgrad width (0 ⇒ FP)."""
    out = {}
    for source, role in (("weights", "fwd"), ("grads", "wgrad")):
        if source not in snapshot:
            continue
        widths = {}
        for name in snapshot[source]:
            c = seg.for_param(name, role)
            widths[name] = 0 if c is None else c.mantissa_bits
        out[source] = widths
    return out


def make_step(arch: ArchConfig, policy, schedule, *,
              controller=None, tap=None, recorder=None,
              jit_compile: bool = True, donate: bool = False, **kwargs):
    """THE train-step entry point (DESIGN.md §11): one `PrecisionPolicy`
    drives format, schedule, per-layer/per-role widths, controller loop,
    and kernel backend.

    Returns `train_step(state, batch, key) -> (state, metrics)` — a *host*
    dispatcher over compiled variants:

      * `policy` may be a PrecisionPolicy, a policy spec string, a
        PrecisionSchedule, an HBFPConfig, or None (all coerced via
        `precision.as_policy`; legacy specs pick up `arch.kernel_backend`).
      * one jit variant is compiled per *distinct* resolved segment
        (`ResolvedPolicy` hashes by value, so equal segments share a
        compile); a constant policy is bit-identical to the pre-policy
        static path (regression-tested) and keeps JAX's async dispatch
        (no host sync on the step counter).
      * `tap` (a `numerics.TapConfig`) enables telemetry on its cadence:
        collection steps run the instrumented variant and `metrics` gains
        the "numerics" stats pytree.
      * `controller` (a `numerics.PrecisionController`) closes the loop:
        telemetry snapshots (plus their resolved widths) land in
        `.buffer`, feed `controller.observe`, and the controller's
        override state merges into the segment for the *next* step —
        variants are cached per (segment ⊕ overrides, telemetry), so the
        loop compiles O(#distinct decisions), not O(steps).
      * `recorder` (an `obs.Recorder`, DESIGN.md §12) streams the run
        into the log: `"train/recompile"` when a new jit variant is
        built, `"numerics/snapshot"` (per-layer scalar signals + resolved
        widths) on every tap-cadence collection — with or without a
        controller — and the controller's `"precision/decision"` events
        (the controller picks up this recorder unless it already has
        one), and a `"kernel/gemm"` per Pallas GEMM when a step traces
        (op, shape, tiles, MXU path). Emission is host-side: the compiled
        computation is bit-identical with or without a recorder.

    `metrics` gains "mantissa_bits" (the segment's global width, 0 for
    FP32) and — with a controller — "n_overrides" / "min_mantissa_bits".
    Attributes on the returned fn: `.policy`, `.variants`, `.controller`,
    `.buffer`, `.tap`. Extra kwargs forward to `make_train_step`.
    """
    from repro.kernels.linear import gemm_events
    from repro.obs import NULL_RECORDER
    rec = recorder if recorder is not None else NULL_RECORDER
    pol = as_policy(policy, backend=arch.kernel_backend)
    buffer = None
    if controller is not None:
        from repro.numerics.collect import RingBuffer, TapConfig
        if pol.format(0) is None:
            raise ValueError("adaptive precision needs a BFP base format; "
                             "fp32 has nothing to widen or narrow")
        tap = tap if tap is not None else TapConfig()
        buffer = RingBuffer(tap.history, recorder=rec)
        if rec.enabled and getattr(controller, "recorder", None) is None:
            controller.recorder = rec  # decisions stream as events

    variants = {}
    segments = {}

    def segment(i: int) -> ResolvedPolicy:
        seg = segments.get(i)
        if seg is None:
            seg = segments[i] = pol.resolve_segment(i)
        return seg

    def variant(seg: ResolvedPolicy, telemetry: bool, step):
        fn = variants.get((seg, telemetry))
        if fn is None:
            fn = make_train_step(arch, seg, schedule,
                                 taps=tap if telemetry else None, **kwargs)
            if jit_compile:
                fn = jax.jit(fn, donate_argnums=(0,) if donate else ())
            variants[(seg, telemetry)] = fn
            gcfg = seg.global_cfg
            rec.emit("train/recompile", step=step,
                     mantissa_bits=0 if gcfg is None else gcfg.mantissa_bits,
                     n_overrides=len(seg.layer_overrides)
                     + len(seg.controller_overrides),
                     backend=seg.backend, telemetry=telemetry,
                     n_variants=len(variants))
        return fn

    # int(state.step) blocks on the previous step's output (a host sync
    # per step) — skip it entirely when nothing dispatches on the step
    single = pol.num_segments == 1 and controller is None \
        and (tap is None or tap.cadence is None)

    def train_step(state: TrainState, batch, key):
        if single:
            step, seg, telemetry = None, segment(0), False
        else:
            step = int(state.step)
            seg = segment(pol.segment_index(step))
            telemetry = tap is not None and tap.collect_at(step)
        if controller is not None:
            # the controller's override state names the current adaptive
            # "segment"; decisions take effect at the next step
            seg = seg.with_controller(controller.overrides())
        # a first call traces: each kernel GEMM logs its tiles and path
        with gemm_events(rec):
            state, metrics = variant(seg, telemetry, step)(state, batch, key)
        metrics = dict(metrics)
        if telemetry and (controller is not None or rec.enabled):
            from repro.numerics.stats import stats_to_host
            # absent when every tap is disabled for this step shape (e.g.
            # acts-only taps under grad accumulation) — nothing to observe.
            # Without a controller the stats pytree stays in metrics for
            # upstream consumers (pre-recorder contract).
            numerics = (metrics.pop("numerics", None)
                        if controller is not None
                        else metrics.get("numerics"))
            if numerics is not None:
                snapshot = stats_to_host(numerics)
                snapshot["widths"] = _tap_widths(seg, snapshot)
                if controller is not None:
                    from repro.numerics.controller import merge_sources
                    buffer.append(step, snapshot)  # emits numerics/snapshot
                    controller.observe(step, merge_sources(snapshot))
                else:
                    from repro.numerics.collect import snapshot_event
                    rec.emit("numerics/snapshot", step=step,
                             **snapshot_event(snapshot))
        gcfg = seg.global_cfg
        metrics["mantissa_bits"] = jnp.asarray(
            0 if gcfg is None else gcfg.mantissa_bits, jnp.float32)
        if controller is not None:
            ovr = controller.overrides()
            # override values are bare widths or {"m", "b"} axis dicts
            # (block-axis decisions, DESIGN.md §13); a dict's "m" is None
            # when only the block diverged from the base format
            widths = [w.get("m") if isinstance(w, dict) else w
                      for _, w in ovr]
            widths = [w for w in widths if w is not None]
            widths.append(controller.base_bits)
            metrics["n_overrides"] = jnp.asarray(float(len(ovr)),
                                                 jnp.float32)
            metrics["min_mantissa_bits"] = jnp.asarray(float(min(widths)),
                                                       jnp.float32)
        return state, metrics

    train_step.policy = pol
    train_step.variants = variants  # exposed for tests / compile accounting
    train_step.controller = controller
    train_step.buffer = buffer
    train_step.tap = tap
    return train_step


def make_scheduled_train_step(arch: ArchConfig, precision, schedule, *,
                              jit_compile: bool = True, donate: bool = False,
                              **kwargs):
    """Deprecated alias of `make_step` (kept one release; DESIGN.md §11
    migration table). `precision` may be a PrecisionSchedule, HBFPConfig,
    or None — exactly the pre-policy surface; behaviour (including the
    "mantissa_bits" metric and per-segment compilation) is unchanged."""
    fn = make_step(arch, precision, schedule, jit_compile=jit_compile,
                   donate=donate, **kwargs)
    if not isinstance(precision, PrecisionPolicy):
        fn.schedule = as_schedule(precision)  # legacy attribute, kept
    return fn
