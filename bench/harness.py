"""What every cell shares: finding its files by name, the device check,
the compile cache, the compile counter, and the result line.

A cell is one entry of BENCHMARK.json's `workloads`. Everything that
belongs to one configuration, traffic mix or per-layer metric sits in a
file of its own under bench/, found by the name BENCHMARK.json gives:

  bench/configs/<config>.json   sizes as run, published values, cut;
                                `structure` names the model's module
  bench/structures/<structure>.py  the model: the program's arch, seeded
                                weights, f32 reference, work counts
                                (`dense_decoder` where the file names
                                none; the contract is in its __init__)
  bench/traffic/<traffic>.json  the mix's parameters; `kind` names the
                                runner
  bench/<kind>_cell.py          the runner: run(), finish(), calibrate()
  bench/limits/<workload>.json  limits of the correctness comparison
  bench/metrics/<metric>.py     a reader: read(reading) -> value or None
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class SetupError(RuntimeError):
    """The run cannot start: no accelerator, too few chips, a missing
    file. The run prints no result line."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list      # BENCHMARK.json end_to_end entries this cell reports
    per_layer: list       # per_layer entries this cell reports
    peaks: dict

    @property
    def name(self) -> str:
        return self.workload["name"]


def reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def find_cell(name: str, bench_file: str = None) -> Cell:
    """Everything one workload needs, from BENCHMARK.json and bench/."""
    spec = load_json(bench_file or os.path.join(ROOT, "BENCHMARK.json"))
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == wl["config"])
    e2e = [m for m in spec["end_to_end"] if reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if m["moves"] in e2e_names
             and reports(m, name)]
    return Cell(workload=wl,
                config=load_json(os.path.join(ROOT, conf["file"])),
                traffic=load_json(os.path.join(
                    BENCH, "traffic", wl["traffic"] + ".json")),
                limits=load_json(os.path.join(BENCH, "limits",
                                              name + ".json")),
                end_to_end=e2e, per_layer=layer,
                peaks=load_json(os.path.join(BENCH, "peaks.json")))


def device_info(chips: int) -> dict:
    """The devices JAX sees; SetupError unless they are TPUs, at least
    `chips` of them. Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SetupError(f"needs a TPU; JAX found {devs[0].platform} "
                         f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise SetupError(f"needs {chips} chips; JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peak_of(peaks: dict, kind: str) -> dict:
    if kind not in peaks["devices"]:
        raise SetupError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks["devices"][kind]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: $JAX_COMPILATION_CACHE_DIR when
    set, else .jax_cache/ at the checkout's root (a fixed path: the path
    is part of the cache's key). Every program is cached, however quick
    its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compiles (cache loads included) and persistent-cache
    hits and misses, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def load_runner(kind: str):
    """The runner of a traffic kind, bench/<kind>_cell.py."""
    if not (isinstance(kind, str) and kind.isidentifier() and os.path.isfile(
            os.path.join(BENCH, kind + "_cell.py"))):
        raise SetupError(f"no runner bench/{kind}_cell.py for traffic of "
                         f"kind {kind!r}")
    return importlib.import_module(kind + "_cell")


def load_reader(metric: str):
    """The per-layer metric's reader, bench/metrics/<metric>.py."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def memory_peak_bytes(n: int) -> int:
    """Peak bytes in use on the fullest of the first n devices."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:n])


def log(msg: str):
    print(msg, flush=True)


def checks_line(checks: list) -> dict:
    """{name: {value, limit}} of every number compared."""
    return {c["name"]: {"value": c["value"], "limit": c["limit"]}
            for c in checks}


def print_checks(checks: list):
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr,
              flush=True)
