"""Kernel tile-size autotuner (DESIGN.md §10, docs/KERNELS.md).

The Pallas GEMM kernels take (bm, bk, bn) tile sizes; the best triple
depends on the problem shape, dtype, and mantissa width (int8 vs f32 MXU
path), and on the backend (interpret-mode CPU favors few large steps, TPU
favors MXU-aligned VMEM-resident tiles). This module provides:

  * `candidates(M, K, N)` — the search space: a power-of-two tile menu
    clipped to the problem, filtered by a double-buffered VMEM estimate;
  * `TuningTable` — a persisted on-disk JSON table mapping
    `op/MxKxN/dtype/m<bits>/b<block>` keys to the winning tiles + timings;
  * `lookup(op, M, K, N, ...)` — the trace-time entry point `ops.py` and
    `kernels/linear.py` call when no explicit tiles are given: returns the
    tuned tiles when the table has the shape, else `shape_tiles`, the
    rule that picks tiles from the GEMM's shape;
  * `autotune_op(...)` — measure every candidate for one op/shape and
    record the winner.

`benchmarks/kernel_bench.py` drives `autotune_op` over representative
shapes and records the default-vs-tuned speedups into BENCH_kernels.json;
the tuning table itself lives at results/autotune_kernels.json (override
with $REPRO_AUTOTUNE_TABLE).
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Optional, Tuple

from repro.kernels.common import GROUP, slice_width
from repro.kernels.hbfp_matmul import vmem_bytes
from repro.obs import NULL_RECORDER
from repro.obs.trace import time_fn

Tiles = Tuple[int, int, int]

# today's tiles for exponent groups under 128 (the dequantize-in-VMEM path)
DEFAULT_TILES: Tiles = (128, 128, 128)
TILE_MENU: Tuple[int, ...] = (32, 64, 128, 256)
# the tiles' share of a v5e core's 128 MiB VMEM (hbfp_matmul.vmem_bytes)
VMEM_BUDGET_BYTES = 64 * 2 ** 20
# shape rule: the longest contraction edge (8 exponent groups, unrolled in
# the kernel body) and output edge it picks
RULE_DEPTH = 1024
RULE_EDGE = 2048
TABLE_ENV = "REPRO_AUTOTUNE_TABLE"

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_TABLE_PATH = os.path.join(_ROOT, "results", "autotune_kernels.json")


def table_path() -> str:
    return os.environ.get(TABLE_ENV, DEFAULT_TABLE_PATH)


def cache_key(op: str, M: int, K: int, N: int, dtype: str,
              mantissa_bits: int, block: int = 0) -> str:
    """Table key: one entry per (op, logical shape, dtype, mantissa width,
    exponent-block size). The shape is the *logical* (M, K, N) of the GEMM —
    padding to tile multiples happens downstream and depends on the chosen
    tiles. `block` is the schedulable BFP block size (DESIGN.md §13);
    0 is the default whole-tile granularity. It changes the kernel dataflow
    (sub-block scales force the dequantize-in-VMEM path), so tuned tiles
    are not transferable across block sizes."""
    return f"{op}/{M}x{K}x{N}/{dtype}/m{mantissa_bits}/b{int(block)}"


def _padded(d: int) -> int:
    """A dim as the kernels tile it: itself up to 128, else padded to a
    multiple of 128 (the exponent group)."""
    return d if d <= GROUP else -(-d // GROUP) * GROUP


def clip_tiles(tiles: Iterable[int], M: int, K: int, N: int) -> Tiles:
    """Tiles no longer than the dims padded to whole exponent groups."""
    return tuple(min(int(t), _padded(d)) for t, d in zip(tiles, (M, K, N)))


def align_tiles(tiles: Iterable[int], block: int) -> Tiles:
    """Round each tile edge up to a multiple of the exponent-block size so
    sub-block groups divide the kernel tile exactly (pad-and-slice covers
    the overhang; zero padding quantizes to zero). block=0 ⇒ unchanged."""
    if not block:
        return tuple(int(t) for t in tiles)
    b = int(block)
    return tuple(-(-int(t) // b) * b for t in tiles)


def candidates(M: int, K: int, N: int, *,
               menu: Tuple[int, ...] = TILE_MENU,
               budget: int = VMEM_BUDGET_BYTES) -> Tuple[Tiles, ...]:
    """Distinct (bm, bk, bn) triples: the menu clipped to the problem dims,
    VMEM-feasible, deduplicated (clipping collapses oversized entries)."""
    out = []
    seen = set()
    for bm in menu:
        for bk in menu:
            for bn in menu:
                t = clip_tiles((bm, bk, bn), M, K, N)
                if t in seen or vmem_bytes(*t) > budget:
                    continue
                seen.add(t)
                out.append(t)
    return tuple(out)


class TuningTable:
    """On-disk tile-tuning table. JSON object: {key: entry} where entry is
    {"tiles": [bm, bk, bn], "us": winner_us, "default_us": us at the
    shape rule's tiles, "speedup": default_us/us, "backend": ...,
    "n_candidates": ...}. Unknown extra fields are preserved."""

    def __init__(self, entries: Optional[Dict[str, dict]] = None,
                 path: Optional[str] = None):
        self.entries: Dict[str, dict] = dict(entries or {})
        self.path = path or table_path()

    @classmethod
    def load(cls, path: Optional[str] = None) -> "TuningTable":
        path = path or table_path()
        entries: Dict[str, dict] = {}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    entries = json.load(f)
            except (OSError, json.JSONDecodeError):
                entries = {}  # corrupt table ⇒ behave as untuned
        return cls(entries, path)

    def get(self, key: str) -> Optional[Tiles]:
        e = self.entries.get(key)
        if not e or "tiles" not in e or len(e["tiles"]) != 3:
            return None
        return tuple(int(t) for t in e["tiles"])

    def put(self, key: str, tiles: Iterable[int], **meta) -> None:
        self.entries[key] = {"tiles": [int(t) for t in tiles], **meta}

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.entries, f, indent=1, sort_keys=True)
        os.replace(tmp, path)  # atomic, like checkpointing (DESIGN.md §6)
        return path


_CACHED: Optional[TuningTable] = None
_CACHED_PATH: Optional[str] = None


def get_table(refresh: bool = False) -> TuningTable:
    """Process-wide cached table (ops.py hits this at every trace)."""
    global _CACHED, _CACHED_PATH
    p = table_path()
    if refresh or _CACHED is None or _CACHED_PATH != p:
        _CACHED = TuningTable.load(p)
        _CACHED_PATH = p
    return _CACHED


def invalidate_cache() -> None:
    global _CACHED, _CACHED_PATH
    _CACHED = None
    _CACHED_PATH = None


def _edges(d: int, block: int) -> Tuple[int, ...]:
    """Tile edges for one GEMM dimension, largest first: the dimension
    itself up to 128, else the multiples of 128 that divide it padded to
    128 (so no padding beyond the 128 tiles'), of whole exponent groups."""
    if d <= GROUP:
        return (d,)
    n = _padded(d) // GROUP
    edges = (GROUP * t for t in range(n, 0, -1) if n % t == 0)
    return tuple(e for e in edges if e % slice_width(block, e) == 0)


# which tile of (bm, bk, bn) each op contracts
_DEPTH = {"matmul_fwd": 1, "matmul_dgrad": 2, "matmul_wgrad": 0}


def shape_tiles(op: str, M: int, K: int, N: int, block: int = 0,
                budget: int = VMEM_BUDGET_BYTES) -> Tiles:
    """The tiles an untuned GEMM runs at, from its shape alone. The
    contraction edge is the longest edge up to RULE_DEPTH (at most 8
    exponent groups a grid step); each output edge the longest up to
    RULE_EDGE, the larger stepping down until the tile's VMEM
    (`hbfp_matmul.vmem_bytes`, f32 operands) fits `budget`. Large output
    edges cut how often each operand block is fetched and re-quantized.
    Sub-128 exponent groups keep DEFAULT_TILES (their dequantize path
    gains nothing from long tiles)."""
    if block and block < GROUP:
        return clip_tiles(DEFAULT_TILES, M, K, N)
    dims = (M, K, N)
    depth = _DEPTH[op]
    out = [a for a in range(3) if a != depth]   # (rows, cols) of the tile
    opts = {a: [e for e in _edges(dims[a], block)
                if e <= (RULE_DEPTH if a == depth else RULE_EDGE)]
            or [min(_edges(dims[a], block))] for a in range(3)}
    pick = {a: 0 for a in range(3)}

    def tiles():
        return tuple(opts[a][pick[a]] for a in range(3))

    def fits():
        t = tiles()
        return vmem_bytes(t[out[0]], t[depth], t[out[1]]) <= budget

    while not fits():
        a = max((a for a in out if pick[a] + 1 < len(opts[a])),
                key=lambda a: opts[a][pick[a]], default=None)
        if a is None:
            break
        pick[a] += 1
    return tiles()


def lookup(op: str, M: int, K: int, N: int, *, dtype: str = "float32",
           mantissa_bits: int = 8, block: int = 0) -> Tiles:
    """Trace-time tile resolution: tuned tiles if the table has this
    (op, shape, dtype, m, b) cell, else the shape rule (`shape_tiles`) —
    always clipped to the problem so small shapes stay single-block."""
    t = get_table().get(cache_key(op, M, K, N, dtype, mantissa_bits, block))
    return clip_tiles(t or shape_tiles(op, M, K, N, block), M, K, N)


def _time_us(fn, n: int = 3, warmup: int = 1) -> float:
    """Min-of-n microbenchmark of `fn()` — the shared `obs.trace.time_fn`
    loop with the autotuner's historical semantics (sync each call,
    reduce=min; robust to host contention)."""
    import jax
    return time_fn(fn, n=n, warmup=warmup, sync=jax.block_until_ready,
                   reduce="min", sync_each=True)


def autotune_op(op: str, run_fn, M: int, K: int, N: int, *,
                dtype: str = "float32", mantissa_bits: int = 8,
                block: int = 0,
                table: Optional[TuningTable] = None,
                menu: Tuple[int, ...] = TILE_MENU,
                n: int = 3, save: bool = True, log=None,
                recorder=None):
    """Search tiles for one GEMM. `run_fn(tiles)` must execute the kernel
    once with those tiles (the harness times it, min-of-n). Records the
    winner into the table (and saves it) and returns (best_tiles, report)
    where report carries per-candidate timings plus the default-tiling
    baseline for the speedup accounting. `recorder`: optional
    `obs.Recorder` — emits "autotune/search" when the sweep starts and
    "autotune/winner" with the chosen tiles + speedup."""
    import jax
    rec = recorder if recorder is not None else NULL_RECORDER
    table = table or get_table()
    cands = candidates(M, K, N, menu=menu)
    default = shape_tiles(op, M, K, N, block)
    if default not in cands:
        cands = (default,) + cands
    key = cache_key(op, M, K, N, dtype, mantissa_bits, block)
    rec.emit("autotune/search", op=op, key=key, shape=[M, K, N],
             n_candidates=len(cands), n=n)
    timings = {}
    for t in cands:
        timings[t] = _time_us(lambda t=t: run_fn(t), n=n)
        if log:
            log(f"    {op} {M}x{K}x{N} tiles={t}: {timings[t]:9.1f} us")
    best = min(timings, key=timings.get)
    report = {
        "tiles": list(best), "us": round(timings[best], 1),
        "default_tiles": list(default),
        "default_us": round(timings[default], 1),
        "speedup": round(timings[default] / timings[best], 3),
        "backend": jax.default_backend(),
        "n_candidates": len(cands),
    }
    rec.emit("autotune/winner", op=op, key=key, **report)
    table.put(key, best,
              **{k: v for k, v in report.items() if k != "tiles"})
    if save:
        table.save()
        invalidate_cache()  # subsequent lookups see the new entry
    return best, report
