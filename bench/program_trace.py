"""The program's own names in a profiler trace: its spans, and the phase
scopes of its compiled train step.

bench/trace_reduce.py reads the device's ops by instruction name and the
benchmark's `bench.*` host spans. This module reads the same .xplane.pb
for what the program itself writes there:

  * host events named `repro.*`: the spans of `repro.obs` recorders that
    annotate on the profiler's clock (Trainer and ServeEngine do so by
    default), e.g. `repro.train/step` and its children `repro.train/data`
    and `repro.train/dispatch`;
  * each device op's scope path, the HLO `op_name` that `jax.named_scope`
    writes: "jit(train_step)/transpose(jvp(model))/model.ffn/dot_general".
    Source: the `tf_op` stat of the op's event metadata on the device
    plane, the op's own `op_name`. Where that names no scope (XLA leaves
    it off some fusions, async slices and its layout copies, or gives a
    copy its parameter's name), the path comes from the executed
    module's HLO, the `Hlo Proto` stat of the /host:metadata plane
    (`hlo_op_names`): a fusion's fused computation's, and a layout copy's
    or broadcast's, that of the op that uses its result, else of its
    operand. Ops with neither are unscoped.

A scope path's top-level scope is its first component, transforms
unwrapped ("transpose(jvp(model))" -> "model"), that is one of SCOPES;
"model.<part>" belongs to "model" (XLA inlines remat and loop bodies
under their own names). A fusion counts toward the scope of its own
name. Loops and calls (trace_reduce.CONTAINERS) span their bodies and
are left out, as in `trace_reduce.top_ops`.

`jax.profiler.ProfileData` does not expose event metadata, so the file is
read here a second time with a small protobuf wire reader (field numbers
of TSL's xplane.proto and XLA's hlo.proto).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import statistics
from typing import Dict, List, Optional, Tuple

import harness
import trace_reduce as trace

SCOPES = ("hbfp.narrow", "model", "optim.adamw", "hbfp.widen")
UPDATE_SCOPES = ("hbfp.narrow", "optim.adamw", "hbfp.widen")
STEP_MODULE = "jit_train_step"


@dataclasses.dataclass
class Program:
    trace: trace.Trace                    # trace_reduce's view, unchanged
    paths: Dict[str, Dict[str, str]]      # per device plane: op event -> path
    spans: List[Tuple[str, float, float]]  # repro.* host spans


# -- protobuf wire format ---------------------------------------------------

def _varint(b, i):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b):
    """(field number, value) of a message: ints for varints, memoryview
    slices for length-delimited fields, raw bytes for fixed widths."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif kind == 1:
            v, i = b[i:i + 8], i + 8
        elif kind == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} not supported")
        yield key >> 3, v


def _map_entries(b):
    k = v = None
    for f, x in _fields(b):
        if f == 1:
            k = x
        elif f == 2:
            v = x
    return k, v


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _planes(buf):
    """{plane name: {"events": {metadata name: {stat: value}},
    "stat_names": {id: name}}} of an XSpace, lines left undecoded."""
    out = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = _str(v)
            elif pf == 4:
                events.append(_map_entries(v)[1])
            elif pf == 5:
                sid, meta = _map_entries(v)
                for mf, mv in _fields(meta):
                    if mf == 2:
                        stat_names[sid] = _str(mv)
        out[name] = {"events": events, "stat_names": stat_names}
    for plane in out.values():
        names = plane["stat_names"]
        decoded = {}
        for ev in plane["events"]:
            ev_name, stats = "", {}
            for ef, ev_v in _fields(ev):
                if ef == 2:
                    ev_name = _str(ev_v)
                elif ef == 5:
                    sid, val = None, None
                    for sf, sv in _fields(ev_v):
                        if sf == 1:
                            sid = sv
                        else:               # 7: a reference to a name
                            val = names.get(sv, "") if sf == 7 else sv
                    stats[names.get(sid, "")] = val
            decoded[ev_name] = stats
        plane["events"] = decoded
    return out


def _packed(v) -> List[int]:
    if isinstance(v, int):
        return [v]
    out, j = [], 0
    while j < len(v):
        x, j = _varint(v, j)
        out.append(x)
    return out


def _instructions(hlo_proto):
    """{computation id: (instructions, root id)} of an HloProto; an
    instruction is a dict of name, op, op_name, id, operands, called."""
    comps = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for mf, comp in _fields(module):
            if mf != 3:
                continue
            cid, root, instrs = None, None, []
            for cf, cv in _fields(comp):
                if cf == 2:
                    ins = {"name": "", "op": "", "op_name": "", "id": None,
                           "operands": [], "called": []}
                    for inf, iv in _fields(cv):
                        if inf == 1:
                            ins["name"] = _str(iv)
                        elif inf == 2:
                            ins["op"] = _str(iv)
                        elif inf == 7:
                            for of, ov in _fields(iv):
                                if of == 2:
                                    ins["op_name"] = _str(ov)
                        elif inf == 35:
                            ins["id"] = iv
                        elif inf == 36:
                            ins["operands"] += _packed(iv)
                        elif inf == 38:
                            ins["called"] += _packed(iv)
                    instrs.append(ins)
                elif cf == 5:
                    cid = cv
                elif cf == 6:
                    root = cv
            comps[cid] = (instrs, root)
    return comps


def hlo_op_names(hlo_proto) -> Dict[str, str]:
    """Instruction name -> scope path from an executed module's HLO: the
    instruction's own op_name; for a fusion without one, its fused
    computation's (root first). An instruction still under no scope (XLA's
    layout copies and broadcasts carry none, or a parameter's name) takes
    the path of the first instruction that uses its result, else of its
    first operand, through up to three such links."""
    comps = _instructions(hlo_proto)
    name = {}
    for instrs, _ in comps.values():
        for ins in instrs:
            n = ins["op_name"]
            if not n and ins["op"] == "fusion" and ins["called"]:
                body, root = comps.get(ins["called"][0], ([], None))
                n = next((i["op_name"] for i in sorted(
                    body, key=lambda i: i["id"] != root) if i["op_name"]),
                    "")
            name[ins["id"]] = n
    for instrs, _ in comps.values():
        users = {}
        for ins in instrs:
            for o in ins["operands"]:
                users.setdefault(o, []).append(ins["id"])
        for _ in range(3):
            for ins in instrs:
                if top_scope(name[ins["id"]]) is None:
                    near = users.get(ins["id"], []) + ins["operands"]
                    name[ins["id"]] = next(
                        (name[i] for i in near
                         if top_scope(name.get(i, "")) is not None),
                        name[ins["id"]])
    return {ins["name"]: name[ins["id"]] for instrs, _ in comps.values()
            for ins in instrs if name[ins["id"]]}


# -- reading ----------------------------------------------------------------

def from_file(path: str) -> Program:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    pd = ProfileData.from_serialized_xspace(bytes(buf))
    spans = []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith("repro.")]
    return Program(trace=trace.from_profile(pd),
                   paths=op_paths(_planes(buf)), spans=spans)


def op_paths(planes) -> Dict[str, Dict[str, str]]:
    """Per device plane, each op event's name -> its scope path ("" when
    it has none); see the module docstring for the sources."""
    hlo = {}
    meta = planes.get("/host:metadata", {"events": {}})
    for name, stats in meta["events"].items():
        if stats.get("Hlo Proto") is not None:
            hlo[re.sub(r"\(\d+\)$", "", name)] = stats["Hlo Proto"]
    from_hlo = None
    out = {}
    for pname, plane in planes.items():
        if not pname.startswith("/device:TPU:"):
            continue
        paths = {}
        for ev_name, stats in plane["events"].items():
            tf_op = stats.get("tf_op")
            path = "" if tf_op is None else _str(tf_op).rstrip(":")
            if top_scope(path) is None:
                if from_hlo is None:
                    from_hlo = hlo_op_names(hlo[STEP_MODULE]) \
                        if STEP_MODULE in hlo else {}
                path = from_hlo.get(trace.instr_name(ev_name), path)
            paths[ev_name] = path
        out[pname] = paths
    return out


def load(trace_dir: str) -> Program:
    """The newest .xplane.pb under trace_dir."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_file(paths[-1])


_loaded: Dict[str, Program] = {}


def of(reading) -> Optional[Program]:
    """The program's names in a traced run's trace, None in an untraced
    run. `reading.program` when set; else read once from the directory
    bench/run.py traces into (.bench_trace/<workload>, read before the
    runner deletes it)."""
    if reading.trace is None:
        return None
    prog = getattr(reading, "program", None)
    if prog is not None:
        return prog
    d = os.path.join(harness.ROOT, ".bench_trace", reading.cell.name)
    if d not in _loaded:
        try:
            _loaded[d] = load(d)
        except (OSError, ValueError):
            return None
    return _loaded[d]


# -- reductions -------------------------------------------------------------

def top_scope(path: str) -> Optional[str]:
    """'jit(train_step)/transpose(jvp(model))/model.ffn/dot_general' ->
    'model'; None when no component names a top-level scope."""
    for part in path.split("/"):
        while (m := re.match(r"^[\w.\-]+\((.*)\)$", part)):
            part = m.group(1)
        if part in SCOPES:
            return part
        if part.startswith("model."):
            return "model"
    return None


def steps(p: Program) -> int:
    return len(trace.module_events(p.trace, STEP_MODULE))


def step_ops(p: Program):
    """(instruction, scope path, seconds) of every op of the train step's
    traced runs, containers left out."""
    out = []
    for plane, evs in p.trace.ops.items():
        runs = sorted((s, e) for n, s, e in p.trace.modules.get(plane, [])
                      if STEP_MODULE in n)
        starts = [s for s, _ in runs]
        paths = p.paths.get(plane, {})
        for name, s, e in evs:
            instr = trace.instr_name(name)
            if trace.base_name(instr) in trace.CONTAINERS:
                continue
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or s >= runs[k][1]:
                continue
            out.append((instr, paths.get(name, ""), (e - s) / 1e9))
    return out


def scope_breakdown(p: Program) -> Dict[str, float]:
    """Device seconds of the train step's ops by top-level scope; "-" for
    ops under none."""
    out = dict.fromkeys(SCOPES + ("-",), 0.0)
    for _, path, sec in step_ops(p):
        out[top_scope(path) or "-"] += sec
    return out


def kernel_free_seconds(p: Program, scope: str) -> float:
    """Device seconds of the ops under `scope` whose instruction is not a
    Pallas kernel (hbfp_*)."""
    return sum(sec for instr, path, sec in step_ops(p)
               if top_scope(path) == scope and not instr.startswith("hbfp_"))


def span_seconds(p: Program, name: str) -> List[float]:
    """Durations of the repro spans named `name` inside the window."""
    lo, hi = p.trace.window()
    return [(e - s) / 1e9 for n, s, e in p.spans
            if n == name and lo <= s and e <= hi]


def median_span_ms(p: Program, name: str) -> Optional[float]:
    d = span_seconds(p, name)
    return 1e3 * statistics.median(d) if d else None


def idle_gaps(p: Program, n: int = 10):
    """trace_reduce.idle_gaps with the program's spans among the labels:
    each gap is named by the innermost bench.* or repro.* span at its
    middle."""
    both = dataclasses.replace(p.trace, spans=p.trace.spans + p.spans)
    return trace.idle_gaps(both, n)
