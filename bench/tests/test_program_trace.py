"""The program's spans and scopes in a trace, on small synthetic traces
with known answers, and on a trace recorded on the CPU."""

import pytest

import harness
import program_trace as P
import trace_reduce as T
import update_work
from run import Reading

MS = 1_000_000
NARROW = "jit(train_step)/hbfp.narrow/convert_element_type"
ATTN = "jit(train_step)/jvp(model)/model.attn/pallas_call"
GLUE = "jit(train_step)/jvp(model)/model.attn/mul"
WGRAD = "jit(train_step)/transpose(jvp(model))/model.ffn/pallas_call"
REMAT = "checkpoint/rematted_computation/model.ffn/mul"
ADAMW = "jit(train_step)/optim.adamw/mul"
WIDEN = "jit(train_step)/hbfp.widen/add"

# (event, start ms, end ms, scope path)
OPS = [("%while.3 = (s32[]) while(...)", 0, 50, ""),
       ("%fusion.1 = bf16[8,8] fusion(...)", 0, 5, NARROW),
       ("%hbfp_matmul_fwd.12 = f32[8,8] custom-call(...)", 5, 15, ATTN),
       ("%fusion.2 = f32[8,8] fusion(...)", 15, 20, GLUE),
       ("%hbfp_matmul_wgrad.2 = f32[8,8] custom-call(...)", 20, 35, WGRAD),
       ("%fusion.3 = f32[8,8] fusion(...)", 35, 40, REMAT),
       ("%fusion.4 = f32[8,8] fusion(...)", 40, 46, ADAMW),
       ("%fusion.5 = f32[8,8] fusion(...)", 46, 50, WIDEN),
       ("%copy.9 = f32[8,8] copy(...)", 50, 51, ""),
       ("%fusion.1 = bf16[8,8] fusion(...)", 60, 65, NARROW),
       ("%hbfp_flash_fwd.1 = bf16[4,8] custom-call(...)", 65, 70, ATTN),
       ("%fusion.2 = f32[8,8] fusion(...)", 70, 75, GLUE),
       ("%fusion.4 = f32[8,8] fusion(...)", 80, 86, ADAMW),
       ("%fusion.5 = f32[8,8] fusion(...)", 86, 90, WIDEN),
       ("%fusion.7 = u32[2] fusion(...)", 96, 97, "jit(train_batch)/add")]
MODULES = [("jit_train_step(1)", 0, 52), ("jit_train_step(1)", 60, 95),
           ("jit_train_batch(2)", 96, 97)]
BENCH_SPANS = [("bench.window", -5, 100), ("bench.sync", 90, 96),
               ("bench.step_dispatch", 52, 59)]
REPRO_SPANS = [("repro.train/step", -4, -3), ("repro.train/data", -4, -3.5),
               ("repro.train/step", 53, 58), ("repro.train/data", 54, 57),
               ("repro.train/dispatch", 57, 58),
               ("repro.train/step", 96, 99)]

# a one-layer model whose update moves 7824 bytes: matrices 4x4 (q, k,
# v, o), 4x8 (gate, in), 8x4 (out), 4x16 (head, embedding) = 288
# parameters at 26 bytes; vectors (2 norms + final) 12 at 28 bytes
CFG = {"hidden_size": 4, "intermediate_size": 8, "num_attention_heads": 1,
       "num_key_value_heads": 1, "head_dim": 4, "num_hidden_layers": 1,
       "vocab_size": 16}
PEAK = {"int8": 393e12, "bf16": 197e12, "hbm_bytes_per_s": 1e6}
TRAFFIC = {"batch": 1, "seq": 4, "arith": "int8"}


def base_trace(spans=BENCH_SPANS):
    ops = [(n, s * MS, e * MS) for n, s, e, _ in OPS]
    return T.Trace(ops={"/device:TPU:0": ops},
                   modules={"/device:TPU:0": [(n, s * MS, e * MS)
                                              for n, s, e in MODULES]},
                   spans=[(n, s * MS, e * MS) for n, s, e in spans])


def program():
    return P.Program(trace=base_trace(),
                     paths={"/device:TPU:0": {n: p for n, _, _, p in OPS}},
                     spans=[(n, s * MS, e * MS) for n, s, e in REPRO_SPANS])


class Cell:
    name = "synthetic"
    config = CFG
    traffic = TRAFFIC


def reading(prog):
    r = Reading(Cell(), out={}, setup_s=0.0, peak=PEAK,
                trace=base_trace() if prog is None else prog.trace)
    r.program = prog
    return r


def read(metric, r):
    return harness.load_reader(metric)(r)


def test_top_scope():
    assert P.top_scope(WGRAD) == "model"
    assert P.top_scope(REMAT) == "model"
    assert P.top_scope(NARROW) == "hbfp.narrow"
    assert P.top_scope("jit(train_step)/transpose(jvp())/mul") is None
    assert P.top_scope("") is None


def test_scope_breakdown_keeps_to_the_step_and_leaves_out_loops():
    b = P.scope_breakdown(program())
    assert b == pytest.approx({"hbfp.narrow": 0.010, "model": 0.045,
                               "optim.adamw": 0.012, "hbfp.widen": 0.008,
                               "-": 0.001})
    assert P.steps(program()) == 2


def test_update_bytes_of_the_synthetic_model():
    assert update_work.trainable_params(CFG) == (288, 12)
    assert update_work.weight_update_bytes(CFG) == 7824


def test_new_readers_give_known_values():
    r = reading(program())
    # 7824 B at 1e6 B/s = 7.824 ms a step; 30 ms of update ops in 2 steps
    assert read("weight_update_roofline", r) == pytest.approx(
        100 * 7.824e-3 * 2 / 0.030)
    # model ops that are not hbfp_* kernels: 5 + 5 + 5 ms in 2 steps
    assert read("model_glue_ms.train", r) == pytest.approx(7.5)
    # repro.train/step spans of 1, 5 and 3 ms
    assert read("host_step_ms.train", r) == pytest.approx(3.0)


def test_new_readers_read_nothing_without_the_programs_names():
    r = reading(P.Program(trace=base_trace(), paths={}, spans=[]))
    for m in ("weight_update_roofline", "model_glue_ms.train",
              "host_step_ms.train"):
        assert read(m, r) is None
    untraced = reading(None)
    untraced.trace = None
    for m in ("weight_update_roofline", "model_glue_ms.train",
              "host_step_ms.train"):
        assert read(m, untraced) is None


def test_existing_readers_and_reductions_are_unchanged():
    plain, named = reading(None), reading(program())
    for m in ("mfu.train", "hbfp_matmul_roofline", "hbfp_flash_roofline",
              "idle_share.train"):
        a, b = read(m, plain), read(m, named)
        assert a is not None and a == b
    assert T.idle_gaps(plain.trace) == T.idle_gaps(named.trace)
    assert T.top_ops(plain.trace) == T.top_ops(named.trace)


def test_idle_gap_inside_a_trainer_span_takes_its_label():
    gaps = dict((round(s * 1e3, 6), lab) for lab, s in P.idle_gaps(program()))
    # 51..60: the host was fetching the batch (inside train/step)
    assert gaps[9.0] == "repro.train/data"
    # the same gap read by the benchmark's own spans alone
    assert dict((round(s * 1e3, 6), lab) for lab, s in
                T.idle_gaps(program().trace))[9.0] == "bench.step_dispatch"


# -- the protobuf reader, on hand-encoded messages ---------------------------

def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def msg(*fields):
    return b"".join(fields)


def instruction(name, op, iid, op_name="", operands=(), called=()):
    parts = [field(1, name), field(2, op), field(35, iid)]
    if op_name:
        parts.append(field(7, msg(field(2, op_name))))
    if operands:
        parts.append(field(36, b"".join(_varint(o) for o in operands)))
    if called:
        parts.append(field(38, b"".join(_varint(c) for c in called)))
    return msg(*parts)


def hlo_proto():
    fused = msg(field(1, "fused_computation"),
                field(2, instruction("mul.1", "multiply", 11,
                                     op_name=WIDEN)),
                field(5, 2), field(6, 11))
    entry = msg(field(1, "main"),
                field(2, instruction("p0", "parameter", 1,
                                     op_name="state.params['w']")),
                field(2, instruction("copy.1", "copy", 2, operands=[1])),
                field(2, instruction("fusion.1", "fusion", 3, op_name=NARROW,
                                     operands=[2])),
                field(2, instruction("fusion.2", "fusion", 4, operands=[3],
                                     called=[2])),
                field(2, instruction("copy.2", "copy", 5, operands=[4])),
                field(2, instruction("tuple.1", "tuple", 6, operands=[5])),
                field(5, 1), field(6, 6))
    return msg(field(1, msg(field(1, "jit_train_step"),
                            field(3, fused), field(3, entry))))


def test_hlo_names_fusions_and_the_copies_xla_adds():
    names = P.hlo_op_names(hlo_proto())
    assert names["fusion.1"] == NARROW
    assert names["fusion.2"] == WIDEN          # its fused computation's root
    assert names["copy.1"] == NARROW           # the op that uses it
    assert names["copy.2"] == WIDEN            # no named user: its operand


def xspace():
    def stat_meta(sid, name):
        return field(5, msg(field(1, sid), field(2, msg(field(1, sid),
                                                        field(2, name)))))

    def event_meta(eid, name, stats):
        return field(4, msg(field(1, eid), field(2, msg(
            field(1, eid), field(2, name),
            *[field(5, msg(field(1, sid), field(5, v)))
              for sid, v in stats]))))

    device = msg(field(2, "/device:TPU:0"), stat_meta(7, "tf_op"),
                 event_meta(1, "%fusion.1 = bf16[8] fusion(...)",
                            [(7, NARROW + ":")]),
                 event_meta(2, "%copy.2 = f32[8] copy(...)", []),
                 event_meta(3, "%copy.1 = f32[8] copy(...)",
                            [(7, "state.params['w']:")]))
    meta = msg(field(2, "/host:metadata"), field(5, msg(
        field(1, 3), field(2, msg(field(1, 3), field(2, "Hlo Proto"))))),
        field(4, msg(field(1, 9), field(2, msg(
            field(1, 9), field(2, "jit_train_step(123)"),
            field(5, msg(field(1, 3), field(6, hlo_proto()))))))))
    return msg(field(1, device), field(1, meta))


def test_op_paths_from_tf_op_else_from_the_modules_hlo():
    planes = P._planes(memoryview(xspace()))
    paths = P.op_paths(planes)["/device:TPU:0"]
    assert paths == {"%fusion.1 = bf16[8] fusion(...)": NARROW,
                     "%copy.2 = f32[8] copy(...)": WIDEN,
                     "%copy.1 = f32[8] copy(...)": NARROW}


def test_a_recorded_cpu_trace_has_the_trainers_spans(tmp_path):
    import run
    import tiny
    import train_cell
    cell = tiny.cell(tiny.TRAIN)
    train_cell.run(cell, 7, 1.0, run.Tracer(str(tmp_path)))
    p = P.load(str(tmp_path))
    names = {n for n, _, _ in p.spans}
    assert {"repro.train/step", "repro.train/data",
            "repro.train/dispatch"} <= names
    assert len(P.span_seconds(p, "repro.train/step")) \
        == tiny.TRAIN["trace_steps"]
    r = Reading(cell, out={}, setup_s=0.0, peak=PEAK, trace=p.trace)
    r.program = p
    assert read("host_step_ms.train", r) > 0
    # the CPU has no device plane: nothing to read there, and no error
    assert read("weight_update_roofline", r) is None
    assert read("model_glue_ms.train", r) is None
