"""Model FLOP utilisation of the training step: the operations the
forward and backward passes require (bench/work.py, from shapes; remat
not counted) times the traced steps, over the device time from the first
traced step's start to the last one's end, times the peak of the
arithmetic the cell declares (int8 for HBFP cells, bf16 for bf16 ones)."""
import trace_reduce as trace
import work


def read(r):
    if r.trace is None:
        return None
    ev = trace.module_events(r.trace, "jit_train_step")
    if not ev:
        return None
    tr = r.cell.traffic
    ops = work.train_step_ops(r.cell.config, tr["batch"], tr["seq"])
    span = (ev[-1][1] - ev[0][0]) / 1e9
    return 100.0 * ops * len(ev) / (span * r.peak[tr["arith"]])
