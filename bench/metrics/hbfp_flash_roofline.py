"""Roofline share of the BFP flash-attention kernels: the least time of
causal attention forward and backward (bench/work.py) in the traced
steps, over the summed device time of the hbfp_flash_* kernels."""
import trace_reduce as trace
import work


def read(r):
    if r.trace is None:
        return None
    t = trace.op_seconds(r.trace, "hbfp_flash_")
    steps = len(trace.module_events(r.trace, "jit_train_step"))
    if t <= 0 or not steps:
        return None
    tr = r.cell.traffic
    least = work.attention_least_seconds(
        r.cell.config, tr["batch"], tr["seq"], tr["arith"],
        r.peak[tr["arith"]], r.peak["hbm_bytes_per_s"])
    return 100.0 * least * steps / t
