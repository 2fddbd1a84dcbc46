"""Roofline share of the weight update: its least HBM time (every
trainable parameter's gradient read, its f32 master and AdamW moments
read and written; bench/update_work.py) in the traced steps, over the
summed device time of the ops under the update's scopes, hbfp.narrow,
optim.adamw and hbfp.widen (bench/program_trace.py)."""
import program_trace
import update_work


def read(r):
    p = program_trace.of(r)
    if p is None:
        return None
    by_scope = program_trace.scope_breakdown(p)
    t = sum(by_scope[s] for s in program_trace.UPDATE_SCOPES)
    steps = program_trace.steps(p)
    if t <= 0 or not steps:
        return None
    least = (update_work.weight_update_bytes(r.cell.config)
             / r.peak["hbm_bytes_per_s"])
    return 100.0 * least * steps / t
