"""Device milliseconds a traced step spends in the model's XLA ops: the
ops under the `model` scope, forward and backward, that are not a BFP
Pallas kernel (hbfp_*): norms, RoPE, SwiGLU, cross-entropy, the
embedding and its gradient (bench/program_trace.py)."""
import program_trace


def read(r):
    p = program_trace.of(r)
    if p is None:
        return None
    steps = program_trace.steps(p)
    if not steps or program_trace.scope_breakdown(p)["model"] <= 0:
        return None
    return 1e3 * program_trace.kernel_free_seconds(p, "model") / steps
