"""Share of the traced training window with no operation on the device."""
import trace_reduce as trace


def read(r):
    if r.trace is None:
        return None
    lo, hi = r.trace.window()
    return 100.0 * (1.0 - trace.busy_ns(r.trace, lo, hi) / (hi - lo))
