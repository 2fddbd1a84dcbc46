"""Median host milliseconds of the Trainer's step in the traced window:
the `repro.train/step` spans, the batch, the key and the dispatch of one
step (bench/program_trace.py)."""
import program_trace


def read(r):
    p = program_trace.of(r)
    if p is None:
        return None
    return program_trace.median_span_ms(p, "repro.train/step")
