"""Training tokens per second: every token of every step completed in the
window, over the window's length on the host clock."""


def read(r):
    return r.out["tokens"] / r.out["window_s"]
