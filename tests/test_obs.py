"""Unified run-log & tracing plane (DESIGN.md §12): event schema and
injected clocks, span nesting/sync semantics, JSONL rotation, Prometheus
exposition, the shared benchmark timer, the live run-log follower, and
bit-identity of the instrumented train step with sinks disabled."""
import json
import os

import pytest

from repro.obs import (DEFAULT_BUCKETS, Event, JSONLSink, KINDS, ManualClock,
                       MemorySink, MetricsRegistry, NULL_RECORDER,
                       PrometheusTextfileSink, Recorder, SCHEMA_VERSION,
                       SystemClock, time_fn)


# ---------------------------------------------------------------------------
# events + recorder
# ---------------------------------------------------------------------------

def test_event_json_shape_and_version():
    ev = Event(kind="train/progress", t=12.5, step=3, data={"loss": 1.0})
    d = ev.to_json()
    assert d == {"v": SCHEMA_VERSION, "kind": "train/progress", "t": 12.5,
                 "step": 3, "data": {"loss": 1.0}}
    assert "step" not in Event(kind="span", t=0.0).to_json()


def test_recorder_stamps_injected_clock():
    clk = ManualClock(t0=100.0)
    ms = MemorySink()
    rec = Recorder([ms], clock=clk)
    rec.emit("ckpt/save", step=1, bytes=10)
    clk.advance(2.5)
    rec.emit("ckpt/load", step=1)
    assert [e.t for e in ms.events] == [100.0, 102.5]
    assert ms.kinds() == ["ckpt/save", "ckpt/load"]


def test_disabled_recorder_is_noop():
    assert not NULL_RECORDER.enabled
    assert NULL_RECORDER.emit("span", name="x") is None
    with NULL_RECORDER.span("anything") as sp:
        sp.annotate(k=1)  # must not raise, must not record


def test_bad_event_kind_rejected():
    rec = Recorder([MemorySink()])
    with pytest.raises(ValueError, match="bad event kind"):
        rec.emit("Not A Kind")
    with pytest.raises(ValueError, match="bad event kind"):
        rec.emit("a/b/c")


def test_run_id_stamped_into_data():
    ms = MemorySink()
    Recorder([ms], run_id="r7").emit("span", name="x")
    assert ms.events[0].data["run"] == "r7"


def test_registered_kinds_match_schema_regex():
    import re
    pat = re.compile(r"^[a-z0-9_.]+(/[a-z0-9_.]+)?$")
    assert all(pat.match(k) for k in KINDS)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_duration_nesting_and_sync_flag():
    clk = ManualClock()
    ms = MemorySink()
    synced = []
    rec = Recorder([ms], clock=clk, sync=synced.append)
    with rec.span("outer", step=5) as outer:
        clk.advance(1.0)
        with rec.span("inner") as inner:
            clk.advance(0.25)
            inner.sync("device_buf")
        clk.advance(1.0)
        outer.annotate(phase="tail")
    inner_ev, outer_ev = ms.events  # inner closes first
    assert inner_ev.data["name"] == "inner"
    assert inner_ev.data["dur_us"] == pytest.approx(0.25e6)
    assert inner_ev.data["parent"] == "outer"
    assert inner_ev.data["depth"] == 1
    assert inner_ev.data["synced"] is True
    assert synced == ["device_buf"]
    assert outer_ev.data["dur_us"] == pytest.approx(2.25e6)
    assert outer_ev.data["depth"] == 0
    assert "parent" not in outer_ev.data
    assert outer_ev.data["synced"] is False
    assert outer_ev.data["phase"] == "tail"
    assert outer_ev.step == 5


def test_span_records_error_and_still_emits():
    ms = MemorySink()
    rec = Recorder([ms])
    with pytest.raises(RuntimeError):
        with rec.span("doomed"):
            raise RuntimeError("boom")
    assert "RuntimeError('boom')" in ms.events[0].data["error"]


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs enter and exit."""

    def __init__(self, log, name):
        self.log, self.name = log, name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


@pytest.mark.parametrize("with_sinks", [True, False])
def test_annotate_opens_and_closes_nested_spans_in_order(with_sinks):
    log = []
    ms = MemorySink()
    rec = Recorder([ms] if with_sinks else [],
                   annotate=lambda name: _FakeAnnotation(log, name))
    emitted = []
    emit = rec.emit
    rec.emit = lambda *a, **kw: emitted.append(a) or emit(*a, **kw)
    with rec.span("train/step"):
        with rec.span("train/data"):
            pass
        with rec.span("train/dispatch"):
            pass
    assert log == [("enter", "repro.train/step"),
                   ("enter", "repro.train/data"),
                   ("exit", "repro.train/data"),
                   ("enter", "repro.train/dispatch"),
                   ("exit", "repro.train/dispatch"),
                   ("exit", "repro.train/step")]
    if with_sinks:
        assert [e.data["name"] for e in ms.events] == [
            "train/data", "train/dispatch", "train/step"]
    else:
        assert not rec.enabled and emitted == []


def test_annotation_closes_when_the_span_raises():
    log = []
    rec = Recorder(annotate=lambda name: _FakeAnnotation(log, name))
    with pytest.raises(RuntimeError):
        with rec.span("doomed"):
            raise RuntimeError("boom")
    assert log == [("enter", "repro.doomed"), ("exit", "repro.doomed")]


def test_null_recorder_does_not_annotate():
    assert NULL_RECORDER.annotate_fn is None
    assert Recorder().annotate_fn is None


def test_obs_imports_no_jax():
    """obs stays stdlib-only: device sync and profiler annotation are
    injected by the jax-aware layers."""
    import subprocess
    import sys
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, repro.obs; print(sorted(m for m in sys.modules "
         "if m == 'jax' or m.startswith('jax.')))"],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------

def test_jsonl_sink_lines_parse(tmp_path):
    p = str(tmp_path / "run.jsonl")
    rec = Recorder([JSONLSink(p)], clock=ManualClock(t0=1.0))
    rec.emit("train/progress", step=0, loss=2.0)
    rec.emit("ckpt/save", step=0, bytes=5)
    rec.close()
    lines = [json.loads(ln) for ln in open(p)]
    assert [ln["kind"] for ln in lines] == ["train/progress", "ckpt/save"]
    assert lines[0]["data"]["loss"] == 2.0 and lines[0]["t"] == 1.0


def test_jsonl_sink_rotation_keeps_backups(tmp_path):
    p = str(tmp_path / "run.jsonl")
    sink = JSONLSink(p, max_bytes=200, backups=2)
    rec = Recorder([sink], clock=ManualClock())
    for i in range(40):
        rec.emit("train/progress", step=i, loss=float(i))
    rec.close()
    names = sorted(os.listdir(tmp_path))
    assert names == ["run.jsonl", "run.jsonl.1", "run.jsonl.2"]
    # rotation never splits a line: every retained line parses
    for name in names:
        for ln in open(tmp_path / name):
            json.loads(ln)
    # the newest rotated file holds older steps than the live file
    live0 = json.loads(open(p).readline())
    rot0 = json.loads(open(p + ".1").readline())
    assert rot0["step"] < live0["step"]


def test_jsonl_sink_write_mode_truncates(tmp_path):
    p = str(tmp_path / "run.jsonl")
    for _ in range(2):
        s = JSONLSink(p, mode="w")
        s.write(Event(kind="span", t=0.0, data={"name": "x"}))
        s.close()
    assert len(open(p).readlines()) == 1


def test_prometheus_textfile_sink_dumps_every_n(tmp_path):
    p = str(tmp_path / "obs.prom")
    reg = MetricsRegistry()
    c = reg.counter("steps_total", "steps")
    rec = Recorder([PrometheusTextfileSink(p, reg, every=2)])
    c.inc()
    rec.emit("span", name="a")
    assert not os.path.exists(p)          # 1 event < every
    rec.emit("span", name="b")
    assert "steps_total 1" in open(p).read()
    c.inc(4)
    rec.flush()                           # flush forces a dump
    assert "steps_total 5" in open(p).read()
    assert not os.path.exists(p + ".tmp")  # atomic rename discipline


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total")
    c.inc(); c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(7); g.dec(3)
    assert g.value == 4
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 3 and h.sum == pytest.approx(5.55)


def test_registry_idempotent_and_kind_mismatch():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")


def test_labels_route_to_distinct_series():
    reg = MetricsRegistry()
    m = reg.counter("lane_tokens", labelnames=("lane",))
    m.labels(lane="0").inc(5)
    m.labels(lane="1").inc(1)
    assert m.labels(lane="0").value == 5
    with pytest.raises(ValueError, match="labels"):
        m.labels(slot="0")
    with pytest.raises(ValueError, match="use .labels"):
        m.inc()


def test_prometheus_rendering_histogram_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("ttft_seconds", "ttft", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.render_prometheus()
    assert "# TYPE ttft_seconds histogram" in text
    assert 'ttft_seconds_bucket{le="0.1"} 1' in text
    assert 'ttft_seconds_bucket{le="1.0"} 2' in text
    assert 'ttft_seconds_bucket{le="+Inf"} 3' in text
    assert "ttft_seconds_count 3" in text
    d = reg.to_dict()
    assert d["ttft_seconds"]["series"][""]["count"] == 3


# ---------------------------------------------------------------------------
# shared benchmark timer
# ---------------------------------------------------------------------------

def test_time_fn_deterministic_with_manual_clock():
    clk = ManualClock()
    calls = []

    def fn():
        calls.append(1)
        clk.advance(0.001)  # 1 ms per call

    # batch mode: n calls, one trailing sync, amortized mean
    us = time_fn(fn, n=4, warmup=2, clock=clk)
    assert us == pytest.approx(1000.0)
    assert len(calls) == 6  # warmup included
    # sync_each min: per-call timing
    us = time_fn(fn, n=3, warmup=0, reduce="min", sync_each=True, clock=clk)
    assert us == pytest.approx(1000.0)


def test_time_fn_sync_semantics_and_validation():
    clk = ManualClock()
    synced = []

    def sync(x):
        synced.append(x)
        clk.advance(0.002)  # device time visible only through sync

    def fn():
        return "out"

    us = time_fn(fn, n=2, warmup=1, sync=sync, clock=clk)
    # batch mode syncs once after n calls: 2 ms / 2 calls = 1 ms each
    assert us == pytest.approx(1000.0)
    assert synced == ["out"] * 2  # warmup sync + one trailing sync
    with pytest.raises(ValueError, match="reduce"):
        time_fn(fn, reduce="max")
    with pytest.raises(ValueError, match="sync_each"):
        time_fn(fn, reduce="min", sync_each=False)
    with pytest.raises(ValueError, match="n must be"):
        time_fn(fn, n=0)


# ---------------------------------------------------------------------------
# run-log follower
# ---------------------------------------------------------------------------

def test_follow_runlog_renders_and_counts(tmp_path):
    from repro.analysis.report import follow_runlog
    p = str(tmp_path / "run.jsonl")
    rec = Recorder([JSONLSink(p)], clock=ManualClock())
    rec.emit("train/progress", step=0, elapsed_s=1.0, loss=2.5)
    rec.emit("numerics/snapshot", step=0,
             weights={"blocks.0.wq": {"sqnr_db": 21.0, "clip_frac": 0.01,
                                      "sat_tile_frac": 0.2, "ftz_frac": 0.0,
                                      "exp_spread": 3.0}},
             widths={"weights": {"blocks.0.wq": 4}})
    rec.emit("precision/decision", step=0, layer="blocks.0.wq",
             action="widen", **{"from": 4}, to=8, reason="clip>thr",
             sqnr_db=21.0, clip_frac=0.2)
    rec.emit("ckpt/save", step=1, dur_s=0.1, bytes=2 ** 20, path="x")
    rec.emit("span", name="train/step", dur_us=5.0, depth=0, synced=False)
    rec.emit("wildcard/kind", anything=1)  # unknown kinds are tolerated
    rec.close()
    out = []
    counts = follow_runlog(p, out=out.append)
    assert counts == {"train/progress": 1, "numerics/snapshot": 1,
                      "precision/decision": 1, "ckpt/save": 1, "span": 1,
                      "wildcard/kind": 1}
    text = "\n".join(out)
    assert "loss 2.5000" in text
    assert "| blocks.0.wq | 4 | weights | 21.0 |" in text
    assert "[WIDEN] step 0 blocks.0.wq: m4 -> m8 (clip>thr" in text
    assert "[ckpt] saved step 1: 1.00 MiB" in text
    assert "6 events" in text and "1 precision decisions" in text


def test_follow_runlog_skips_torn_lines(tmp_path):
    from repro.analysis.report import follow_runlog
    p = tmp_path / "run.jsonl"
    good = json.dumps({"v": 1, "kind": "ckpt/save", "t": 0.0, "step": 1,
                       "data": {"bytes": 0, "dur_s": 0.0}})
    p.write_text(good + "\n" + '{"v": 1, "kind": "trunc')
    counts = follow_runlog(str(p), out=lambda *_: None)
    assert counts == {"ckpt/save": 1}


# ---------------------------------------------------------------------------
# instrumented step: bit-identity with sinks disabled
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_instrumented_step_bit_identical_without_and_with_recorder():
    """Acceptance (ISSUE 8): all emission is host-side and outside jit, so
    the training computation is bit-identical whether a recorder streams
    the run or observability is off entirely."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.core import HBFPConfig
    from repro.data import SyntheticLM
    from repro.models import init_params
    from repro.numerics import TapConfig
    from repro.optim import make_schedule
    from repro.train import init_train_state, make_step

    arch = get_arch("yi-9b").smoke()
    pipe = SyntheticLM(arch.vocab_size, 17, 4, seed=3)
    lrs = make_schedule("constant", base_lr=2e-3, warmup_steps=2,
                        total_steps=30)
    ms = MemorySink()
    runs = {}
    for name, rec in (("off", None), ("on", Recorder([ms]))):
        fn = make_step(arch, HBFPConfig(8, 16), lrs,
                       tap=TapConfig(cadence=2), recorder=rec)
        s = init_train_state(jax.random.key(0), arch, init_params)
        for i in range(3):
            k = jax.random.fold_in(jax.random.key(1), i)
            s, m = fn(s, pipe.batch(i), k)
        runs[name] = (s, float(m["loss"]))
    (s0, l0), (s1, l1) = runs["off"], runs["on"]
    assert l0 == l1
    for a, b in zip(jax.tree.leaves(s0.params), jax.tree.leaves(s1.params)):
        assert jnp.array_equal(a, b)
    # and the recorder actually observed the run: snapshots at steps 0, 2
    snaps = ms.of_kind("numerics/snapshot")
    assert [e.step for e in snaps] == [0, 2]
    assert all("widths" in e.data for e in snaps)
    assert len(ms.of_kind("train/recompile")) == 2  # plain + telemetry
