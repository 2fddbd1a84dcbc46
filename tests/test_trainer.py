"""Fault tolerance: atomic checkpoints, preemption + bit-exact resume,
packed (BFP-compressed) checkpoints, retention."""
import os

import pytest as _pytest

# multi-run training integration tests — excluded from the fast CI lane
pytestmark = _pytest.mark.slow

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.configs import get_arch
from repro.core import HBFP8_16
from repro.data import SyntheticLM
from repro.models import init_params
from repro.optim import make_schedule
from repro.train import init_train_state, make_train_step
from repro.train.trainer import Trainer


@pytest.fixture(scope="module")
def setup():
    arch = get_arch("xlstm-350m").smoke()
    pipe = SyntheticLM(arch.vocab_size, 17, 4, seed=7)
    sched = make_schedule("constant", base_lr=1e-3, warmup_steps=2,
                          total_steps=30)
    step = jax.jit(make_train_step(arch, HBFP8_16, sched))
    state = init_train_state(jax.random.key(0), arch, init_params)
    return arch, pipe, step, state


def test_checkpoint_roundtrip(tmp_path, setup):
    _, _, _, state = setup
    save_checkpoint(str(tmp_path), 3, state)
    restored, meta = load_checkpoint(str(tmp_path), state)
    assert meta["step"] == 3
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_checkpoint_compresses(tmp_path, setup):
    _, _, _, state = setup
    d1, d2 = str(tmp_path / "plain"), str(tmp_path / "packed")
    save_checkpoint(d1, 1, state.params)
    save_checkpoint(d2, 1, state.params, hbfp=HBFP8_16, packed=True)
    size = lambda d: sum(os.path.getsize(os.path.join(r, f))
                         for r, _, fs in os.walk(d) for f in fs)
    s1, s2 = size(d1), size(d2)
    assert s2 < s1 * 0.55, (s1, s2)  # ~2x+ smaller (paper's compact models)
    restored, _ = load_checkpoint(d2, state.params)
    # packed leaves reproduce the wide-BFP values (16-bit wide mantissa)
    from repro.core import widen_params
    wide = widen_params(jax.tree.map(lambda x: jnp.asarray(x), restored),
                        HBFP8_16)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(wide)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_preemption_resume_bit_exact(tmp_path, setup):
    arch, pipe, step, state = setup
    d = str(tmp_path / "ckpt")
    tr = Trainer(train_step=step, init_state=state, data_fn=pipe.batch,
                 ckpt_dir=d, ckpt_every=10, hbfp=HBFP8_16)
    with pytest.raises(RuntimeError, match="simulated preemption"):
        tr.run(30, fail_at_step=17, log_every=0)
    assert latest_step(d) == 10

    tr2 = Trainer(train_step=step, init_state=state, data_fn=pipe.batch,
                  ckpt_dir=d, ckpt_every=10, hbfp=HBFP8_16)
    assert tr2.start_step == 10
    s_resumed, _ = tr2.run(30, log_every=0)

    tr3 = Trainer(train_step=step, init_state=state, data_fn=pipe.batch,
                  ckpt_dir=None)
    s_straight, _ = tr3.run(30, log_every=0)
    for a, b in zip(jax.tree.leaves(s_resumed.params),
                    jax.tree.leaves(s_straight.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_retention_and_atomicity(tmp_path, setup):
    _, _, _, state = setup
    d = str(tmp_path / "r")
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(d, s, {"x": jnp.ones(3) * s}, keep=2)
    steps = sorted(int(p[5:]) for p in os.listdir(d)
                   if p.startswith("step_") and not p.endswith(".tmp"))
    assert steps == [4, 5]
    assert not any(p.endswith(".tmp") for p in os.listdir(d))


def test_background_checkpoint(tmp_path, setup):
    _, _, _, state = setup
    d = str(tmp_path / "bg")
    t = save_checkpoint(d, 7, {"x": jnp.arange(10)}, background=True)
    t.join()
    restored, meta = load_checkpoint(d, {"x": jnp.zeros(10, jnp.int32)})
    assert meta["step"] == 7
    np.testing.assert_array_equal(np.asarray(restored["x"]), np.arange(10))


def test_trainer_timing_deterministic_with_manual_clock(setup):
    """Satellite (ISSUE 8): the loop reads time only from the recorder's
    injected clock, so a ManualClock makes every elapsed figure — span
    durations, progress events, the printed line — exactly assertable."""
    from repro.obs import ManualClock, MemorySink, Recorder
    _, pipe, step, state = setup
    clk = ManualClock()
    ms = MemorySink()
    synced = []
    rec = Recorder([ms], clock=clk, sync=synced.append)

    def data(i):          # the pipeline "takes" 0.25s per step
        clk.advance(0.25)
        return pipe.batch(i)

    def stepped(s, b, k):  # the device "takes" 0.1s per step
        clk.advance(0.1)
        return step(s, b, k)

    lines = []
    tr = Trainer(train_step=stepped, init_state=state, data_fn=data,
                 ckpt_dir=None, recorder=rec)
    tr.run(6, log_every=5, log_fn=lines.append)

    spans = [e for e in ms.of_kind("span") if e.data["name"] == "train/step"]
    assert len(spans) == 6
    # the step span covers the batch (0.25s) and the dispatch (0.1s)
    assert all(e.data["dur_us"] == pytest.approx(0.35e6) for e in spans)
    # sync (block_until_ready stand-in) only on log-cadence steps
    assert [e.data["synced"] for e in spans] == [True, False, False,
                                                False, False, True]
    assert len(synced) == 2
    prog = ms.of_kind("train/progress")
    assert [e.step for e in prog] == [0, 5]
    assert prog[0].data["elapsed_s"] == pytest.approx(0.35)
    assert prog[1].data["elapsed_s"] == pytest.approx(6 * 0.35)
    assert lines[0].startswith("step      0 ") and "(0.3s)" in lines[0]
    assert "(2.1s)" in lines[1]


def test_trainer_step_span_nests_data_and_dispatch(setup):
    """train/step is the parent of the loop body: train/data (the batch
    and the key fold) then train/dispatch (the train_step call)."""
    from repro.obs import ManualClock, MemorySink, Recorder
    _, pipe, step, state = setup
    clk = ManualClock()
    ms = MemorySink()
    log = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    rec = Recorder([ms], clock=clk, sync=lambda x: x, annotate=Ann)

    def data(i):
        clk.advance(0.25)
        return pipe.batch(i)

    def stepped(s, b, k):
        clk.advance(0.1)
        return step(s, b, k)

    Trainer(train_step=stepped, init_state=state, data_fn=data,
            ckpt_dir=None, recorder=rec).run(2, log_every=0)
    spans = [(e.step, e.data["name"], e.data.get("parent"), e.data["depth"],
              round(e.data["dur_us"])) for e in ms.of_kind("span")]
    assert spans == [
        (i, name, parent, depth, dur) for i in range(2)
        for name, parent, depth, dur in (
            ("train/data", "train/step", 1, 250000),
            ("train/dispatch", "train/step", 1, 100000),
            ("train/step", None, 0, 350000))]
    assert log == 2 * [("enter", "repro.train/step"),
                       ("enter", "repro.train/data"),
                       ("exit", "repro.train/data"),
                       ("enter", "repro.train/dispatch"),
                       ("exit", "repro.train/dispatch"),
                       ("exit", "repro.train/step")]


def test_trainer_default_recorder_annotates_without_sinks(setup):
    """No recorder passed: spans go to the profiler's clock, no events."""
    _, pipe, step, state = setup
    tr = Trainer(train_step=step, init_state=state, data_fn=pipe.batch,
                 ckpt_dir=None)
    assert not tr.recorder.enabled
    assert tr.recorder.annotate_fn is jax.profiler.TraceAnnotation
    assert tr.recorder.sync_fn is None


def test_trainer_checkpoint_events_flow_to_recorder(tmp_path, setup):
    from repro.obs import MemorySink, Recorder
    _, pipe, step, state = setup
    d = str(tmp_path / "obs_ckpt")
    ms = MemorySink()
    tr = Trainer(train_step=step, init_state=state, data_fn=pipe.batch,
                 ckpt_dir=d, ckpt_every=2, hbfp=HBFP8_16,
                 recorder=Recorder([ms]))
    tr.run(3, log_every=0)
    saves = ms.of_kind("ckpt/save")
    assert [e.step for e in saves] == [2, 3]
    assert all(e.data["bytes"] > 0 and e.data["dur_s"] >= 0 for e in saves)
    # a resuming trainer emits the restore
    ms2 = MemorySink()
    tr2 = Trainer(train_step=step, init_state=state, data_fn=pipe.batch,
                  ckpt_dir=d, ckpt_every=2, hbfp=HBFP8_16,
                  recorder=Recorder([ms2]))
    assert tr2.start_step == 3
    loads = ms2.of_kind("ckpt/load")
    assert [e.step for e in loads] == [3]
    assert loads[0].data["bytes"] == saves[-1].data["bytes"]


def test_elastic_restore_structure_only(tmp_path, setup):
    """Restore works from ShapeDtypeStructs (any-mesh restore path)."""
    _, _, _, state = setup
    d = str(tmp_path / "el")
    save_checkpoint(d, 2, state.params)
    like = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state.params)
    restored, _ = load_checkpoint(d, like)
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
