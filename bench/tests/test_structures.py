"""A model of another structure, or a runner of another traffic kind,
arrives as new files alone: a copy of the benchmark with only files
added runs a tiny training cell of a structure the dense decoder's check
refuses, and names it by its configuration; a structure or a kind with
no file of its own stops the run before it starts."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

import harness
import tiny
from test_run_cpu import no_result

SRC = os.path.join(harness.ROOT, "src")

# zero-centred RMSNorm (Gemma's: the norm multiplies by 1 + scale, scales
# start at 0) on the dense decoder: the program has the option, the dense
# decoder's reference does not
TOY = '''"""Dense decoder with zero-centred RMSNorm scales (the norm multiplies
by 1 + scale; scales start at 0), on the dense reference."""
import dataclasses

import jax
import jax.numpy as jnp

from structures import dense_decoder as dense
from structures.dense_decoder import (  # noqa: F401
    attention_fwd_ops, attention_shape, trainable_params, weight_gemms)


def _map_norms(f, params):
    return jax.tree_util.tree_map_with_path(
        lambda p, a: f(a) if p[-1].key.endswith("norm_scale") else a,
        params)


def program_arch(cfg):
    return dataclasses.replace(dense.program_arch(cfg),
                               zero_centered_norm=True)


def make_params(cfg, key, dtype=jnp.bfloat16):
    return _map_norms(jnp.zeros_like, dense.make_params(cfg, key, dtype))


def logits(cfg, params, tokens):
    return dense.logits(cfg, _map_norms(lambda a: a + 1.0, params), tokens)


def loss(cfg, params, batch):
    return dense.loss(cfg, _map_norms(lambda a: a + 1.0, params), batch)
'''

DRIVE = '''import json, os, sys
sys.path.insert(0, os.path.join(os.getcwd(), "bench"))
import harness, program, run, structures, update_work, work
from structures import dense_decoder
cell = harness.find_cell("train.tiny-zc")
runner = harness.load_runner(cell.traffic["kind"])
arch = program.program_arch(cell.config)
try:
    dense_decoder.check(arch, "toy")
    refused = False
except harness.SetupError:
    refused = True
out = runner.run(cell, %d, 1.0)
checks = runner.finish(cell, %d, out)
reading = run.Reading(cell, out, 1.0, None)
tr = cell.traffic
print(json.dumps({
    "structure": structures.of(cell.config).__name__, "refused": refused,
    "zero_centered": arch.zero_centered_norm, "checks": checks,
    "metrics": run.read_metrics(cell.end_to_end, reading),
    "ops": work.train_step_ops(cell.config, tr["batch"], tr["seq"]),
    "update_bytes": update_work.weight_update_bytes(cell.config)}))
'''


def copy_benchmark(root):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))


def digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def add_cell(root, cfg, traffic, structure_file=None):
    """A config, a traffic mix, limits and BENCHMARK.json entries for
    the cell train.tiny-zc, as new files and new entries only."""
    bench = root / "bench"
    if structure_file is not None:
        (bench / "structures" / "zero_centered_decoder.py").write_text(
            structure_file)
    (bench / "configs" / "tiny-zc.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny.b2s64.json").write_text(json.dumps(traffic))
    (bench / "limits" / "train.tiny-zc.json").write_text(json.dumps(
        dict(tiny.LIMITS, control={"policy": "4; backend=pallas"})))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-zc", "source": "a test",
                            "file": "bench/configs/tiny-zc.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "train.tiny-zc", "config": "tiny-zc",
                              "traffic": "tiny.b2s64", "chips": 1,
                              "why": "a test"})
    e2e = next(m for m in spec["end_to_end"]
               if m["name"] == "train_tokens_per_s")
    e2e["workloads"].append("train.tiny-zc")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def python(root, args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    return subprocess.run([sys.executable] + args, cwd=root, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_a_second_structure_needs_only_new_files(tmp_path):
    copy_benchmark(tmp_path)
    before = digests(tmp_path / "bench")
    add_cell(tmp_path, dict(tiny.TINY_CFG, structure="zero_centered_decoder"),
             tiny.TRAIN, TOY)
    after = digests(tmp_path / "bench")
    assert {k: after[k] for k in before} == before
    seed = 2**36 + 5
    r = python(tmp_path, ["-c", DRIVE % (seed, seed)], timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["structure"] == "structures.zero_centered_decoder"
    assert got["refused"] and got["zero_centered"]
    assert got["checks"] and all(c["ok"] for c in got["checks"]), got
    assert set(got["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert got["metrics"]["train_tokens_per_s"]["value"] > 0
    import update_work
    import work
    tr = tiny.TRAIN
    assert got["ops"] == work.train_step_ops(tiny.TINY_CFG, tr["batch"],
                                             tr["seq"])
    assert got["update_bytes"] == update_work.weight_update_bytes(
        tiny.TINY_CFG)


@pytest.mark.parametrize("cfg,traffic,says", [
    (dict(tiny.TINY_CFG, structure="no_such_structure"), tiny.TRAIN,
     "bench/structures/no_such_structure.py"),
    (tiny.TINY_CFG, dict(tiny.TRAIN, kind="replay"), "bench/replay_cell.py"),
], ids=["structure", "kind"])
def test_a_structure_or_kind_with_no_file_gives_no_result(tmp_path, cfg,
                                                          traffic, says):
    copy_benchmark(tmp_path)
    add_cell(tmp_path, cfg, traffic)
    r = python(tmp_path, ["bench/run.py", "--workload", "train.tiny-zc",
                          "--seed", str(2**33), "--seconds", "1",
                          "--trace", "0"], timeout=300)
    assert r.returncode == 2 and no_result(r.stdout)
    assert says in r.stderr
