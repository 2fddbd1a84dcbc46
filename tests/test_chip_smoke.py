"""chip_smoke.py: its phases at smoke size on the CPU, its verdict, its
kernel count, and its refusal to run without a TPU."""
import json
import math
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _arch():
    return chip_smoke.smoke_arch()[0].smoke()


def test_smoke_arch_keeps_published_widths():
    arch, cut = chip_smoke.smoke_arch()
    assert (arch.d_model, arch.d_ff, arch.n_heads, arch.n_kv_heads,
            arch.hd) == (4096, 11008, 32, 4, 128)
    assert (arch.n_layers, arch.vocab_size, arch.dtype) == (4, 8000,
                                                            "bfloat16")
    assert "48->4" in cut and "64000->8000" in cut


def test_train_phases_at_smoke_size():
    """sim, pallas (interpreted here: no tpu_custom_call) and fp32 agree
    on the step-0 loss of the same parameters and batch."""
    arch = _arch()
    runs = {name: chip_smoke.train_phase(arch, pol, batch=2, seq=32,
                                         steps=steps)
            for name, pol, steps in (("sim", "8", 2),
                                     ("pallas", "8; backend=pallas", 2),
                                     ("fp32", "fp32", 1))}
    for name, r in runs.items():
        assert len(r["losses"]) == (1 if name == "fp32" else 2)
        assert all(math.isfinite(x) for x in r["losses"])
        assert r["kernel_calls"]["total"] == 0
    step0 = [r["losses"][0] for r in runs.values()]
    assert max(step0) - min(step0) <= chip_smoke.LOSS_TOL


def test_serve_phase_at_smoke_size():
    r = chip_smoke.serve_phase(_arch(), prompt_lens=(8, 16), per_len=2,
                               new_tokens=4, max_batch=2, ctx_len=32)
    assert r["requests"] == 4
    assert r["complete"] == 4
    assert r["first_token_matches"] == 4


def test_kernel_calls_counts_named_kernels():
    hlo = "\n".join([
        '  %hbfp_matmul_fwd.3 = f32[8,128]{1,0} custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call", metadata={}',
        '  ROOT %transpose_jvp_hbfp_flash_fwd__.1 = bf16[1,8,128] '
        'custom-call(%q), custom_call_target="tpu_custom_call"',
        '  %fusion.2 = f32[8] fusion(%x), kind=kLoop, '
        'calls=%hbfp_matmul_dgrad_like',
    ])
    calls = chip_smoke.kernel_calls(hlo)
    assert calls["total"] == 2
    assert calls["hbfp_matmul_fwd"] == 1
    assert calls["hbfp_flash_fwd"] == 1
    assert calls["hbfp_matmul_dgrad"] == 0


def _train(losses):
    return {"losses": losses, "kernel_calls": dict.fromkeys(
        chip_smoke.KERNELS, 1)}


_SERVE_OK = {"requests": 2, "complete": 2, "first_token_matches": 2}


@pytest.mark.parametrize("train,fp32,serve,expect", [
    ({"sim": _train([9.0, 8.5]), "pallas": _train([9.01, 8.4])}, 9.0,
     _SERVE_OK, None),
    ({"sim": _train([9.0, 9.1]), "pallas": _train([9.0, 8.4])}, 9.0,
     _SERVE_OK, "did not fall"),
    ({"sim": _train([9.0, float("nan")]), "pallas": _train([9.0, 8.4])},
     9.0, _SERVE_OK, "non-finite"),
    ({"sim": _train([9.0, 8.5]), "pallas": _train([9.0, 8.4])}, 9.2,
     _SERVE_OK, "differ"),
    ({"sim": _train([9.0, 8.5]), "pallas": _train([9.0, 8.4])}, 9.0,
     dict(_SERVE_OK, complete=1), "requests complete"),
    ({"sim": _train([9.0, 8.5]), "pallas": _train([9.0, 8.4])}, 9.0,
     dict(_SERVE_OK, first_token_matches=1), "first tokens"),
])
def test_check_verdict(train, fp32, serve, expect):
    fails = chip_smoke.check(train, fp32, serve)
    if expect is None:
        assert fails == []
    else:
        assert len(fails) == 1 and expect in fails[0], fails


def test_check_requires_every_kernel():
    train = {"sim": _train([9.0, 8.5]), "pallas": _train([9.0, 8.4])}
    train["pallas"]["kernel_calls"]["hbfp_flash_dkv"] = 0
    fails = chip_smoke.check(train, 9.0, _SERVE_OK)
    assert len(fails) == 1 and "hbfp_flash_dkv" in fails[0]


def _no_result(r):
    lines = r.stdout.strip().splitlines()
    if not lines:
        return True
    try:
        return json.loads(lines[-1]).get("ok") is not True
    except (json.JSONDecodeError, AttributeError):
        return True


def test_refuses_to_run_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, cwd=ROOT, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert _no_result(r)
    assert "needs a TPU" in r.stderr


def test_fails_alone_outside_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert _no_result(r)
