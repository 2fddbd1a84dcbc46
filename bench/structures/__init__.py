"""One module per model structure: bench/structures/<structure>.py, found
by the name a configuration file gives under `structure`
(`dense_decoder` where the file names none).

The shared modules (program, weights, reference, work, update_work) hold
nothing model-specific: each delegates to the configuration's structure
module. A structure module provides, for a configuration file `cfg` (the
file as loaded, every key, nested groups and nulls included):

  program_arch(cfg) -> ArchConfig
      the program's architecture at the file's sizes; raises
      harness.SetupError where the program's structure is not the one
      this module's reference implements.
  make_params(cfg, key, dtype) -> tree
      random weights from `key`, in the tree the program takes (its
      parameter names, layer leaves stacked as [L, ...]), in `dtype` for
      matrices; called under jit, so one device program makes the tree.
  logits(cfg, params, tokens) -> f32 [B, S, V]
  loss(cfg, params, batch) -> f32 scalar
      the plain float32 reference (mean next-token cross-entropy over
      batch["tokens"] and batch["labels"]): follows the published model,
      imports nothing of the program, every contraction at
      Precision.HIGHEST (`mm`, below), and fits one chip at the timed
      sizes.
  weight_gemms(cfg, tokens) -> [(name, M, K, N)]
      every weight GEMM of one forward pass over `tokens` tokens, LM head
      included; M is the rows the GEMM takes (for an expert, the tokens
      routed to it).
  attention_fwd_ops(cfg, batch, seq) -> int
      operations of the model's attention in one forward pass over
      `batch` sequences of `seq` tokens, all layers (work.py's
      conventions).
  attention_shape(cfg) -> (layers, heads, kv_heads, head_dim)
      of that attention, for the bytes it moves.
  trainable_params(cfg) -> (parameters in matrices, parameters in vectors)
      every leaf the optimizer updates, split by whether it is stored as
      a matrix (gradient in bf16) or a vector (gradient in f32).
"""
from __future__ import annotations

import contextlib
import functools
import importlib
import os

import jax
import jax.numpy as jnp
from jax import lax

from harness import SetupError

F32 = jnp.float32

# the format `mm` rounds its operands to while tracing (None: none)
_ROUNDING = [None]


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def mm(spec, a, b):
    """An einsum in full float32: the reference's every contraction.
    Inside `rounded("fp8")`, the fp8 one a control puts in the program's
    place."""
    if _ROUNDING[-1] is None:
        return _mm(spec, a, b)
    return _mm_fp8(spec, a, b)


@contextlib.contextmanager
def rounded(fmt):
    """`mm`s traced inside round their operands to `fmt` (None, "fp8")."""
    if fmt not in (None, "fp8"):
        raise SetupError(f"no operand rounding {fmt!r}")
    _ROUNDING.append(fmt)
    try:
        yield
    finally:
        _ROUNDING.pop()


def _fp8(x, dtype):
    """x rounded to an fp8 format under one scale for the whole tensor,
    its largest magnitude at the format's largest finite value."""
    top = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(F32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(spec, a, b):
    """fp8 training's contraction: operands in e4m3, the incoming
    gradient in e5m2, each under a scale per tensor; f32 accumulation."""
    return _mm(spec, _fp8(a, jnp.float8_e4m3fn), _fp8(b, jnp.float8_e4m3fn))


def _mm_fp8_fwd(spec, a, b):
    qa, qb = _fp8(a, jnp.float8_e4m3fn), _fp8(b, jnp.float8_e4m3fn)
    return _mm(spec, qa, qb), (qa, qb)


def _mm_fp8_bwd(spec, res, g):
    _, vjp = jax.vjp(lambda x, y: _mm(spec, x, y), *res)
    return vjp(_fp8(g, jnp.float8_e5m2))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT = "dense_decoder"


def of(cfg: dict):
    """The structure module the configuration names."""
    name = cfg.get("structure", DEFAULT)
    if not (isinstance(name, str) and name.isidentifier()
            and os.path.isfile(os.path.join(HERE, name + ".py"))):
        raise SetupError(f"no structure module bench/structures/{name}.py")
    return importlib.import_module(f"{__name__}.{name}")
