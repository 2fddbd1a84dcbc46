"""Every op of the compiled train step is named by its phase.

`make_train_step` runs its phases under `jax.named_scope`: "hbfp.narrow",
"model" (its gradient is "transpose(jvp(model))"; nested "model.attn",
"model.ffn", "model.embed", "model.head"), "optim.adamw" and
"hbfp.widen". The compiled step's instructions carry those names in their
`op_name` metadata, which is what a device trace attributes time by. XLA's
own instructions (layout copies, broadcasts of constants it folded)
carry no `op_name` at all and are not checked; every instruction that
does carry one, its own or its fused computation's, must sit under one of
the four top-level scopes, scalar bookkeeping (step counter, lr) aside.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.models import init_params
from repro.optim import make_schedule
from repro.train import init_train_state, make_step

TOP = ("hbfp.narrow", "model", "optim.adamw", "hbfp.widen")
# instructions that compute nothing on the device, and loops that span
# their bodies (checked through their bodies)
SKIP = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
        "while", "conditional", "call"}

_COMP = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\((.*)$")
_CALLED = re.compile(r"\b(calls|body|condition|branch_computations|"
                     r"true_computation|false_computation)="
                     r"(\{[^}]*\}|%[\w.\-]+)")


def parse_hlo(text):
    """{computation: [instruction dict]} and the entry's name."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            root, name, shape, op, rest = m.groups()
            on = re.search(r'op_name="([^"]*)"', rest)
            called = [(k, [n.strip().lstrip("%") for n in v.strip("{}")
                           .split(",")]) for k, v in _CALLED.findall(rest)]
            comps[cur].append({"root": bool(root), "name": name,
                               "shape": shape, "op": op, "called": called,
                               "op_name": on.group(1) if on else None})
    return comps, entry


def executed(comps, entry):
    """The computations that run as device ops: the entry and the bodies
    of its loops, conditionals and calls (not fused computations and not
    reducers' to_apply regions)."""
    seen, todo = set(), [entry]
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for ins in comps[c]:
            for kind, names in ins["called"]:
                if kind != "calls" or ins["op"] == "call":
                    todo += names
    return seen


def op_name(comps, ins):
    """The instruction's op_name; a fusion without one takes its fused
    computation's (root first)."""
    if ins["op_name"] or ins["op"] != "fusion":
        return ins["op_name"]
    body = comps[dict(ins["called"])["calls"][0]]
    for i in sorted(body, key=lambda i: not i["root"]):
        if i["op_name"]:
            return i["op_name"]
    return None


def top_scope(name: str):
    """'jit(train_step)/transpose(jvp(model))/model.ffn/dot_general' ->
    'model'; None when no component names a top-level scope."""
    for part in name.split("/"):
        while (m := re.match(r"^[\w.\-]+\((.*)\)$", part)):
            part = m.group(1)
        if part in TOP:
            return part
        if part.startswith("model."):
            return "model"
    return None


def test_top_scope_unwraps_transforms():
    assert top_scope("jit(train_step)/transpose(jvp(model))/model.ffn/dot"
                     ) == "model"
    assert top_scope("jit(train_step)/hbfp.narrow/convert_element_type"
                     ) == "hbfp.narrow"
    assert top_scope("checkpoint/rematted_computation/model.attn/mul"
                     ) == "model"
    assert top_scope("jit(train_step)/add") is None


@pytest.mark.parametrize("policy", ["8", "8; backend=pallas"])
def test_every_named_op_of_the_compiled_step_is_under_a_phase(policy):
    arch = get_arch("yi-9b").smoke()
    sched = make_schedule("constant", base_lr=1e-3, warmup_steps=1,
                          total_steps=1)
    fn = make_step(arch, policy, sched)
    state = init_train_state(jax.random.key(0), arch, init_params)
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
             "labels": jnp.zeros((2, 32), jnp.int32)}
    key = jax.random.key(1)
    jax.eval_shape(fn, state, batch, key)        # builds the jit variant
    (step,) = fn.variants.values()
    comps, entry = parse_hlo(step.lower(state, batch, key).compile()
                             .as_text())
    per_scope = dict.fromkeys(TOP, 0)
    outside = []
    for c in executed(comps, entry):
        for ins in comps[c]:
            if ins["op"] in SKIP or re.match(r"^\w+\[\]", ins["shape"]):
                continue
            name = op_name(comps, ins)
            if name is None:
                continue
            scope = top_scope(name)
            if scope is None:
                outside.append((ins["name"], name))
            else:
                per_scope[scope] += 1
    assert outside == []
    assert all(per_scope.values()), per_scope
