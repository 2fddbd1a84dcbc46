"""Least bytes of the weight update against hand-computed values."""
import json
import os

import jax
import pytest

import update_work
from tiny import TINY_CFG
from weights import make_params

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_yi_update_bytes():
    c = cfg("yi-9b-4L")
    layer = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008
    matrices = 4 * layer + 2 * 8000 * 4096          # + embedding and head
    vectors = (2 * 4 + 1) * 4096
    assert update_work.trainable_params(c) == (matrices, vectors)
    assert matrices + vectors == 757_633_024
    b = update_work.weight_update_bytes(c)
    assert b == matrices * 26 + vectors * 28 == 19_698_532_352
    assert b / 819e9 == pytest.approx(24.05e-3, rel=1e-3)


def test_minicpm_update_bytes():
    c = cfg("minicpm-2b-10L")
    layer = 4 * 2304 * 2304 + 3 * 2304 * 5760
    matrices = 10 * layer + 2 * 15344 * 2304        # untied head
    vectors = (2 * 10 + 1) * 2304
    assert update_work.trainable_params(c) == (matrices, vectors)
    assert matrices + vectors == 681_221_376
    b = update_work.weight_update_bytes(c)
    assert b == matrices * 26 + vectors * 28 == 17_711_852_544
    assert b / 819e9 == pytest.approx(21.63e-3, rel=1e-3)


def test_counts_every_leaf_the_program_trains():
    """Against the tree the benchmark hands the program: the norm scales
    (stacked over layers) are the vectors, every other leaf a matrix."""
    shapes = jax.eval_shape(lambda k: make_params(TINY_CFG, k),
                            jax.random.key(0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    total = sum(x.size for _, x in flat)
    vectors = sum(x.size for p, x in flat
                  if "norm" in jax.tree_util.keystr(p))
    assert vectors == (2 * TINY_CFG["num_hidden_layers"] + 1) \
        * TINY_CFG["hidden_size"]
    assert update_work.trainable_params(TINY_CFG) == (total - vectors,
                                                      vectors)
