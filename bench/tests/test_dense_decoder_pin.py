"""The dense decoder's seeded weights and f32 reference readings, pinned
bit for bit: checksums of every weight leaf, each checked step's loss,
and checksums of the first gradient's and the change's leaf norms, at two
seeds for each tiny configuration. A move of the model's code that
changed any of them would change what the cells read."""
import hashlib

import jax
import numpy as np
import pytest

import compare
import reference
import tiny
from weights import make_params, seed_key, train_batch

HP = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
      "grad_clip": 1.0}

# (weights checksum, losses as float.hex, leaf norms checksum)
PINNED = {
    ("yi", 2**40 + 3): ("3cf46152bf1bd847", ["0x1.914c580000000p+2",
                                             "0x1.8283ac0000000p+2",
                                             "0x1.8218d00000000p+2"],
                        "c904f7bd753dcddc"),
    ("yi", 7): ("20770277960c6437", ["0x1.7e15500000000p+2",
                                     "0x1.876b680000000p+2",
                                     "0x1.84db040000000p+2"],
                "9f5d7f0b74d2527b"),
    ("minicpm", 2**40 + 3): ("d3d74765d8f7aba0", ["0x1.6421380000000p+2",
                                                  "0x1.65fed00000000p+2",
                                                  "0x1.66f8080000000p+2"],
                             "d35158bff72eb3a7"),
    ("minicpm", 7): ("4555d7c60630c324", ["0x1.65d2ac0000000p+2",
                                          "0x1.66ff2c0000000p+2",
                                          "0x1.62a6f80000000p+2"],
                     "0eed0096e154c982"),
}
CONFIGS = {"yi": tiny.TINY_CFG, "minicpm": tiny.TINY_MINICPM}


def checksum(items: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(items):
        h.update(k.encode())
        h.update(items[k])
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name,seed", list(PINNED), ids=lambda v: str(v))
def test_dense_decoder_numbers_are_pinned(name, seed):
    cfg = CONFIGS[name]
    wkey, dkey = jax.random.split(seed_key(seed))
    params = jax.device_get(jax.jit(lambda k: make_params(cfg, k))(wkey))
    leaves = {"/".join(str(getattr(p, "key", p)) for p in path):
              np.asarray(v).tobytes()
              for path, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    batches = [train_batch(dkey, s, 2, 32, cfg["vocab_size"])
               for s in range(3)]
    r = reference.train_readings(cfg, HP, params, batches)
    norms = {f"{part}/{k}": float(v).hex().encode()
             for part in ("grad1", "grad1_raw", "change")
             for k, v in compare.flat(r[part]).items()}
    want_params, want_losses, want_norms = PINNED[(name, seed)]
    assert checksum(leaves) == want_params
    assert [float(x).hex() for x in r["losses"]] == want_losses
    assert checksum(norms) == want_norms
