"""The move of the one model family out of harness/ changed no number: the
family's weights, served logits, loss and gradients at the rehearsal's toy
size, for two seeds, are bit for bit what the parent's harness/weights.py and
harness/reference.py gave. ``data/family_digests.json`` was recorded from the
parent's code (commit c756467, PR 26) by ``record()`` below, before the move,
on this sandbox's CPU (jax 0.9.0); one thread and eight gave the same bits.

    python3 benchmark/tests/test_family_digests.py <tree whose code to record>
"""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "family_digests.json")
CONFIG = os.path.join(HERE, "rehearsal", "configs", "tiny.json")
SEEDS = (7, 2 ** 31 + 11)
DTYPES = ("float32", "bfloat16")


def _digest(tree):
    """sha256 over every leaf's path, dtype, shape and bytes, in path order."""
    import jax
    h = hashlib.sha256()
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def digests(make_weights, served_logits, loss_and_grads, mm, cfg):
    """{name: sha256} of what the three functions give for each seed."""
    out = {}
    for seed in SEEDS:
        rng = np.random.default_rng(seed)
        ids = rng.integers(0, cfg["vocab_size"], (4, 64)).astype(np.int32)
        for dtype in DTYPES:
            w = make_weights(cfg, seed, dtype)
            out[f"weights.{dtype}.{seed}"] = _digest(w)
            out[f"served_logits.{dtype}.{seed}"] = _digest(
                served_logits(cfg, seed, ids[:2, :48], dtype, mm))
            out[f"loss_and_grads.{dtype}.{seed}"] = _digest(
                loss_and_grads(w, ids, cfg, mm, 2))
    return out


def _cfg():
    with open(CONFIG) as f:
        return json.load(f)


def record(tree):
    """Digests of the harness's own weights and reference in ``tree``, a
    checkout of the commit before the move."""
    sys.path.insert(0, tree)
    from benchmark.harness import reference as R, weights as W
    cfg = {k: v for k, v in _cfg().items() if k != "family"}
    return digests(W.make_weights, R.served_logits, R.loss_and_grads,
                   R.mm_exact, cfg)


@pytest.fixture(scope="module")
def found():
    from benchmark.harness import loader, reference
    fam = loader.load_family("gpt")
    return digests(fam.weights.make_weights, fam.reference.served_logits,
                   fam.reference.loss_and_grads, reference.mm_exact, _cfg())


def _recorded():
    if not os.path.exists(DATA):
        return {}
    with open(DATA) as f:
        return json.load(f)


RECORDED = _recorded()


def test_the_parents_digests_are_kept():
    assert len(RECORDED) == 3 * len(SEEDS) * len(DTYPES)


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_family_gives_the_parents_bits(found, name):
    assert found[name] == RECORDED[name]


if __name__ == "__main__":
    got = record(sys.argv[1])
    with open(DATA, "w") as f:
        json.dump(got, f, indent=1, sort_keys=True)
        f.write("\n")
    print(json.dumps(got, indent=1, sort_keys=True))
