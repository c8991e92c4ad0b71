"""``correct`` has to be able to fail. At a size a test run can hold:

* the control (the reference computed in float8, put in the program's place)
  comes out as not correct, for training and for serving;
* a whole run with the timed path broken underneath comes out as not
  correct, once for each fault a cell can have: a step that returns its
  state unchanged; half of the batch left out, the mean taken over the rest
  (on several chips this is also what a dp replica computes when the
  exchange between chips is left out); a token altered where it is produced.
"""
import numpy as np
import pytest

import run as bench_run
from benchmark.harness import compare, reference, serve, sut, train
from conftest import last_json_line

SEED = 2 ** 31 + 23


def test_control_fails_training(rehearsal_cell):
    cell = rehearsal_cell("tiny.train-tiny")
    n = cell.file["check"]["reference_steps"]
    ref = train.reference_readings(cell, SEED, n)
    ctl = train.reference_readings(cell, SEED, n, mm="fp8")
    checks = train.compare_readings(ctl, ref, cell.file["check"]["limits"])
    assert not checks.correct
    same = train.compare_readings(ref, ref, cell.file["check"]["limits"])
    assert same.correct


def test_control_fails_serving(rehearsal_cell):
    cell = rehearsal_cell("tiny.serve-tiny")
    cfg = cell.config
    rng = np.random.default_rng(SEED)
    ids = rng.integers(0, cfg["vocab_size"], (4, 128)).astype(np.int32)
    logits = np.asarray(cell.family.reference.served_logits(
        cfg, SEED, ids, "float32", reference.mm_exact))
    # greedy continuations under the reference itself: gap 0
    rows = [(40, logits[i, 39:39 + 60].argmax(-1).tolist()) for i in range(4)]
    gap, n = serve.reference_gap(cell, SEED, ids, rows)
    assert gap == 0.0 and n == 240
    gap_ctl, _ = serve.reference_gap(cell, SEED, ids, rows, mm="fp8")
    assert gap_ctl > cell.file["check"]["limits"]["logit_gap"]


@pytest.mark.parametrize("name", ["tiny.train-tiny", "tiny.serve-tiny"])
def test_readings_tool_holds_the_control_to_the_cells_limits(rehearsal_cell,
                                                            capsys, name):
    """tools/readings.py puts the program's, the control's and the planted
    fault's numbers through ``Checks`` with the cell's own limits."""
    from tools import readings
    cell = rehearsal_cell(name)
    if cell.traffic["kind"] == "train":
        out = readings.train_seed(cell, SEED, True)
        assert out["fault_half_batch"]["correct"] is False
    else:
        out = readings.serve_seed(cell, SEED, True, 1.0)
    assert out["program"]["correct"] is True
    assert out["control_fp8"]["correct"] is False
    printed = capsys.readouterr().out
    assert "control_fp8: compared" in printed and "NOT CORRECT" in printed


class _StateUnchanged:
    """A step that computes its loss and returns its state unchanged."""

    def __init__(self, step):
        self._s = step

    def __getattr__(self, k):
        return getattr(self._s, k)

    def __call__(self, ids):
        import jax
        keep_p = jax.tree_util.tree_map(lambda a: a + 0, self._s.params)
        loss = self._s(ids)
        self._s.params = keep_p
        return loss

    def __setattr__(self, k, v):
        if k == "_s":
            object.__setattr__(self, k, v)
        else:
            setattr(self._s, k, v)


class _HalfBatch(_StateUnchanged):
    """Half of the batch left out, the mean taken over the rest."""

    def __call__(self, ids):
        return self._s(ids[: len(ids) // 2])


@pytest.mark.parametrize("fault", [_StateUnchanged, _HalfBatch])
def test_broken_train_step_is_not_correct(rehearsal_cell, capsys, monkeypatch,
                                          fault):
    cell = rehearsal_cell("tiny.train-tiny")
    real = cell.family.sut.make_trainer
    monkeypatch.setattr(cell.family.sut, "make_trainer",
                        lambda *a, **k: fault(real(*a, **k)))
    bench_run.run_cell(cell, SEED, 0.5, False, require_chip=False)
    res = last_json_line(capsys.readouterr().out)
    assert res["correct"] is False
    assert any(v["value"] > v["limit"] for v in res["compared"].values())


def test_altered_token_is_not_correct(rehearsal_cell, capsys, monkeypatch):
    cell = rehearsal_cell("tiny.serve-tiny")
    real = sut.make_request

    def altered(prompt, max_new_tokens, on_token, **kw):
        def garbled(req, tok):
            # every seventh token comes out as its neighbour in the vocabulary
            on_token(req, tok + 1 if len(req.tokens) % 7 == 0 else tok)
        return real(prompt, max_new_tokens, garbled, **kw)

    monkeypatch.setattr(sut, "make_request", altered)
    bench_run.run_cell(cell, SEED, 1.5, False, require_chip=False)
    res = last_json_line(capsys.readouterr().out)
    assert res["correct"] is False


def test_sound_runs_are_correct(rehearsal_cell, capsys):
    for name in ("tiny.train-tiny", "tiny.serve-tiny"):
        bench_run.run_cell(rehearsal_cell(name), SEED, 0.5, False,
                           require_chip=False)
        assert last_json_line(capsys.readouterr().out)["correct"] is True


def test_worst_leaf_gap_is_a_gap_of_norms():
    ref = {"a": 1.0, "b": 1e-6, "c": 2.0}
    prog = {"a": 1.1, "b": 3e-6, "c": 2.0}
    gap, at = compare.worst_leaf_gap(prog, ref)
    assert at == "a" and gap == pytest.approx(0.1)   # b is held to the median
    assert compare.nought_gradient_leaves({"a": 1.0, "b": 1e-6, "c": 2.0}) \
        == {"b"}
