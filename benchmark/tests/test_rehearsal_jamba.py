"""A whole serving run of the jamba family on the CPU at a toy width with the
published layer pattern (``rehearsal/bench-jamba.json``: no entry of
BENCHMARK.json, so no benchmark run reaches it): prefill down a four-rung chunk
ladder and decode through the attention layer's pages and the Mamba layers'
two states a slot, prompts of up to 120 positions in four slots that shorter
and longer requests reuse, the served tokens against the family's plain
reference. Its output says ``platform: cpu`` and carries nothing under a
device metric's name. And the float8 control and each planted fault of
``families/jamba/faults.py``, put in the program's place, fail the rehearsal
cell's own comparison."""
import contextlib
import functools
import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal")


@functools.lru_cache(None)
def _tiny():
    from benchmark.harness import loader
    with open(os.path.join(REHEARSAL, "bench-jamba.json")) as f:
        bench = json.load(f)
    return loader.Cell(bench, bench["workloads"][0],
                       os.path.join(REHEARSAL, "cells"))


@pytest.mark.parametrize("trace", [0, 1])
def test_jamba_rehearsal_runs_to_correct(capsys, trace):
    import run as bench_run
    cell = _tiny()
    assert cell.family.name == "jamba"
    bench_run.run_cell(cell, 2 ** 31 + 17, 1.5, bool(trace),
                       require_chip=False)
    out = capsys.readouterr()
    res = json.loads([l for l in out.out.splitlines() if l.strip()][-1])
    assert res["correct"] is True, out.err
    assert res["device"]["platform"] == "cpu"
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["compared"]["served_logit_gap_max"]["value"] <= 1e-3
    assert all(k.startswith("rehearsal_cpu.") for k in res["metrics"])
    # a share of a roofline is a device's: None off it
    assert not [k for k in res["metrics"] if "roofline" in k]
    if trace:
        # 1 of 14 layers keeps pages, 13 keep two states a slot (at this
        # width a slot's states weigh as much as a hundred positions' pages)
        assert res["metrics"]["rehearsal_cpu.state_cache_share"]["value"] > 0


def _tiny_logits(ids, mm):
    import jax.numpy as jnp
    cell = _tiny()
    return cell.family.reference.served_logits(
        cell.config, 2 ** 31 + 5, jnp.asarray(ids, jnp.int32), "float32", mm)


@functools.lru_cache(None)
def _tiny_exact():
    """Two rows of 96 seeded tokens and the exact reference's logits."""
    import numpy as np
    from benchmark.harness import reference
    ids = np.random.default_rng(5).integers(
        0, _tiny().config["vocab_size"], (2, 96))
    return ids, _tiny_logits(ids, reference.mm_exact)


@pytest.mark.parametrize("fault", [
    "control_fp8", "state_not_zeroed", "pads_advance_state",
    "dt_bc_norms_dropped", "taps_reversed", "state_in_bfloat16",
    "rotary_applied"])
def test_a_planted_fault_comes_out_not_correct(fault):
    """Put in the program's place, the float8 control and each model of
    ``families/jamba/faults.py`` fail the rehearsal cell's own comparison
    (as ``tools/serve_readings.py`` reads ``FAULTS`` at a cell's real
    size). The pads are planted at the rehearsal's own page. ``UNRESOLVED``
    holds those that served tokens do not resolve at the cell's sizes; at
    the rehearsal's they fail too."""
    import jax.numpy as jnp
    import numpy as np
    from benchmark.harness import compare, reference
    cell = _tiny()
    faults = importlib.import_module(
        cell.family.reference.__package__ + ".faults")
    planted = {**faults.FAULTS, **faults.UNRESOLVED,
               "control_fp8": contextlib.nullcontext}
    assert len(planted) == 7
    plant = planted[fault]
    if fault == "pads_advance_state":
        plant = functools.partial(plant, cell.file["engine"]["page_size"])
    ids, ref = _tiny_exact()
    with plant():
        low = _tiny_logits(ids, reference.mm_fp8 if fault == "control_fp8"
                           else reference.mm_exact)
    first = np.asarray(jnp.argmax(low, axis=-1))
    rows = [(48, first[k, 47:].tolist()) for k in range(len(ids))]
    checks = compare.Checks()
    checks.add("served_logit_gap_max",
               np.concatenate(compare.logit_gaps(ref, rows)).max(),
               cell.file["check"]["limits"]["logit_gap"])
    assert not checks.correct, checks.lines()
