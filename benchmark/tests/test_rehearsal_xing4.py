"""A whole serving run of the xing4 family on the CPU at a toy width with
every kind of layer (``rehearsal/bench-xing4.json``: no entry of
BENCHMARK.json, so the driver never runs it): prefill down a four-rung chunk
ladder and decode through the latent pool, the served tokens against the
family's plain reference. Its output says ``platform: cpu`` and carries
nothing under a device metric's name; the readers of the family's own
per-layer metrics find a counter and still return nothing off the chip."""
import functools
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal")


@pytest.mark.parametrize("trace", [0, 1])
def test_xing4_rehearsal_runs_to_correct(capsys, trace):
    import run as bench_run
    from benchmark.harness import loader
    with open(os.path.join(REHEARSAL, "bench-xing4.json")) as f:
        bench = json.load(f)
    cell = loader.Cell(bench, bench["workloads"][0],
                       os.path.join(REHEARSAL, "cells"))
    assert cell.family.name == "xing4"
    bench_run.run_cell(cell, 2 ** 31 + 11, 1.5, bool(trace),
                       require_chip=False)
    out = capsys.readouterr()
    res = json.loads([l for l in out.out.splitlines() if l.strip()][-1])
    assert res["correct"] is True, out.err
    assert res["device"]["platform"] == "cpu"
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["compared"]["served_logit_gap_max"]["value"] <= 1e-3
    assert all(k.startswith("rehearsal_cpu.") for k in res["metrics"])
    # a share of a roofline or of the experts is a device's: None off it
    assert not [k for k in res["metrics"]
                if "roofline" in k or "moe_touched" in k]


@functools.lru_cache(None)
def _tiny():
    from benchmark.harness import loader
    with open(os.path.join(REHEARSAL, "bench-xing4.json")) as f:
        bench = json.load(f)
    return loader.Cell(bench, bench["workloads"][0],
                       os.path.join(REHEARSAL, "cells"))


def _tiny_logits(ids, mm):
    import jax.numpy as jnp
    cell = _tiny()
    return cell.family.reference.served_logits(
        cell.config, 2 ** 31 + 5, jnp.asarray(ids, jnp.int32), "float32", mm)


@functools.lru_cache(None)
def _tiny_exact():
    """Two rows of 64 seeded tokens and the exact reference's logits."""
    import numpy as np
    from benchmark.harness import reference
    ids = np.random.default_rng(5).integers(
        0, _tiny().config["vocab_size"], (2, 64))
    return ids, _tiny_logits(ids, reference.mm_exact)


@pytest.mark.parametrize("fault", [
    "control_fp8", "wrong_rotary_pairing", "dropped_shared_expert",
    "unscaled_routed_weights", "capacity_dropped_tokens"])
def test_a_planted_fault_comes_out_not_correct(fault):
    """Put in the program's place, the float8 control and each model of
    ``families/xing4/faults.py`` fail the rehearsal cell's own comparison
    (as ``tools/serve_readings.py`` reads them at a cell's real size)."""
    import contextlib
    import importlib
    import jax.numpy as jnp
    import numpy as np
    from benchmark.harness import compare, reference
    cell = _tiny()
    faults = importlib.import_module(
        cell.family.reference.__package__ + ".faults")
    ids, ref = _tiny_exact()
    with faults.FAULTS.get(fault, contextlib.nullcontext)():
        low = _tiny_logits(ids, reference.mm_fp8 if fault == "control_fp8"
                           else reference.mm_exact)
    first = np.asarray(jnp.argmax(low, axis=-1))
    rows = [(32, first[k, 31:].tolist()) for k in range(len(ids))]
    checks = compare.Checks()
    checks.add("served_logit_gap_max",
               np.concatenate(compare.logit_gaps(ref, rows)).max(),
               cell.file["check"]["limits"]["logit_gap"])
    assert not checks.correct, checks.lines()
