"""Dry runs of whole benchmark runs on the CPU at a toy width, through the
rehearsal cell kept here as files (no entry of BENCHMARK.json's workloads, so
the driver never runs it). What they print says ``platform: cpu`` and carries
nothing under a device metric's name."""
import pytest

import run as bench_run
from conftest import last_json_line


@pytest.mark.parametrize("name,trace", [
    ("tiny.train-tiny", 0), ("tiny.train-tiny", 1),
    ("tiny.serve-tiny", 0), ("tiny.serve-open-tiny", 1)])
def test_rehearsal_run(rehearsal_cell, rehearsal_bench, capsys, name, trace):
    cell = rehearsal_cell(name)
    bench_run.run_cell(cell, 2 ** 31 + 11, 1.5, bool(trace),
                       require_chip=False)
    out = capsys.readouterr()
    res = last_json_line(out.out)
    assert res["correct"] is True, out.err
    assert res["device"]["platform"] == "cpu"
    assert res["failed"] == 0 and res["attempted"] > 0
    names = {m["name"] for m in rehearsal_bench["end_to_end"]
             + rehearsal_bench["per_layer"]}
    assert not set(res["metrics"]) & names
    assert all(k.startswith("rehearsal_cpu.") for k in res["metrics"])
    assert list(res)[-1] == "compared"
    assert "compared" in out.err


def test_harness_names_no_cell_config_mix_or_layer_metric():
    """The harness is driven by data: run.py and harness/ hold no name of a
    cell, a configuration, a traffic mix or a per-layer metric."""
    import glob
    import os
    from benchmark.harness import loader
    bench = loader.load_bench()
    names = {w["name"] for w in bench["workloads"]} \
        | {w["traffic"] for w in bench["workloads"]} \
        | {c["name"] for c in bench["configs"]} \
        | {m["name"] for m in bench["per_layer"]}
    files = glob.glob(os.path.join(loader.BENCH_DIR, "harness", "*.py")) \
        + [os.path.join(loader.BENCH_DIR, "run.py")]
    for path in files:
        text = open(path).read()
        for n in names:
            assert n not in text, (path, n)


def test_no_chip_no_result(capsys):
    """On a machine with no accelerator the command exits with another code
    than 0 and prints no result."""
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", "gpt3-1.3B.train-seq2048", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""
