"""Dry runs of whole benchmark runs on the CPU at a toy width, through the
rehearsal cell kept here as files (no entry of BENCHMARK.json's workloads, so
the driver never runs it). What they print says ``platform: cpu`` and carries
nothing under a device metric's name."""
import os

import pytest

import run as bench_run
from conftest import last_json_line


@pytest.mark.parametrize("name,trace", [
    ("tiny.train-tiny", 0), ("tiny.train-tiny", 1),
    ("tiny.serve-tiny", 0), ("tiny.serve-open-tiny", 1),
    # a second model family, kept with the rehearsal: its configuration names
    # its sizes otherwise and holds none of the first family's keys
    ("tiny-altkeys.train-tiny", 1), ("tiny-altkeys.serve-tiny", 1)])
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


FAMILY_WORDS = ("hidden_size", "num_layers", "num_heads", "ffn_mult",
                "max_seq_len", "layer_norm_epsilon", "initializer_range",
                "GPT", "gpt")


def test_harness_names_no_size_of_any_model():
    """What knows a block's shapes is a family's (families/<family>/): no
    file of harness/ or run.py holds a configuration key of the one family
    there is, or its name; of a configuration the harness reads
    ``vocab_size``, ``dtypes`` and ``family``."""
    import glob
    import os
    from benchmark.harness import loader
    files = glob.glob(os.path.join(loader.BENCH_DIR, "harness", "*.py")) \
        + [os.path.join(loader.BENCH_DIR, "run.py")]
    assert len(files) > 10
    for path in files:
        text = open(path).read()
        for word in FAMILY_WORDS:
            assert word not in text, (path, word)


def test_only_files_named_sut_import_the_program():
    import os
    import re
    from benchmark.harness import loader
    importing = re.compile(r"^\s*(import|from)\s+paddle_tpu\b", re.M)
    seen = []
    for d, _dirs, names in os.walk(loader.BENCH_DIR):
        for n in names:
            if n.endswith(".py") and importing.search(
                    open(os.path.join(d, n)).read()):
                seen.append(os.path.relpath(os.path.join(d, n),
                                            loader.BENCH_DIR))
    assert seen and all(os.path.basename(p) == "sut.py" for p in seen), seen
    assert os.path.join("families", "gpt", "sut.py") in seen


def test_second_family_holds_none_of_the_firsts_keys(rehearsal_cell):
    cell = rehearsal_cell("tiny-altkeys.train-tiny")
    assert cell.family.name == "altkeys"
    assert not set(cell.config) & set(FAMILY_WORDS)
    assert cell.family.path.startswith(os.path.dirname(__file__))


def test_training_cell_of_a_family_that_only_serves(rehearsal_cell,
                                                    monkeypatch, capsys):
    """It exits with a sentence that says so, before any set-up."""
    cell = rehearsal_cell("tiny.train-tiny")
    monkeypatch.delattr(cell.family.reference, "loss_and_grads")
    monkeypatch.setattr(cell.family.weights, "make_weights",
                        lambda *a, **k: pytest.fail("set-up was started"))
    with pytest.raises(SystemExit) as e:
        bench_run.run_cell(cell, 1, 0.5, False, require_chip=False)
    assert "loss_and_grads" in str(e.value.code)
    assert "serves only" in str(e.value.code)
    assert capsys.readouterr().out.strip() == ""


def test_no_chip_no_result(capsys):
    """On a machine with no accelerator the command exits with another code
    than 0 and prints no result."""
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", "gpt3-1.3B.train-seq2048", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""
