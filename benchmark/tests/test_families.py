"""Every configuration of BENCHMARK.json names a model family whose
directory holds the four files with the interface's functions (README.md),
and every workload's cell resolves on the CPU: files found, family loaded,
nothing run."""
import json
import os

import pytest

from benchmark.harness import loader

BENCH = loader.load_bench()
SERVES = {"weights": ("make_weights",), "reference": ("served_logits",),
          "work": ("serve_flops",), "sut": ("program_config", "make_engine")}
TRAINS = {"weights": ("seed_leaves", "leaf_parts"),
          "reference": ("loss_and_grads", "decays"),
          "work": ("train_flops_per_token",),
          "sut": ("param_shardings", "make_trainer", "trainer_moment1")}


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_configuration_names_a_whole_family(config):
    entry = {c["name"]: c for c in BENCH["configs"]}[config]
    with open(os.path.join(loader.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert {"family", "vocab_size", "dtypes"} <= set(cfg)
    path = os.path.join(loader.BENCH_DIR, "families", cfg["family"])
    for part in loader.FAMILY_FILES:
        assert os.path.isfile(os.path.join(path, part + ".py")), (path, part)
    fam = loader.load_family(cfg["family"])
    trains = any(loader.load_cell(w["name"], BENCH).traffic["kind"] == "train"
                 for w in BENCH["workloads"] if w["config"] == config)
    for part, names in list(SERVES.items()) + (
            list(TRAINS.items()) if trains else []):
        for fn in names:
            assert callable(getattr(getattr(fam, part), fn, None)), (part, fn)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(workload):
    cell = loader.load_cell(workload, BENCH)
    assert cell.family is loader.load_family(cell.config["family"])
    assert cell.family.name == cell.config["family"]
    assert cell.traffic["kind"] in ("train", "serve_closed", "serve_open")
    for m in cell.per_layer():
        assert callable(loader.load_reader(m["name"]))


def test_unknown_family_is_refused_in_a_sentence(tmp_path):
    with pytest.raises(SystemExit) as e:
        loader.load_family("no-such-family")
    assert "no model family 'no-such-family'" in str(e.value.code)
    (tmp_path / "families" / "half").mkdir(parents=True)
    (tmp_path / "families" / "half" / "weights.py").write_text("")
    with pytest.raises(SystemExit) as e:
        loader.load_family("half", str(tmp_path))
    assert "lacks reference.py, work.py, sut.py" in str(e.value.code)
