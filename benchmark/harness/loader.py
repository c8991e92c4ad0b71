"""Finds everything by the names in BENCHMARK.json: a cell's file, its
configuration's file, its traffic mix's file, the reader of each per-layer
metric, and the model family that the configuration names. Adding a cell, a
configuration, a mix, a metric or a family is adding files and entries;
nothing here is edited."""
import importlib
import importlib.util
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)


def _json(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    def __init__(self, bench, entry, cell_dir=None):
        self.name = entry["name"]
        self.chips = entry["chips"]
        self.bench = bench
        cell_dir = cell_dir or os.path.join(BENCH_DIR, "cells")
        self.file = _json(os.path.join(cell_dir, self.name + ".json"))
        cfg_entry = {c["name"]: c for c in bench["configs"]}.get(entry["config"])
        cfg_path = os.path.join(ROOT, cfg_entry["file"]) if cfg_entry else \
            os.path.join(os.path.dirname(cell_dir), "configs",
                         entry["config"] + ".json")
        self.config = _json(cfg_path)
        if "family" not in self.config:
            raise SystemExit(f"{cfg_path} names no model family: it has "
                             f"no \"family\" key")
        self.family = load_family(self.config["family"],
                                  os.path.dirname(cell_dir))
        self.traffic = _json(os.path.join(
            os.path.dirname(cell_dir), "traffic", entry["traffic"] + ".json"))

    def reports(self, metric):
        """Does this cell report the metric (a BENCHMARK.json entry)? One
        with no ``workloads`` list (``setup_s``) is reported everywhere."""
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self.reports(m)]

    def per_layer(self):
        return [m for m in self.bench["per_layer"] if self.reports(m)]


def load_bench(path=None):
    return _json(path or os.path.join(ROOT, "BENCHMARK.json"))


def load_cell(name, bench=None):
    bench = bench or load_bench()
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    return Cell(bench, entry)


FAMILY_FILES = ("weights", "reference", "work", "sut")
_families = {}          # directory -> Family


class Family:
    """The code that knows one block's shapes: the four modules of
    ``families/<name>/`` (README.md has their interface)."""

    def __init__(self, name, path, package):
        self.name, self.path = name, path
        for part in FAMILY_FILES:
            setattr(self, part, importlib.import_module(f"{package}.{part}"))


def load_family(name, near=None):
    """The family ``name``: ``families/<name>/`` under ``near`` (the
    rehearsal keeps one beside its cells) or else under benchmark/, loaded by
    path as a package of its four files, so that they can import each other
    (``from . import weights``) and the harness (``benchmark.harness``)."""
    dirs = [os.path.join(d, "families", name) for d in (near, BENCH_DIR) if d]
    path = next((d for d in dirs if os.path.isdir(d)), None)
    if path is None:
        raise SystemExit(f"no model family {name!r}: no directory "
                         + " or ".join(dirs))
    if path not in _families:
        missing = [f for f in FAMILY_FILES
                   if not os.path.isfile(os.path.join(path, f + ".py"))]
        if missing:
            raise SystemExit(f"model family {name!r} ({path}) lacks "
                             + ", ".join(f + ".py" for f in missing))
        package = "benchmark_family_%d_%s" % (
            len(_families), "".join(c if c.isalnum() else "_" for c in name))
        pkg = types.ModuleType(package)
        pkg.__path__ = [path]
        sys.modules[package] = pkg
        _families[path] = Family(name, path, package)
    return _families[path]


def load_reader(metric_name):
    """The per-layer metric's reader: ``read(ctx)`` of the file named after
    the metric up to its first dot, loaded by path. A quantity split by the
    end-to-end metric it moves (``x.train``, ``x.serve``) has one reader,
    ``x.py``."""
    stem = metric_name.split(".")[0]
    path = os.path.join(BENCH_DIR, "layer_metrics", stem + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + "".join(c if c.isalnum() else "_" for c in stem),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
