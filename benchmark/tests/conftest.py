import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.join(ROOT, "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)

REHEARSAL = os.path.join(HERE, "rehearsal")


@pytest.fixture(scope="session")
def rehearsal_bench():
    with open(os.path.join(REHEARSAL, "bench.json")) as f:
        return json.load(f)


@pytest.fixture
def rehearsal_cell(rehearsal_bench):
    from benchmark.harness import loader

    def make(name):
        entry = {w["name"]: w for w in rehearsal_bench["workloads"]}[name]
        return loader.Cell(rehearsal_bench, entry,
                           os.path.join(REHEARSAL, "cells"))
    return make


def last_json_line(text):
    return json.loads([l for l in text.splitlines() if l.strip()][-1])
