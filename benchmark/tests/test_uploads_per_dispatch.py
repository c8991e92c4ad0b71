"""The reader of ``uploads_per_dispatch`` on hand-made counters: a program
counter, read on and off the chip; silent against a program that does not
count ``paged_uploads`` (the parent of the PR that brought it) and on an
empty window."""
import types

import pytest

from benchmark.harness import loader

PAGED = {"paged_steps": 14, "chunk_steps": 4, "boundaries": 10}


def ctx(counters, on_chip=True):
    return types.SimpleNamespace(counters=dict(counters), on_chip=on_chip,
                                 trace=None)


@pytest.mark.parametrize("counters,expected", [
    (PAGED, None),                                          # no such counter
    ({"paged_steps": 0, "paged_uploads": 0}, None),         # empty window
    ({}, None),
    ({**PAGED, "paged_uploads": 14}, 1.0),                  # one buffer each
    ({**PAGED, "paged_uploads": 126}, 9.0),                 # nine arrays each
    ({**PAGED, "paged_uploads": 42, "paged_fetches": 14}, 3.0),
], ids=["parent", "empty_window", "no_counters", "one", "nine",
        "quantised_pool"])
@pytest.mark.parametrize("on_chip", [True, False])
def test_uploads_per_dispatch(counters, expected, on_chip):
    got = loader.load_reader("uploads_per_dispatch")(ctx(counters, on_chip))
    assert got is None if expected is None else got == pytest.approx(expected)
