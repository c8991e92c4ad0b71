"""The reader of ``greedy_tail_share`` on hand-made counters: a program
counter, read on and off the chip; silent against a program that does not
count ``sampled_steps`` (the parent of the PR that brought it) and on an
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
    ({**PAGED, "sampled_steps": 0}, 100.0),
    ({**PAGED, "sampled_steps": 3}, 100.0 * 11 / 14),       # drew in 3 of 14
    ({**PAGED, "sampled_steps": 14}, 0.0),
    ({"paged_steps": 0, "sampled_steps": 0}, None),         # empty window
    ({}, None),
], ids=["parent", "all_greedy", "mixed", "all_sampled", "empty_window",
        "no_counters"])
@pytest.mark.parametrize("on_chip", [True, False])
def test_greedy_tail_share(counters, expected, on_chip):
    got = loader.load_reader("greedy_tail_share")(ctx(counters, on_chip))
    assert got is None if expected is None else got == pytest.approx(expected)
