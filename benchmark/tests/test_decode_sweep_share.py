"""The reader of ``decode_sweep_share`` on hand-made counters: a program
counter, read on and off the chip; silent against a program that does not
count the decode dispatches' pages (the parent of the PR that brought it) and
on an empty window."""
import types

import pytest

from benchmark.harness import loader

PAGED = {"paged_steps": 14, "chunk_steps": 4, "boundaries": 10}
TABLE = 10 * 16 * 128                       # ten [16, 1] dispatches, 128 pages


def ctx(counters, on_chip=True):
    return types.SimpleNamespace(counters=dict(counters), on_chip=on_chip,
                                 trace=None)


@pytest.mark.parametrize("counters,expected", [
    (PAGED, None),                                          # no such counter
    ({**PAGED, "decode_pages_table": TABLE,
      "decode_pages_swept": TABLE // 20}, 5.0),             # the kernel
    ({**PAGED, "decode_pages_table": TABLE,
      "decode_pages_swept": TABLE}, 100.0),                 # the gather read
    ({"decode_pages_table": 0, "decode_pages_swept": 0}, None),
    ({}, None),
], ids=["parent", "live_pages", "whole_table", "empty_window", "no_counters"])
@pytest.mark.parametrize("on_chip", [True, False])
def test_decode_sweep_share(counters, expected, on_chip):
    got = loader.load_reader("decode_sweep_share")(ctx(counters, on_chip))
    assert got is None if expected is None else got == pytest.approx(expected)
