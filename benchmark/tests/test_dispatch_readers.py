"""The readers that find a dispatch by its executable's own name
(``decode_device_ms``, ``chunk_device_ms``), split the device's idle by what
the program's host loop was doing (``idle_launch_ms``, ``idle_fetch_ms``,
``idle_turn_ms``) and read the launch counter (``host_launch_ms``), on a
hand-made trace whose numbers can be worked out on paper: decode and chunk
runs interleaved, a gap under each kind of span, a seam inside a run, one run
cut by the window's edge; the same with the device plane 1.9 ms early, as a
v5e profile has it. Silent on a trace whose spans carry no ``exe=``
(the parent of the PR that brought them); launch + fetch + turn is the idle
that ``device_idle`` reads, less the seam."""
import types

import pytest

from benchmark.harness import loader, trace as T
from benchmark.layer_metrics import decode_bytes_roofline as DBR
from benchmark.layer_metrics import decode_device_ms as DD
from benchmark.layer_metrics import idle_launch_ms as IL

MS = 1_000_000
DECODE, CHUNK = "pt_paged_b16_t1", "pt_paged_b1_t16"
TRACE_READERS = ("decode_device_ms", "chunk_device_ms", "idle_launch_ms",
                 "idle_fetch_ms", "idle_turn_ms")
# milliseconds of the 100 ms window, worked out in make_trace's comments
LAUNCH, FETCH, TURN, RUNS = 14.0, 6.0, 51.0, 3
EXPECTED = {"decode_device_ms": 9.0, "chunk_device_ms": 6.0,
            "idle_launch_ms": LAUNCH / RUNS, "idle_fetch_ms": FETCH / RUNS,
            "idle_turn_ms": TURN / RUNS}
EARLY = 1_900_000       # ns the device plane of a v5e profile lies early


def span(name, kind, exe, a, b, tagged=True):
    tags = f"kind={kind},exe={exe}" if tagged else f"kind={kind}"
    return [f"pt.serve.{name}#{tags}#", int(a * MS), int((b - a) * MS)]


def make_trace(tagged=True, early=0):
    """Device 0 over a window of 100 ms, ``early`` ns ahead of the host:

    idle  0- 5   turn 2 | chunk feed 2-4: launch 2 | its wait from 4: launch 1
    busy  5-11   the chunk run (two operations, a seam of 1 us between)
    idle 11-17   wait until 13: fetch 2 | emit: turn 1 | decode feed 14-16:
                 launch 2 | its wait from 16: launch 1
    busy 17-27   a decode run (a %while around two operations)
    idle 27-33   fetch 2 | turn 1 | decode feed 30-32: launch 2 | launch 1
    busy 33-41   a decode run
    idle 41-95   fetch 2 | turn 47 | chunk feed 90-94: launch 4 | launch 1
    busy 95-103  a chunk run that the window's edge (100) cuts: no run

    Every run starts 2 ms after its launch span has ended and ends 2 ms
    before its wait span: the dispatches leave the clocks a room of -2 to
    2 ms, whose middle is no shift."""
    ops = [
        ["%fusion.1 = bf16[8] fusion()", 5 * MS, 3 * MS],
        ["%fusion.2 = bf16[8] fusion()", 8 * MS + 1000, 3 * MS - 1000],
        ["%while.3 = (s32[]) while()", 17 * MS, 10 * MS],
        ["%fusion.4 = bf16[8] fusion()", 17 * MS, 4 * MS],
        ["%fusion.5 = bf16[8] fusion()", 21 * MS, 6 * MS],
        ["%fusion.4 = bf16[8] fusion()", 33 * MS, 8 * MS],
        ["%fusion.1 = bf16[8] fusion()", 95 * MS, 8 * MS],
    ]
    modules = [
        [f"jit_{CHUNK}(123)", 5 * MS, 6 * MS],
        [f"jit_{DECODE}(456)", 17 * MS, 10 * MS],
        [f"jit_{DECODE}(456)", 33 * MS, 8 * MS],
        ["jit_pt_page_copy(789)", 33 * MS, 1 * MS],     # no feed names it
        [f"jit_{CHUNK}(123)", 95 * MS, 8 * MS],
    ]
    host = [
        ["Engine.step", 1 * MS, 60 * MS],
        span("feed", "chunk", CHUNK, 2, 4, tagged),
        span("launch", "chunk", CHUNK, 2.5, 3, tagged),
        span("wait", "chunk", CHUNK, 4, 13, tagged),
        ["pt.serve.emit", 13 * MS, 1 * MS],
        span("feed", "decode", DECODE, 14, 16, tagged),
        span("launch", "decode", DECODE, 14.5, 15, tagged),
        span("wait", "decode", DECODE, 16, 29, tagged),
        ["pt.serve.emit", 29 * MS, 1 * MS],
        span("feed", "decode", DECODE, 30, 32, tagged),
        span("launch", "decode", DECODE, 30.5, 31, tagged),
        span("wait", "decode", DECODE, 32, 43, tagged),
        span("feed", "chunk", CHUNK, 90, 94, tagged),
        span("launch", "chunk", CHUNK, 92, 93, tagged),
        span("wait", "chunk", CHUNK, 94, 106, tagged),
    ]
    ahead = lambda rows: [[n, s - early, d] for n, s, d in rows]
    return T.Trace({0: {"ops": ahead(ops), "modules": ahead(modules)}}, host,
                   [0, 100 * MS])


def make_ctx(trace, on_chip=True, counters=None):
    return types.SimpleNamespace(trace=trace, on_chip=on_chip,
                                 counters=dict(counters or {}))


@pytest.mark.parametrize("name", TRACE_READERS)
def test_reader_on_the_hand_made_trace(name):
    read = loader.load_reader(name)
    assert read(make_ctx(make_trace())) == pytest.approx(EXPECTED[name])
    # a device plane that lies early is brought back inside the dispatches
    assert read(make_ctx(make_trace(early=EARLY))) == pytest.approx(
        EXPECTED[name])
    assert read(make_ctx(make_trace(), on_chip=False)) is None
    assert read(make_ctx(None)) is None


@pytest.mark.parametrize("name", TRACE_READERS)
def test_reader_is_silent_where_the_spans_name_no_executable(name):
    assert loader.load_reader(name)(make_ctx(make_trace(tagged=False))) \
        is None


def test_launch_fetch_and_turn_are_the_idle():
    tr = make_trace()
    split = IL.idle_split(tr, 0)
    assert split == pytest.approx(
        {"launch": LAUNCH / 1e3, "fetch": FETCH / 1e3, "turn": TURN / 1e3})
    idle_s = tr.idle_share(0) * tr.window_s      # what device_idle reads
    assert sum(split.values()) == pytest.approx(idle_s - 1e-6)   # the seam
    per_run = sum(loader.load_reader(n)(make_ctx(tr))
                  for n in ("idle_launch_ms", "idle_fetch_ms",
                            "idle_turn_ms"))
    assert per_run * RUNS / 1e3 == pytest.approx(idle_s, rel=0.01)


def test_the_clocks_are_aligned_by_the_dispatches_own_spans():
    assert IL.dispatches(make_trace())[:2] == [[3 * MS, 13 * MS, CHUNK],
                                               [15 * MS, 29 * MS, DECODE]]
    assert IL.clock_shift(make_trace(), 0) == 0
    assert IL.clock_shift(make_trace(early=EARLY), 0) == EARLY
    assert IL.clock_shift(make_trace(tagged=False), 0) == 0
    # read as it stands, an early plane moves the idle from under the
    # launch to under the wait of the dispatch that follows
    early = make_trace(early=EARLY)
    early.host = [h for h in early.host if "launch" not in h[0]]
    assert IL.clock_shift(early, 0) == 0
    as_it_stands = IL.idle_split(early, 0)
    assert as_it_stands["launch"] < LAUNCH / 2e3
    assert as_it_stands["fetch"] > 1.5 * FETCH / 1e3


def test_executables_and_runs_by_the_programs_names():
    tr = make_trace()
    assert DD.executables(tr) == {DECODE: "decode", CHUNK: "chunk"}
    assert DD.runs(tr, 0, [DECODE]) == [[17 * MS, 10 * MS], [33 * MS, 8 * MS]]
    assert DD.runs(tr, 0, [CHUNK]) == [[5 * MS, 6 * MS]]     # one is cut
    assert DD.runs(tr, 0, []) == []
    assert DD.executables(make_trace(tagged=False)) == {}


def test_the_older_reader_of_decode_dispatches_reads_what_it_read():
    """``decode_bytes_roofline`` finds a decode dispatch by ``kind=decode``
    on feed and wait: ``exe=`` beside it and the launch span change nothing."""
    want = [[14 * MS, 29 * MS], [30 * MS, 43 * MS]]
    assert DBR.decode_dispatches(make_trace()) == want
    assert DBR.decode_dispatches(make_trace(tagged=False)) == want


@pytest.mark.parametrize("counters,expected", [
    ({"boundaries": 10, "feed_s": 0.05, "launch_s": 0.02}, 2.0),
    ({"boundaries": 10, "feed_s": 0.05}, None),          # no such counter
    ({"boundaries": 0, "launch_s": 0.0}, None),          # empty window
    ({}, None),
], ids=["counted", "parent", "empty_window", "no_counters"])
def test_host_launch_ms(counters, expected):
    read = loader.load_reader("host_launch_ms")
    got = read(make_ctx(None, counters=counters))
    assert got is None if expected is None else got == pytest.approx(expected)
    assert read(make_ctx(None, on_chip=False, counters=counters)) is None
