"""The trace reduction on a small recorded trace (the head of one training
step of cell 1 on the v5e, my chip run, PR 24, with the benchmark's host
spans set beside it) and on a hand-made two-device trace whose numbers can be
worked out on paper."""
import json
import os

import pytest

from benchmark.harness import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "train_step_head.trace.json")) as f:
        return T.Trace.from_json(json.load(f))


def test_recorded_window_and_busy(recorded):
    assert recorded.window_s == pytest.approx(0.130382419, rel=1e-9)
    # a %while spans its body: the union must not count nested time twice
    assert recorded.busy_s(0) == pytest.approx(0.130180724, rel=1e-6)
    assert 0 < recorded.idle_share(0) < 0.01
    assert recorded.busy_s(0) <= recorded.window_s


def test_recorded_mosaic_kernels(recorded):
    secs, n = recorded.kernel_seconds(0, lambda t: T.MOSAIC in t)
    assert n == 8                      # one flash forward call a layer
    assert secs == pytest.approx(0.0346521, rel=1e-6)
    top = recorded.op_seconds(0, 3)
    assert top[0][0] == "%closed_call.15 (mosaic)"
    assert all(not name.startswith("%while") for name, _ in
               recorded.op_seconds(0, 50))


def test_recorded_idle_gap_named_by_host_span(recorded):
    gaps = dict(recorded.idle_gaps(0))
    assert gaps["HybridTrainStep.__call__"] == pytest.approx(2.01665e-4,
                                                             rel=1e-3)
    idle = recorded.window_s - recorded.busy_s(0)
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)


def hand_made():
    ms = 1e6
    dev0 = [["%fusion.1 = f32[8] fusion()", 0 * ms, 10 * ms],
            ["%all-reduce.1 = f32[8] all-reduce()", 8 * ms, 6 * ms],
            ["%while.1 = () while()", 20 * ms, 10 * ms],
            ["%fusion.2 = f32[8] fusion()", 21 * ms, 4 * ms],
            ["%k.3 = f32[8] custom-call(), " + T.MOSAIC, 25 * ms, 5 * ms]]
    dev1 = [["%fusion.1 = f32[8] fusion()", 0 * ms, 5 * ms]]
    host = [["Engine.step", 13 * ms, 8 * ms], ["Engine.submit", 31 * ms, 2 * ms]]
    return T.Trace({0: {"ops": dev0, "modules": []},
                    1: {"ops": dev1, "modules": []}}, host, [0, 40 * ms])


def test_hand_made_numbers():
    t = hand_made()
    assert t.window_s == pytest.approx(0.040)
    assert t.busy_s(0) == pytest.approx(0.024)          # 0-14 and 20-30
    assert t.idle_share(0) == pytest.approx(0.4)
    assert t.idle_share(1) == pytest.approx(0.875)
    assert t.kernel_seconds(0, lambda x: T.MOSAIC in x) == (
        pytest.approx(0.005), 1)
    gaps = dict(t.idle_gaps(0))
    assert gaps["Engine.step"] == pytest.approx(0.006)   # 14-20, middle 17
    assert gaps["(no span)"] == pytest.approx(0.010)     # 30-40, middle 35
    assert t.used_devices() == [0, 1]


def test_program_spans_are_kept_and_name_no_gap(tmp_path):
    """``from_xplane`` keeps a host event under one of the benchmark's own
    span names or under the program's prefix ``pt.``, and drops the rest; a
    gap is still named by the benchmark's span, though the program's nest
    inside it."""
    import jax
    from jax.profiler import TraceAnnotation
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("the window"):
        with TraceAnnotation("Engine.step"):
            with TraceAnnotation("pt.serve.step"):
                with TraceAnnotation("pt.serve.feed", kind="decode"):
                    jax.block_until_ready(jax.numpy.ones(8) + 1)
        with TraceAnnotation("somebody else's span"):
            pass
    jax.profiler.stop_trace()
    t = T.Trace.from_xplane(T.newest_xplane(str(tmp_path)), ["Engine.step"],
                            "the window")
    names = [h[0] for h in t.host]
    assert sorted(names) == ["Engine.step", "pt.serve.feed#kind=decode#",
                             "pt.serve.step"]

    ms = 1e6
    h = hand_made()
    before = h.idle_gaps(0)
    h.host += [["pt.serve.step", 13 * ms, 8 * ms],
               ["pt.serve.wait#kind=decode#", 16 * ms, 3 * ms]]
    assert h.idle_gaps(0) == before
    assert dict(before)["Engine.step"] == pytest.approx(0.006)


def test_window_clips_operations():
    t = hand_made()
    t.window = [5e6, 9e6]
    assert t.busy_s(0) == pytest.approx(0.004)
    assert t.kernel_seconds(0, lambda x: x.startswith("%all-reduce")) == (
        pytest.approx(0.001), 1)


def test_plain_form_round_trip(recorded):
    again = T.Trace.from_json(json.loads(json.dumps(recorded.to_json())))
    assert again.busy_s(0) == recorded.busy_s(0)
