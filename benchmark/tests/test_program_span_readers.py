"""The readers of the program's own spans and counters (the ``pt.serve.*``
phase clock of ``Engine.step``, the named flash kernels) on a hand-made
context whose numbers can be worked out on paper: the expected number, None
off the chip, None on a zero divisor, and None against a program that has no
such counter or names no kernel (the parent of the PR that brought them)."""
import types

import pytest

from benchmark.harness import loader, spans as spans_mod, trace as T

COUNTERS = {
    "paged_steps": 14, "chunk_steps": 4, "boundaries": 10,
    "decode_time_s": 1.1, "prefill_time_s": 0.32,
    "step_s": 1.5, "admit_s": 0.01, "feed_s": 0.05, "wait_s": 1.38,
    "emit_s": 0.02,
    "admit_queue_wait_s": 0.012, "admit_queue_waits": 3,
    "prefill_span_s": 4.5, "first_tokens": 2,
}
EXPECTED = {
    "decode_step_ms": 110.0,        # 1.1 s over 14 - 4 decode dispatches
    "chunk_step_ms": 80.0,          # 0.32 s over 4 chunk dispatches
    "host_admit_ms": 1.0, "host_feed_ms": 5.0, "host_emit_ms": 2.0,
    "device_wait_share": 92.0,      # 1.38 of 1.5
    "queue_wait_ms": 4.0, "prefill_span_s": 2.25,
}
DIVISOR = {
    "decode_step_ms": {"paged_steps": 4}, "chunk_step_ms": {"chunk_steps": 0},
    "host_admit_ms": {"boundaries": 0}, "host_feed_ms": {"boundaries": 0},
    "host_emit_ms": {"boundaries": 0}, "device_wait_share": {"step_s": 0.0},
    "queue_wait_ms": {"admit_queue_waits": 0},
    "prefill_span_s": {"first_tokens": 0},
}
# what the window's difference of a program without the phase clock holds
PARENT_KEYS = ("paged_steps", "chunk_steps", "boundaries", "decode_time_s",
               "prefill_time_s")


def make_ctx(counters=None, on_chip=True, trace=None, steps=2):
    sp = spans_mod.Spans()
    for i in range(steps):
        sp.rows.append(("HybridTrainStep.__call__", float(i), i + 0.9))
    return types.SimpleNamespace(counters=dict(counters or {}),
                                 on_chip=on_chip, trace=trace, spans=sp,
                                 traced=(0.0, 100.0))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_serving_reader(name):
    read = loader.load_reader(name)
    assert read(make_ctx(COUNTERS)) == pytest.approx(EXPECTED[name])
    assert read(make_ctx(COUNTERS, on_chip=False)) is None
    assert read(make_ctx({**COUNTERS, **DIVISOR[name]})) is None
    assert read(make_ctx({})) is None


@pytest.mark.parametrize("name", sorted(set(EXPECTED)
                                        - {"decode_step_ms", "chunk_step_ms"}))
def test_serving_reader_is_silent_on_a_program_without_the_clock(name):
    parent = {k: COUNTERS[k] for k in PARENT_KEYS}
    assert loader.load_reader(name)(make_ctx(parent)) is None


def kernel_trace(named=True):
    """Two devices, a window of 40 ms: two named Mosaic calls and one
    unnamed on device 0, the named ones again, longer, on device 1."""
    ms = 1e6
    fwd, again, dq, dkv = ("%flash_fwd.14", "%flash_fwd.15",
                           "%flash_bwd_dq.10", "%flash_bwd_dkv.10") \
        if named else ("%closed_call.15", "%rematted_computation.10",
                       "%checkpoint.21", "%checkpoint.20")
    tail = " = bf16[8] custom-call(), " + T.MOSAIC

    def ops(k):
        return [[fwd + tail, 0 * ms, 4 * k * ms],
                [again + tail, 10 * ms, 2 * k * ms],
                [dq + tail, 15 * ms, 3 * k * ms],
                [dkv + tail, 20 * ms, 5 * k * ms],
                ["%other_kernel.3" + tail, 30 * ms, 1 * ms],
                ["%flash_fwd_lookalike.1 = bf16[8] fusion()", 32 * ms, 1 * ms]]
    return T.Trace({0: {"ops": ops(1), "modules": []},
                    1: {"ops": ops(1.5), "modules": []}}, [], [0, 40 * ms])


def test_flash_readers_on_named_kernels():
    ctx = make_ctx(trace=kernel_trace(), steps=2)
    # forward: (4 + 2) ms on device 0, 9 ms on device 1: mean 7.5 over 2 steps
    assert loader.load_reader("flash_fwd_ms")(ctx) == pytest.approx(3.75)
    # backward: (3 + 5) ms and 12 ms: mean 10 over 2 steps
    assert loader.load_reader("flash_bwd_ms")(ctx) == pytest.approx(5.0)


@pytest.mark.parametrize("name", ["flash_fwd_ms", "flash_bwd_ms"])
def test_flash_readers_find_nothing(name):
    read = loader.load_reader(name)
    assert read(make_ctx(trace=None)) is None
    assert read(make_ctx(trace=kernel_trace(), on_chip=False)) is None
    assert read(make_ctx(trace=kernel_trace(), steps=0)) is None
    # a program that names no kernel: its Mosaic calls carry other names
    assert read(make_ctx(trace=kernel_trace(named=False))) is None


def test_flash_split_adds_up_to_what_flash_roofline_divides_by():
    """Forward and backward together are every Mosaic call of the step but
    the one unnamed."""
    tr = kernel_trace()
    ctx = make_ctx(trace=tr, steps=2)
    both = loader.load_reader("flash_fwd_ms")(ctx) \
        + loader.load_reader("flash_bwd_ms")(ctx)
    every = sum(tr.kernel_seconds(d, lambda t: T.MOSAIC in t)[0]
                for d in tr.used_devices()) / 2 / 2
    assert 1e3 * every - both == pytest.approx(1.0 / 2)   # %other_kernel
