"""The readers of the jamba cell's own per-layer metrics, the two selective
scan kernels and the decode read of one KV head against their rooflines, on a hand-made trace whose numbers can
be worked out on paper; None where nothing ran (a program without the
kernels, or counters that a program without the model does not keep),
never 0. And the family's required work on a toy configuration worked out on
paper."""
import types

import pytest

from benchmark.harness import loader, peaks, runner, trace as T

MOSAIC = T.MOSAIC
US = 1000
# 4 layers, attention at 1 of period 4: Mamba layers 0, 2, 3; d_inner 16,
# d_state 4, dt_rank 2
CFG = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
       "intermediate_size": 16, "num_hidden_layers": 4,
       "attn_layer_offset": 1, "attn_layer_period": 4, "mamba_d_state": 4,
       "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 2,
       "vocab_size": 32}
PEAK_BYTES = 819e9


def _trace(names):
    """Device 0 over a window of 1 ms: each named kernel call 2 us, one
    after the other from 10 us, and a fusion between them."""
    ops, at = [], 10 * US
    for name in names:
        ops.append([f"{name} = f32[8] custom-call(), "
                    f"{MOSAIC}", at, 2 * US])
        ops.append(["%fusion.9 = f32[8] fusion()", at + 2 * US, US])
        at += 3 * US
    return T.Trace({0: {"ops": ops, "modules": []}}, [], [0, 1000 * US])


class _Log:
    """What the window's clients saw over the traced part: ``processed``."""

    def __init__(self, decode_tokens, decode_ctx_positions):
        self.p = {"decode_tokens": decode_tokens,
                  "decode_ctx_positions": decode_ctx_positions}

    def processed(self, a, b):
        return dict(self.p)


def _ctx(names, counters, on_chip=True, log=None):
    return types.SimpleNamespace(
        trace=_trace(names), on_chip=on_chip, counters=counters, config=CFG,
        work=runner.Work(loader.load_family("jamba").work),
        peaks=peaks.PEAKS["TPU v5 lite"], traced=(0.0, 1.0),
        facts={"log": log or _Log(6, 600)})


STEPS = ["%ssm_step.30", "%ssm_step.31", "%ssm_step.32"] * 2
SCANS = ["%ssm_scan.30", "%ssm_scan.31", "%ssm_scan.32"]
READS = ["%paged_mqa_decode.7", "%paged_mqa_decode.8"] * 2


def test_ssm_step_roofline_on_paper():
    """Two decode dispatches of three Mamba layers traced (six calls of 2
    us); over the window 8 decode dispatches held 24 live slots, 3 a
    dispatch, so the traced calls carried 6 slot steps: every slot's state
    of 4 x 16 float32 read and written in each of 3 layers, its position's
    dt, dt x, y (16 each) and B, C (4 each), A (64) a call. Bytes bound
    it."""
    counters = {"paged_steps": 10, "chunk_steps": 2, "ssm_step_slots": 24}
    ctx = _ctx(STEPS + SCANS, counters)
    nbytes = 4 * (3 * 6 * (3 * 16 + 2 * 4) + 6 * 64) + 2 * 3 * 6 * 64 * 4
    want = 100 * nbytes / PEAK_BYTES / 12e-6
    got = loader.load_reader("ssm_step_roofline")(ctx)
    assert got == pytest.approx(want)


def test_ssm_scan_roofline_on_paper():
    """One chunk dispatch traced (three calls of 2 us); over the window 4
    chunk dispatches held 200 real positions, 50 a dispatch: each
    position's operands in each of 3 layers, A and the slot's state in and
    out a call."""
    counters = {"paged_steps": 10, "chunk_steps": 4,
                "ssm_scan_positions": 200}
    ctx = _ctx(STEPS + SCANS, counters)
    nbytes = 4 * (3 * 50 * (3 * 16 + 2 * 4) + 3 * 64) + 2 * 3 * 64 * 4
    want = 100 * nbytes / PEAK_BYTES / 6e-6
    got = loader.load_reader("ssm_scan_roofline")(ctx)
    assert got == pytest.approx(want)


def test_paged_mqa_decode_roofline_on_paper():
    """Two decode dispatches traced, each reading the one attention layer
    (four calls of 2 us); the clients saw 6 decode tokens whose contexts
    held 600 positions: K and V of one head of 4 a position, q and o of two
    heads of 4 a token, bf16. Bytes bound it."""
    ctx = _ctx(READS + STEPS, {})
    nbytes = 2 * (2 * 4 * 600 + 2 * 8 * 6)
    want = 100 * nbytes / PEAK_BYTES / 8e-6
    got = loader.load_reader("paged_mqa_decode_roofline")(ctx)
    assert got == pytest.approx(want)


@pytest.mark.parametrize("reader", ["ssm_step_roofline", "ssm_scan_roofline",
                                    "paged_mqa_decode_roofline"])
@pytest.mark.parametrize("names,counters,on_chip,log", [
    ([], {"paged_steps": 10, "chunk_steps": 4, "ssm_step_slots": 24,
          "ssm_scan_positions": 200}, True, None),
    (STEPS + SCANS, {"paged_steps": 10, "chunk_steps": 4}, True,
     _Log(0, 0)),
    (STEPS + SCANS, {}, True, _Log(0, 0)),
    (STEPS + SCANS + READS, {"paged_steps": 10, "chunk_steps": 4,
                             "ssm_step_slots": 24, "ssm_scan_positions": 200},
     False, None),
], ids=["no_kernel_ran", "no_counter", "parent_counters", "off_chip"])
def test_nothing_to_read_is_none_never_zero(reader, names, counters, on_chip,
                                            log):
    got = loader.load_reader(reader)(_ctx(names, counters, on_chip, log))
    assert got is None


def test_required_work_of_a_toy_configuration():
    work = loader.load_family("jamba").work
    H, Di, N, R, F, V = 8, 16, 4, 2, 16, 32
    assert work.ssm_layers(CFG) == 3
    mamba = H * 2 * Di + Di * (R + 2 * N) + R * Di + Di * H
    attn = H * (2 * 4 + 2 * 4) + 2 * 4 * H               # q; k, v; o (d 4)
    mlp = 3 * H * F
    assert work.matrix_params(CFG) == 3 * (mamba + mlp) + attn + mlp + V * H
    vectors = (4 + 3) * Di + N * Di + R + 2 * N
    assert work.all_params(CFG) == work.matrix_params(CFG) + 3 * vectors \
        + 9 * H
    # 5 tokens whose prefixes hold 100 positions: the convolution and the
    # scan of every Mamba layer a token, the attention layer's two products
    # a position attended
    assert work.serve_flops(CFG, 100, 5) == 5 * (
        2 * work.matrix_params(CFG) + 3 * (2 * 4 * Di + 6 * N * Di)) \
        + 2 * 2 * 2 * 4 * 100
    # 2 dispatches over 100 live positions: every parameter, one slot's
    # states (float32 4 x 16 and three bf16 rows of 16) read and written in
    # 3 layers, K and V rows of one head of 4
    assert work.decode_bytes(CFG, 2, 0, 100) == 2 * (
        2 * work.all_params(CFG) + 2 * 3 * (4 * 16 * 4 + 3 * 16 * 2)) \
        + 2 * 2 * 4 * 100
    # the one attention layer's decode read of 100 positions for 5 tokens:
    # two heads' products with each, K and V of one head a position, q and
    # o of two heads a token
    assert work.decode_attention_work(CFG, 100, 5) == (
        4 * 8 * 100, 2 * (2 * 4 * 100 + 2 * 8 * 5))
