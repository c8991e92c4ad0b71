"""The readers of the xing4 cell's own per-layer metrics on a hand-made
context whose numbers can be worked out on paper: the expected number, and
None off the chip, without a trace, without the program's counter (the
parent of the PR that brought it) and without a decode dispatch."""
import types

import pytest

from benchmark.harness import loader, trace as T

MS = 1e6
CFG = {"hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 4,
       "qk_rope_head_dim": 2, "v_head_dim": 4, "q_lora_rank": 4,
       "kv_lora_rank": 6, "hc_mult": 2, "first_k_dense_replace": 1,
       "num_hidden_layers": 3, "intermediate_size": 16,
       "moe_intermediate_size": 4, "n_routed_experts": 8,
       "n_shared_experts": 1, "num_experts_per_tok": 2, "vocab_size": 32}
COUNTERS = {"paged_steps": 5, "chunk_steps": 1, "moe_touched_decode": 24,
            "moe_layer_dispatches_decode": 8}


def decode_trace():
    """A window of 100 ms. Two decode dispatches lie whole in it (feed at
    10 and 40 ms, outputs at 30 and 60 ms), a chunk dispatch between them,
    a third decode dispatch ends after the window. The device: a ``%while``
    over two operations of 5 and 10 ms in the first, 15 ms in the second,
    7 ms of the chunk step, 4 ms of the third."""
    host = [["pt.serve.feed#kind=decode#", 10 * MS, 2 * MS],
            ["pt.serve.wait#kind=decode#", 12 * MS, 18 * MS],
            ["pt.serve.feed#kind=chunk#", 30 * MS, 1 * MS],
            ["pt.serve.wait#kind=chunk#", 31 * MS, 9 * MS],
            ["pt.serve.feed#kind=decode#", 40 * MS, 2 * MS],
            ["pt.serve.wait#kind=decode#", 42 * MS, 18 * MS],
            ["pt.serve.feed#kind=decode#", 90 * MS, 2 * MS],
            ["pt.serve.wait#kind=decode#", 92 * MS, 18 * MS]]
    ops = [["%while.1 = (s32[]) while()", 13 * MS, 15 * MS],
           ["%fusion.1 = bf16[8] fusion()", 13 * MS, 5 * MS],
           ["%fusion.2 = bf16[8] fusion()", 18 * MS, 10 * MS],
           ["%fusion.9 = bf16[8] fusion()", 32 * MS, 7 * MS],
           ["%fusion.1 = bf16[8] fusion()", 43 * MS, 15 * MS],
           ["%fusion.1 = bf16[8] fusion()", 93 * MS, 4 * MS]]
    return T.Trace({0: {"ops": ops, "modules": []}}, host, [0, 100 * MS])


def make_ctx(counters=COUNTERS, on_chip=True, trace="decode"):
    from benchmark.families.xing4 import work
    log = types.SimpleNamespace(
        processed=lambda a, b: {"decode_ctx_positions": 1000})
    return types.SimpleNamespace(
        counters=dict(counters), on_chip=on_chip, config=CFG, work=work,
        trace=decode_trace() if trace == "decode" else trace,
        traced=(0.0, 0.1), facts={"log": log},
        peaks={"hbm_bytes_per_s": 1e6})


def test_decode_bytes_roofline_takes_the_devices_time_of_the_decode_dispatches():
    from benchmark.families.xing4 import work
    read = loader.load_reader("decode_bytes_roofline")
    # two dispatches, 24 touched experts over the window's 4: 12 in these
    # two, 1000 positions; 30 ms on the device (the %while spans, it is not
    # counted; the chunk step's 7 ms and the cut dispatch's 4 are not its)
    nbytes = work.decode_bytes(CFG, 2, 12, 1000)
    assert nbytes == 2 * (2 * work.shared_params(CFG) + 12 * 3 * 8 * 4
                          + 3 * 8 * 1000)
    assert read(make_ctx()) == pytest.approx(100.0 * nbytes / 1e6 / 0.030)


@pytest.mark.parametrize("ctx", [
    dict(on_chip=False), dict(trace=None),
    dict(counters={"paged_steps": 5, "chunk_steps": 1}),
    dict(counters={**COUNTERS, "paged_steps": 1}),
    dict(trace=T.Trace({0: {"ops": [["%f.1 = fusion()", 0, MS]],
                            "modules": []}}, [], [0, 100 * MS]))],
    ids=["off_chip", "no_trace", "no_counter", "no_decode_step",
         "no_decode_span"])
def test_decode_bytes_roofline_is_silent(ctx):
    assert loader.load_reader("decode_bytes_roofline")(make_ctx(**ctx)) is None


def test_moe_touched_share():
    read = loader.load_reader("moe_touched_share")
    # 24 touched of 8 experts x 8 expert-layer dispatches
    assert read(make_ctx()) == pytest.approx(37.5)
    assert read(make_ctx(on_chip=False)) is None
    assert read(make_ctx({"moe_layer_dispatches_decode": 8})) is None
    assert read(make_ctx({**COUNTERS, "moe_layer_dispatches_decode": 0})) \
        is None
