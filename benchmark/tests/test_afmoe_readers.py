"""The reader of the afmoe cell's own per-layer metric on hand-made
counters: the expected number, and None from a program that does not count
the pages (the parent of the PR that brought them) or admitted nothing. And
the family's required work on a toy configuration worked out on paper."""
import types

import pytest

from benchmark.harness import loader

CFG = {"hidden_size": 8, "head_dim": 4, "num_attention_heads": 4,
       "num_key_value_heads": 2, "intermediate_size": 16,
       "moe_intermediate_size": 4, "num_hidden_layers": 3,
       "num_dense_layers": 1, "num_experts": 8, "num_shared_experts": 1,
       "num_experts_per_tok": 2, "vocab_size": 32, "sliding_window": 10,
       "layer_types": ["sliding_attention", "full_attention",
                       "sliding_attention"]}


def _ctx(counters):
    return types.SimpleNamespace(counters=counters, on_chip=True)


@pytest.mark.parametrize("counters,want", [
    ({"kv_pages_mapped_window": 4 * 161 * 3, "kv_pages_unwindowed":
      4 * (161 + 400 + 244)}, 60.0),
    ({"kv_pages_mapped_window": 7, "kv_pages_unwindowed": 7}, 100.0),
    ({}, None),
    ({"kv_pages_mapped_window": 0, "kv_pages_unwindowed": 0}, None),
    ({"kv_pages_mapped_full": 12}, None),
], ids=["capped", "never_past_the_ring", "no_counter", "nothing_admitted",
        "no_window_group"])
def test_window_pages_share(counters, want):
    got = loader.load_reader("window_pages_share")(_ctx(counters))
    assert got == (want if want is None else pytest.approx(want))


def test_required_work_of_a_toy_configuration():
    work = loader.load_family("afmoe").work
    attn = 8 * (2 * 16 + 2 * 8) + 16 * 8                 # q, g; k, v; o
    shared = 3 * attn + 3 * 8 * 16 + 2 * (8 * 8 + 3 * 8 * 4) + 32 * 8
    assert work.shared_params(CFG) == shared
    assert work.active_params(CFG) == shared + 2 * 2 * 3 * 8 * 4
    # 5 tokens whose prefixes hold 100 positions: the full layer attends
    # to all 100, the two window layers to at most 10 a token
    per_position = 2 * 4 * 2 * 4
    assert work.serve_flops(CFG, 100, 5) == 2 * work.active_params(CFG) * 5 \
        + per_position * (100 + 2 * 50)
    assert work.serve_flops(CFG, 30, 5) == 2 * work.active_params(CFG) * 5 \
        + per_position * 3 * 30
    # 2 dispatches, 6 touched experts, 100 positions: K and V rows of 2
    # heads of 4 in the full layer; of the window layers only what the sum
    # alone proves (one window's rows)
    assert work.decode_bytes(CFG, 2, 6, 100) == 2 * (
        2 * shared + 6 * 3 * 8 * 4 + 2 * 2 * 4 * (100 + 2 * 10))
