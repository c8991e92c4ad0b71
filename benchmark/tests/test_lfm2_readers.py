"""The reader of the lfm2 cell's own per-layer metric on hand-made counters:
the expected number, and None from a program that does not count the bytes
(the parent of the PR that brought them) or admitted nothing. And the
family's required work on a toy configuration worked out on paper."""
import types

import pytest

from benchmark.harness import loader

CFG = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
       "intermediate_size": 16, "moe_intermediate_size": 4,
       "num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
       "num_experts_per_tok": 2, "vocab_size": 32, "conv_L_cache": 3,
       "layer_types": ["conv", "full_attention", "conv", "conv", "conv"]}


def _ctx(counters):
    return types.SimpleNamespace(counters=counters, on_chip=True)


@pytest.mark.parametrize("counters,want", [
    # a request of 30 pages of 16 positions: one attention layer's K and V
    # rows of 8 x 64 bf16 and four conv layers' 8 KB a slot, against five
    # layers' pages
    ({"cache_bytes_bound": 30 * 16 * 2048 + 4 * 8192,
      "cache_bytes_all_paged": 5 * 30 * 16 * 2048,
      "state_slots_bound": 1}, 100 * (983040 + 32768) / 4915200),
    ({"cache_bytes_bound": 7, "cache_bytes_all_paged": 7}, 100.0),
    ({}, None),
    ({"cache_bytes_bound": 0, "cache_bytes_all_paged": 0}, None),
    ({"kv_pages_mapped_full": 12}, None),
], ids=["state_beside_pages", "every_layer_paged", "no_counter",
        "nothing_admitted", "no_state_group"])
def test_state_cache_share(counters, want):
    got = loader.load_reader("state_cache_share")(_ctx(counters))
    assert got == (want if want is None else pytest.approx(want))


def test_required_work_of_a_toy_configuration():
    work = loader.load_family("lfm2").work
    conv = 8 * 24 + 8 * 3 + 8 * 8                       # W_in, taps, W_out
    attn = 8 * (8 + 2 * 4) + 8 * 8                      # q; k, v; o (d = 2)
    shared = 4 * conv + attn + 3 * 8 * 16 + 4 * 8 * 8 + 32 * 8
    assert work.shared_params(CFG) == shared
    assert work.active_params(CFG) == shared + 4 * 2 * 3 * 8 * 4
    # 5 tokens whose prefixes hold 100 positions: only the attention layer
    # attends, to all 100
    per_position = 2 * 4 * 2 * 2
    assert work.serve_flops(CFG, 100, 5) == 2 * work.active_params(CFG) * 5 \
        + per_position * 100
    # 2 dispatches, 6 touched experts, 100 positions: K and V rows of 2
    # heads of 2 in the attention layer, and one slot's state (two rows of
    # 8 in four conv layers) a dispatch
    assert work.decode_bytes(CFG, 2, 6, 100) == 2 * (
        2 * (shared + 4 * 2 * 8) + 6 * 3 * 8 * 4 + 2 * 2 * 2 * 100)
