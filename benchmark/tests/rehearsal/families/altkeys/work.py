"""Required work of the rehearsal's second family: its keys translated."""
from .weights import GPT, as_gpt


def serve_flops(cfg, ctx_positions, tokens):
    return GPT.work.serve_flops(as_gpt(cfg), ctx_positions, tokens)


def train_flops_per_token(cfg, seq):
    return GPT.work.train_flops_per_token(as_gpt(cfg), seq)


def attention_train_work(cfg, batch, seq, bytes_per_el=2):
    return GPT.work.attention_train_work(as_gpt(cfg), batch, seq, bytes_per_el)


def decode_attention_work(cfg, ctx_positions, steps_slots, bytes_per_el=2):
    return GPT.work.decode_attention_work(as_gpt(cfg), ctx_positions,
                                          steps_slots, bytes_per_el)
