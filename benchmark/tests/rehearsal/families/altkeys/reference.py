"""The plain reference of the rehearsal's second family: its keys translated,
the GPT family's float32 reference underneath."""
from .weights import GPT, as_gpt


def served_logits(cfg, seed, ids, dtype, mm):
    return GPT.reference.served_logits(as_gpt(cfg), seed, ids, dtype, mm)


def loss_and_grads(params, ids, cfg, mm, rows):
    return GPT.reference.loss_and_grads(params, ids, as_gpt(cfg), mm, rows)


decays = GPT.reference.decays
