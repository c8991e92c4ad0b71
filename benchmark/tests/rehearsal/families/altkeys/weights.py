"""A second model family, kept with the rehearsal to prove that a family
arrives as files: its configuration names the sizes otherwise (``d_model``,
``n_layer``, ``n_head``, ``d_ff``, ...), and these four files translate them.
The only model the program trains and serves is its GPT block, so the
translation leads to that family's functions; the harness sees none of it."""
from benchmark.harness import loader

GPT = loader.load_family("gpt")
KEYS = {"d_model": "hidden_size", "n_layer": "num_layers",
        "n_head": "num_heads", "n_positions": "max_seq_len",
        "norm_eps": "layer_norm_epsilon", "init_std": "initializer_range"}


def as_gpt(cfg):
    out = {KEYS.get(k, k): v for k, v in cfg.items() if k != "d_ff"}
    if "d_ff" in cfg:
        out["ffn_mult"] = int(cfg["d_ff"] // cfg["d_model"])
    return out


def make_weights(cfg, seed, dtype, out_shardings=None):
    return GPT.weights.make_weights(as_gpt(cfg), seed, dtype, out_shardings)


def seed_leaves(cfg, key, dtype):
    return GPT.weights.seed_leaves(as_gpt(cfg), key, dtype)


leaf_parts = GPT.weights.leaf_parts
