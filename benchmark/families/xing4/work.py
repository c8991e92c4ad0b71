"""Required work of the xing4 decoder, from its shapes alone: what
``serve_mfu`` and ``decode_bytes_roofline`` read. They count the same
whatever implements a layer: an expert that a token was not routed to is not
required work, nor is padding, a cast or a recomputation.

``cfg`` is a configuration file's dict under the published key names."""


def _attention_params(cfg):
    H, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (H * cfg["q_lora_rank"] + cfg["q_lora_rank"] * nh * qk
            + H * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * nh * (cfg["qk_nope_head_dim"]
                                          + cfg["v_head_dim"])
            + nh * cfg["v_head_dim"] * H)


def _mhc_params(cfg):
    """The two maps of a layer: [n H, n (2 + n)] each."""
    n = cfg["hc_mult"]
    return 2 * n * cfg["hidden_size"] * n * (2 + n)


def _expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layer_counts(cfg):
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def shared_params(cfg):
    """Parameters every token meets in a matrix multiplication, whatever its
    routing: attention and the mHC maps of every layer, the dense FFNs, of
    each expert layer the router and the shared expert, and the head once.
    Embedding look-ups are no matmul."""
    H = cfg["hidden_size"]
    dense, moe = _layer_counts(cfg)
    per_layer = _attention_params(cfg) + _mhc_params(cfg)
    return ((dense + moe) * per_layer
            + dense * 3 * H * cfg["intermediate_size"]
            + moe * (H * cfg["n_routed_experts"]
                     + cfg["n_shared_experts"] * _expert_params(cfg))
            + cfg["vocab_size"] * H)


def active_params(cfg):
    """``shared_params`` and the experts one token is routed to: the active
    parameters, not the held ones."""
    _, moe = _layer_counts(cfg)
    return shared_params(cfg) + moe * cfg["num_experts_per_tok"] \
        * _expert_params(cfg)


def serve_flops(cfg, ctx_positions, tokens):
    """Forward FLOPs of ``tokens`` processed tokens (prompt and output alike)
    whose causal prefixes hold ``ctx_positions`` positions in sum: 2 FLOPs an
    active parameter a token, and for every position attended the two
    products of a head's scores (nope + rope wide) and values, in every
    layer. (Read absorbed off the latent rows the two products are wider;
    the narrower, plain form is what is required.)"""
    nh = cfg["num_attention_heads"]
    per_position = 2 * nh * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                             + cfg["v_head_dim"])
    return 2 * active_params(cfg) * tokens \
        + cfg["num_hidden_layers"] * per_position * ctx_positions


def decode_bytes(cfg, dispatches, touched_experts, ctx_positions,
                 bytes_per_el=2):
    """The least bytes that ``dispatches`` decode dispatches must read:
    every parameter outside the routed experts (the head with them) once a
    dispatch, each routed expert that got a token once (``touched_experts``:
    the program's counter, summed over expert layers and dispatches), and
    the latent row of every position that the live contexts hold
    (``ctx_positions``, summed over decode tokens) in every layer."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return bytes_per_el * (dispatches * shared_params(cfg)
                           + touched_experts * _expert_params(cfg)
                           + cfg["num_hidden_layers"] * row * ctx_positions)
