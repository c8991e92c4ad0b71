"""Required work of the lfm2 decoder, from its shapes alone: what
``serve_mfu`` and ``decode_bytes_roofline`` read. They count the same
whatever implements a layer: an expert that a token was not routed to is not
required work, nor is padding, a cast or a recomputation. A three-tap
convolution is three multiply-adds a channel: its taps count among the
parameters, its gates (elementwise) do not.

``cfg`` is a configuration file's dict under the published key names."""

CONV = "conv"


def _head_dim(cfg):
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def _conv_params(cfg):
    """W_in, the taps and W_out of one conv operator."""
    H = cfg["hidden_size"]
    return H * 3 * H + H * cfg["conv_L_cache"] + H * H


def _attention_params(cfg):
    """q, k, v and the output projection of one attention operator."""
    H, d = cfg["hidden_size"], _head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return H * (nq + 2 * nkv) + nq * H


def _expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layer_counts(cfg):
    """(dense layers, expert layers, conv layers, attention layers)."""
    dense = cfg["num_dense_layers"]
    conv = sum(1 for t in cfg["layer_types"] if t == CONV)
    return (dense, cfg["num_hidden_layers"] - dense, conv,
            cfg["num_hidden_layers"] - conv)


def shared_params(cfg):
    """Parameters every token meets in a product, whatever its routing:
    every layer's operator, the dense FFNs, each expert layer's router, and
    the head once (tied to the embedding, whose look-up is no matmul)."""
    H = cfg["hidden_size"]
    dense, moe, conv, attn = _layer_counts(cfg)
    return (conv * _conv_params(cfg) + attn * _attention_params(cfg)
            + dense * 3 * H * cfg["intermediate_size"]
            + moe * H * cfg["num_experts"]
            + cfg["vocab_size"] * H)


def active_params(cfg):
    """``shared_params`` and the experts one token is routed to: the active
    parameters, not the held ones."""
    _, moe, _, _ = _layer_counts(cfg)
    return shared_params(cfg) + moe * cfg["num_experts_per_tok"] \
        * _expert_params(cfg)


def serve_flops(cfg, ctx_positions, tokens):
    """Forward FLOPs of ``tokens`` processed tokens (prompt and output alike)
    whose causal prefixes hold ``ctx_positions`` positions in sum: 2 FLOPs an
    active parameter a token, and in each attention layer, for every
    position attended, the two products of a query head's scores and values.
    A conv layer attends to nothing."""
    _, _, _, attn = _layer_counts(cfg)
    per_position = 2 * cfg["num_attention_heads"] * 2 * _head_dim(cfg)
    return 2 * active_params(cfg) * tokens \
        + per_position * attn * ctx_positions


def decode_bytes(cfg, dispatches, touched_experts, ctx_positions,
                 bytes_per_el=2):
    """The least bytes that ``dispatches`` decode dispatches must read:
    every parameter outside the routed experts (the head with them) once a
    dispatch, each routed expert that got a token once (``touched_experts``:
    the program's counter, summed over expert layers and dispatches), the K
    and V rows of the positions that the live contexts hold in the attention
    layers (``ctx_positions``, summed over decode tokens), and the conv
    layers' state rows of the live slots: a dispatch has at least one, and
    of these arguments no more can be said, so one slot's rows a dispatch
    are counted (``conv_L_cache - 1`` rows of ``hidden_size`` a conv layer: 8
    KB at the published widths, 2 MB for 64 slots of four layers beside 0.6
    GB of such weights, so what is left out is under half a percent)."""
    _, _, conv, attn = _layer_counts(cfg)
    row = 2 * cfg["num_key_value_heads"] * _head_dim(cfg)
    state = conv * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"]
    return bytes_per_el * (dispatches * (shared_params(cfg) + state)
                           + touched_experts * _expert_params(cfg)
                           + row * attn * ctx_positions)
