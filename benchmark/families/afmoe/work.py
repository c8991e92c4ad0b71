"""Required work of the afmoe decoder, from its shapes alone: what
``serve_mfu`` and ``decode_bytes_roofline`` read. They count the same
whatever implements a layer: an expert that a token was not routed to is not
required work, nor is a position outside a window layer's window, padding, a
cast or a recomputation.

``cfg`` is a configuration file's dict under the published key names."""

WINDOW = "sliding_attention"


def _attention_params(cfg):
    """q, k, v, the gate and the output projection of one layer."""
    H, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return H * (2 * nq + 2 * nkv) + nq * H


def _expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layer_counts(cfg):
    dense = cfg["num_dense_layers"]
    return dense, cfg["num_hidden_layers"] - dense


def _window_layers(cfg):
    return sum(1 for t in cfg["layer_types"] if t == WINDOW)


def shared_params(cfg):
    """Parameters every token meets in a matrix multiplication, whatever its
    routing: attention of every layer, the dense FFNs, of each expert layer
    the router and the shared expert, and the head once. Embedding look-ups
    are no matmul."""
    H = cfg["hidden_size"]
    dense, moe = _layer_counts(cfg)
    return ((dense + moe) * _attention_params(cfg)
            + dense * 3 * H * cfg["intermediate_size"]
            + moe * (H * cfg["num_experts"]
                     + cfg["num_shared_experts"] * _expert_params(cfg))
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
    products of a query head's scores and values: every position of the
    prefix in a full layer, at most ``sliding_window`` of them in a window
    layer. The harness gives the prefixes' sum alone, so the cap is applied
    to the sum (``sliding_window x tokens``): exact where every prefix lies on
    one side of the window, over by the prefixes that straddle it otherwise
    (under 2% of the whole at prompts up to three windows long)."""
    per_position = 2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]
    window = _window_layers(cfg)
    full = cfg["num_hidden_layers"] - window
    attended = full * ctx_positions \
        + window * min(ctx_positions, cfg["sliding_window"] * tokens)
    return 2 * active_params(cfg) * tokens + per_position * attended


def decode_bytes(cfg, dispatches, touched_experts, ctx_positions,
                 bytes_per_el=2):
    """The least bytes that ``dispatches`` decode dispatches must read:
    every parameter outside the routed experts (the head with them) once a
    dispatch, each routed expert that got a token once (``touched_experts``:
    the program's counter, summed over expert layers and dispatches), and
    the K and V rows of the positions that the live contexts hold
    (``ctx_positions``, summed over decode tokens): all of them in a full
    layer. A window layer must read ``min(context, sliding_window)`` rows a
    token, but of the sum alone no more can be said than that they are at
    least ``min(ctx_positions, sliding_window)`` (all of it may be one
    token's): that is what is counted, so a window layer's rows, up to
    ``sliding_window`` a live slot a dispatch, are left out of the required
    bytes and the share reads that much low."""
    row = 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
    window = _window_layers(cfg)
    full = cfg["num_hidden_layers"] - window
    rows = full * ctx_positions \
        + window * min(ctx_positions, cfg["sliding_window"])
    return bytes_per_el * (dispatches * shared_params(cfg)
                           + touched_experts * _expert_params(cfg)
                           + row * rows)
