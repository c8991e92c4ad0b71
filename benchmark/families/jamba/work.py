"""Required work of the jamba decoder, from its shapes alone: what
``serve_mfu``, ``decode_bytes_roofline``, ``ssm_scan_roofline``,
``ssm_step_roofline`` and ``paged_mqa_decode_roofline`` read. They count the same whatever implements a layer:
padding, a cast or a recomputation is not required work.

A Mamba layer's selective scan, for every position and every element of its
``[d_state, d_inner]`` state: ``dt A`` and ``exp``, the decay of the state
and the input ``(dt x) B`` added to it, and the readout's multiply-add with
``C``: 6 FLOPs (the ``exp`` not counted). Its convolution is ``d_conv``
multiply-adds a channel.

``cfg`` is a configuration file's dict under the published key names."""

STATE_BYTES = 4              # the recurrent state is float32
SCAN_FLOPS = 6               # an element of the state a position


def _head_dim(cfg):
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def _d_inner(cfg):
    return cfg["mamba_expand"] * cfg["hidden_size"]


def ssm_layers(cfg):
    """How many layers are Mamba layers."""
    return sum(1 for l in range(cfg["num_hidden_layers"])
               if l % cfg["attn_layer_period"] != cfg["attn_layer_offset"])


def _attention_layers(cfg):
    return cfg["num_hidden_layers"] - ssm_layers(cfg)


def _mamba_matrices(cfg):
    """W_in, W_x, W_dt and W_out of one Mamba mixer."""
    H, Di = cfg["hidden_size"], _d_inner(cfg)
    N, R = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    return H * 2 * Di + Di * (R + 2 * N) + R * Di + Di * H


def _mamba_vectors(cfg):
    """The taps, the conv and dt biases, A_log, D and the three norms."""
    Di, N, R = _d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    return (cfg["mamba_d_conv"] + 3) * Di + N * Di + R + 2 * N


def _attention_matrices(cfg):
    H, d = cfg["hidden_size"], _head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return H * (nq + 2 * nkv) + nq * H


def matrix_params(cfg):
    """Parameters every token meets in a product: every layer's mixer and
    MLP, and the head once (tied to the embedding, whose look-up is no
    matmul)."""
    H = cfg["hidden_size"]
    mlp = 3 * H * cfg["intermediate_size"]
    return (ssm_layers(cfg) * (_mamba_matrices(cfg) + mlp)
            + _attention_layers(cfg) * (_attention_matrices(cfg) + mlp)
            + cfg["vocab_size"] * H)


def all_params(cfg):
    """Every parameter: the matrices, the Mamba layers' vectors and the
    norms (two a layer and the final one)."""
    H = cfg["hidden_size"]
    return matrix_params(cfg) + ssm_layers(cfg) * _mamba_vectors(cfg) \
        + (2 * cfg["num_hidden_layers"] + 1) * H


def serve_flops(cfg, ctx_positions, tokens):
    """Forward FLOPs of ``tokens`` processed tokens (prompt and output alike)
    whose causal prefixes hold ``ctx_positions`` positions in sum: 2 FLOPs a
    matrix parameter a token, each Mamba layer's convolution and selective
    scan a token, and in each attention layer, for every position attended,
    the two products of a query head's scores and values."""
    Di, N = _d_inner(cfg), cfg["mamba_d_state"]
    per_token = 2 * matrix_params(cfg) + ssm_layers(cfg) * (
        2 * cfg["mamba_d_conv"] * Di + SCAN_FLOPS * N * Di)
    per_position = 2 * cfg["num_attention_heads"] * 2 * _head_dim(cfg)
    return per_token * tokens \
        + per_position * _attention_layers(cfg) * ctx_positions


def _state_bytes(cfg, bytes_per_el):
    """One slot's two states in every Mamba layer, read and written: the
    recurrent state (float32) and the convolution's last ``d_conv - 1``
    rows (the compute type)."""
    Di = _d_inner(cfg)
    return 2 * ssm_layers(cfg) * (
        cfg["mamba_d_state"] * Di * STATE_BYTES
        + (cfg["mamba_d_conv"] - 1) * Di * bytes_per_el)


def decode_bytes(cfg, dispatches, touched_experts, ctx_positions,
                 bytes_per_el=2):
    """The least bytes that ``dispatches`` decode dispatches must read and
    write: every parameter once a dispatch (the head with them), the K and
    V rows of the positions that the live contexts hold in the attention
    layers (``ctx_positions``, summed over decode tokens), and the Mamba
    layers' states of the live slots: a dispatch has at least one, and of
    these arguments no more can be said, so one slot's states a dispatch
    are counted (``ssm_step_roofline`` reads the states of every live slot
    by the program's counter). ``touched_experts`` is no quantity of this
    family (one dense MLP a layer)."""
    row = 2 * cfg["num_key_value_heads"] * _head_dim(cfg)
    return dispatches * (bytes_per_el * all_params(cfg)
                         + _state_bytes(cfg, bytes_per_el)) \
        + bytes_per_el * row * _attention_layers(cfg) * ctx_positions


def decode_attention_work(cfg, ctx_positions, steps_slots, bytes_per_el=2):
    """The decode read of the attention layers, summed over decode steps:
    ``ctx_positions`` is the sum over decode steps and live slots of the
    positions the slot's context holds; K and V of those once a step, and
    every query head's two products with them. ``steps_slots`` (the sum of
    live slots over steps) adds q and o."""
    d, La = _head_dim(cfg), _attention_layers(cfg)
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    flops = 4 * La * nq * ctx_positions
    nbytes = La * (2 * nkv * ctx_positions + 2 * nq * steps_slots) \
        * bytes_per_el
    return flops, nbytes


def _kernel_work(cfg, positions, calls):
    """(FLOPs, bytes) of the selective-scan kernels over ``positions``
    (summed over Mamba layers as ``positions`` x layers) in ``calls`` calls:
    the scan's FLOPs; its operands and output a position (``dt`` and ``dt x``
    and ``y`` over the channels, B and C over the state, float32) and ``A``
    a call; the state once in and once out is added by the caller."""
    Di, N = _d_inner(cfg), cfg["mamba_d_state"]
    L = ssm_layers(cfg)
    flops = SCAN_FLOPS * N * Di * L * positions
    nbytes = STATE_BYTES * (L * positions * (3 * Di + 2 * N)
                            + calls * N * Di)
    return flops, nbytes


def ssm_scan_work(cfg, positions, calls):
    """``ssm_scan``: ``positions`` real positions of [1, T] chunk
    dispatches, ``calls`` kernel calls (a dispatch's Mamba layers each): the
    chunk's slot's state read once and written once a call."""
    flops, nbytes = _kernel_work(cfg, positions, calls)
    state = cfg["mamba_d_state"] * _d_inner(cfg) * STATE_BYTES
    return flops, nbytes + 2 * calls * state


def ssm_step_work(cfg, slot_steps, calls):
    """``ssm_step``: ``slot_steps`` live slots summed over decode
    dispatches, ``calls`` kernel calls: a live slot's state read and written
    in every Mamba layer, its one position's operands."""
    flops, nbytes = _kernel_work(cfg, slot_steps, calls)
    state = cfg["mamba_d_state"] * _d_inner(cfg) * STATE_BYTES
    return flops, nbytes + 2 * ssm_layers(cfg) * slot_steps * state
