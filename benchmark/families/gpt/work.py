"""Required work of a GPT-class decoder, from its shapes alone. Both MFUs
and both kernel rooflines read these functions, and they count the same
whatever implements a layer: recomputed work, padding and casts are not
required work.

``cfg`` is a configuration file's dict (hidden_size, num_layers, num_heads,
vocab_size)."""


def matmul_params(cfg):
    """Parameters that take part in a matrix multiplication for every token:
    the four block GEMMs (12 H^2 a layer) and the LM head once. Embedding
    look-ups are no matmul."""
    H, L, V = cfg["hidden_size"], cfg["num_layers"], cfg["vocab_size"]
    return 12 * L * H * H + V * H


def train_flops_per_token(cfg, seq):
    """Forward + backward: 6 FLOPs a matmul parameter, plus causal attention.
    Non-causal attention is 4*S*H FLOPs a token a layer forward (QK^T and PV);
    the causal half of it, times 3 for forward and backward: 6*L*H*S."""
    H, L = cfg["hidden_size"], cfg["num_layers"]
    return 6 * matmul_params(cfg) + 6 * L * H * seq


def serve_flops(cfg, ctx_positions, tokens):
    """Forward FLOPs of ``tokens`` processed tokens (prompt and output alike)
    whose causal prefixes hold ``ctx_positions`` positions in sum: 2 FLOPs a
    matmul parameter a token, 4*L*H for every position attended."""
    H, L = cfg["hidden_size"], cfg["num_layers"]
    return 2 * matmul_params(cfg) * tokens + 4 * L * H * ctx_positions


def attention_train_work(cfg, batch, seq, bytes_per_el=2):
    """Causal attention forward + backward over the whole model for one step:
    (flops, bytes). Bytes: forward reads q, k, v and writes o; backward reads
    q, k, v, o, do and writes dq, dk, dv: 12 tensors of [batch, seq, H]."""
    H, L = cfg["hidden_size"], cfg["num_layers"]
    flops = 6 * L * H * seq * batch * seq
    nbytes = 12 * L * batch * seq * H * bytes_per_el
    return flops, nbytes


def decode_attention_work(cfg, ctx_positions, steps_slots, bytes_per_el=2):
    """The decode kernel over the whole model, summed over decode steps:
    ``ctx_positions`` is the sum over decode steps and live slots of the
    positions the slot's context holds; K and V of those once per step.
    ``steps_slots`` (sum of live slots over steps) adds q and o."""
    H, L = cfg["hidden_size"], cfg["num_layers"]
    flops = 4 * L * H * ctx_positions
    nbytes = (2 * L * H * ctx_positions + 2 * L * H * steps_slots) * bytes_per_el
    return flops, nbytes
