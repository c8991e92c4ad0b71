"""Model-FLOP estimators — the SINGLE source for every MFU number.

bench.py, tools_mfu_sweep.py and the live step telemetry
(observability/step_telemetry.py) all consume these, so the offline bench
numbers and the live in-run MFU can never diverge by using different
formulas.
"""
from __future__ import annotations


def peak_flops_bf16(device_kind: str) -> float:
    """Per-chip bf16 peak by device kind (marketing numbers; the MFU
    denominator). A device that is not in the table is an error, never a
    default: an MFU against the wrong peak is a wrong number."""
    dk = (device_kind or "").lower()
    table = {
        "v6": 918e12, "v5p": 459e12, "v5 lite": 197e12, "v5e": 197e12,
        "v4": 275e12, "v3": 123e12, "v2": 45e12,
    }
    for k, v in table.items():
        if k in dk:
            return v
    raise ValueError(
        f"no bf16 peak FLOP/s known for device_kind {device_kind!r}; "
        f"known TPU kinds: {sorted(table)}")


def model_flops_per_token(cfg, seq_len):
    """GPT-family training FLOPs per token: 6N matmul + attention term
    (fwd+bwd). ``cfg`` needs hidden_size / num_layers / vocab_size /
    max_seq_len (GPTConfig or BertConfig-shaped). Returns
    (flops_per_token, n_params)."""
    H, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    n_params = 12 * L * H * H + V * H * 2 + cfg.max_seq_len * H
    attn = 12 * L * H * seq_len  # 2*2*S*H per layer fwd, x3 with bwd
    return 6 * n_params + attn, n_params


def dense_flops_per_token(n_params):
    """Transformer training FLOPs per token from the parameter count alone
    (the 6N rule) — for models counted by their live parameters (BERT in
    tools_mfu_sweep) rather than a config formula."""
    return 6 * int(n_params)


def train_step_flops(cfg, batch, seq_len):
    """Total training FLOPs of one (batch, seq) step — what the live step
    telemetry divides by step wall time for achieved FLOP/s."""
    fpt, n_params = model_flops_per_token(cfg, seq_len)
    return fpt * batch * seq_len, n_params


def mfu(flops, wall_s, peak_flops):
    """Achieved / peak; None when any input is missing or degenerate."""
    if not flops or not wall_s or not peak_flops:
        return None
    return (flops / wall_s) / peak_flops
