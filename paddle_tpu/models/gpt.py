"""GPT — flagship decoder-only LM.

Capability target: the reference's Fleet GPT-3 pretraining stack
(PaddleNLP GPT + fleet hybrid parallel; ref distributed surface:
python/paddle/distributed/fleet/meta_parallel). Design is TPU-first:

  * pre-LN transformer blocks; QKV fused column-parallel matmul, row-parallel
    output/down projections (GSPMD 'mp' specs from fleet.mp_layers);
  * attention via the pallas flash kernel on TPU (blockwise XLA elsewhere);
  * weights created in fp32, compute dtype bf16 via a config switch (MXU path);
  * `gpt_block_fn` exposes the block as a pure (params, x) function so the
    same weights drive eager, jit, and the pipeline/scan hybrid path.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import nn
from ..nn.layer_base import Layer
from ..nn import functional as F
from ..tensor_impl import Tensor
from ..tensor import manipulation as M
from ..dispatch import apply as _apply
from ..distributed.fleet.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
)
from ..ops.blockwise_attention import blockwise_attention


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    max_seq_len: int = 2048
    ffn_mult: int = 4
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    use_flash: bool = True
    compute_dtype: str = "bfloat16"
    remat: bool = True
    # remat policy preset (distributed/recompute.py POLICIES): "full"
    # recomputes the whole block in backward; "dots"/"dots_no_batch" keep
    # MXU outputs resident and recompute only elementwise ops — faster when
    # HBM has headroom
    remat_policy: str = "full"
    # pallas flash attention tile sizes (the MFU autotune surface)
    flash_block_q: int = 256
    flash_block_k: int = 256
    tie_embeddings: bool = False
    # pipeline-parallel schedule: "1f1b" (O(stages) activation residency,
    # ref fleet/meta_parallel/pipeline_parallel.py:230) or "gpipe"
    pp_schedule: str = "1f1b"
    # virtual pipeline stages per device (interleaved 1F1B / VPP,
    # ref fleet/meta_parallel/pipeline_parallel.py:613)
    pp_interleave: int = 1
    # True when stacked block params are stored in vpp_storage_perm order
    # (set by HybridTrainStep after permuting; callers passing logical-order
    # params to gpt_forward must leave it False)
    vpp_stage_major: bool = False
    # True when qkv_w/qkv_b columns are stored head-major ([nh, 3, d] order
    # instead of [3, nh, d]) — set by HybridTrainStep when the sequence-
    # parallel schedule activates, so a contiguous 1/mp column shard is
    # exactly the q/k/v projections of nh/mp heads (the [3, nh, d] layout
    # interleaves head groups across shard boundaries). Pure storage
    # relabeling: compute is bitwise identical, but params/checkpoints and
    # this flag must travel together.
    qkv_head_major: bool = False


# headline model family (GPT-3 sizes; ref benchmark configs)
GPT_CONFIGS = {
    "gpt3-125M": GPTConfig(hidden_size=768, num_layers=12, num_heads=12),
    "gpt3-345M": GPTConfig(hidden_size=1024, num_layers=24, num_heads=16),
    "gpt3-760M": GPTConfig(hidden_size=1536, num_layers=24, num_heads=16),
    "gpt3-1.3B": GPTConfig(hidden_size=2048, num_layers=24, num_heads=16),
    "gpt3-2.7B": GPTConfig(hidden_size=2560, num_layers=32, num_heads=32),
    "gpt3-6.7B": GPTConfig(hidden_size=4096, num_layers=32, num_heads=32),
    "gpt3-13B": GPTConfig(hidden_size=5120, num_layers=40, num_heads=40),
}


def _attention(q, k, v, use_flash, causal=True, block_q=256, block_k=256,
               mesh=None):
    """q,k,v arrays [B,S,H,D] -> [B,S,H,D]. Routed by the same logged
    predicate as nn.functional (flash_supported) so gating can't drift.

    ``mesh``: the hybrid mesh the caller is being partitioned over by GSPMD.
    A Mosaic kernel cannot be partitioned automatically ("wrap the call in
    a shard_map"), so the kernel runs per shard over the two dims the
    hybrid layout shards and attention is independent in: batch rows over
    'dp', heads over 'mp'. Inside a region that is already manual (the
    pipeline's, the sequence-parallel block's) the call is per shard as is."""
    from ..ops.pallas_kernels.flash_attention import flash_supported
    if use_flash and flash_supported(q.shape, kv_seq=k.shape[1], why="gpt"):
        from ..ops.pallas_kernels.flash_attention import flash_attention_bshd

        def flash(q, k, v):
            return flash_attention_bshd(q, k, v, causal,
                                        block_q=block_q, block_k=block_k)
        if (mesh is not None and mesh.size > 1
                and not jax.sharding.get_abstract_mesh().manual_axes):
            spec = P("dp" if "dp" in mesh.axis_names else None, None,
                     "mp" if "mp" in mesh.axis_names else None, None)
            flash = jax.shard_map(flash, mesh=mesh, in_specs=(spec,) * 3,
                                  out_specs=spec, check_vma=False)
        return flash(q, k, v)
    return blockwise_attention(q, k, v, causal=causal)


class GPTAttention(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.cfg = config
        h = config.hidden_size
        self.qkv_proj = ColumnParallelLinear(h, 3 * h, gather_output=False)
        self.out_proj = RowParallelLinear(h, h, input_is_parallel=True)

    def forward(self, x):
        cfg = self.cfg
        B, S, Hd = x.shape
        nh = cfg.num_heads
        d = Hd // nh
        qkv = self.qkv_proj(x)
        use_flash = cfg.use_flash

        def attn(qkv_arr):
            q, k, v = jnp.split(qkv_arr.reshape(B, S, 3, nh, d), 3, axis=2)
            out = _attention(q[:, :, 0], k[:, :, 0], v[:, :, 0], use_flash)
            return out.reshape(B, S, nh * d)

        ctx = _apply(attn, qkv, op_name="flash_attention")
        return self.out_proj(ctx)


class GPTMLP(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h = config.hidden_size
        inner = config.ffn_mult * h
        self.up_proj = ColumnParallelLinear(h, inner, gather_output=False)
        self.down_proj = RowParallelLinear(inner, h, input_is_parallel=True)

    def forward(self, x):
        return self.down_proj(F.gelu(self.up_proj(x), approximate=True))


class GPTBlock(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_1 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_epsilon)
        self.mlp = GPTMLP(config)
        self.dropout = nn.Dropout(config.dropout)

    def forward(self, x):
        x = x + self.dropout(self.attn(self.ln_1(x)))
        x = x + self.dropout(self.mlp(self.ln_2(x)))
        return x


class GPTModel(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.wte = VocabParallelEmbedding(config.vocab_size, config.hidden_size,
                                          weight_attr=nn.ParamAttr(initializer=init))
        self.wpe = nn.Embedding(config.max_seq_len, config.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.drop = nn.Dropout(config.dropout)
        self.h = nn.LayerList([GPTBlock(config) for _ in range(config.num_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids):
        B, S = input_ids.shape
        pos = Tensor(jnp.arange(S, dtype=jnp.int32)[None, :])
        x = self.wte(input_ids) + self.wpe(pos)
        cd = self.config.compute_dtype
        if cd:
            x = x.astype(cd)
        x = self.drop(x)
        from ..distributed.recompute import recompute as _recompute
        for block in self.h:
            if self.config.remat:
                x = _recompute(block, x, policy="dots_no_batch")
            else:
                x = block(x)
        return self.ln_f(x)


class GPTForCausalLM(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if config.tie_embeddings:
            self.lm_head = None
        else:
            init = nn.initializer.Normal(0.0, config.initializer_range)
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=False,
                weight_attr=nn.ParamAttr(initializer=init))

    def forward(self, input_ids):
        hidden = self.gpt(input_ids)
        hidden = hidden.astype("float32")
        if self.lm_head is None:  # tied embeddings
            return F.linear(hidden, M.transpose(self.gpt.wte.weight, [1, 0]))
        return self.lm_head(hidden)

    def loss(self, logits, labels):
        """Next-token LM loss; logits [B,S,V], labels [B,S]."""
        V = logits.shape[-1]
        lg = M.reshape(logits[:, :-1, :], [-1, V])
        lb = M.reshape(labels[:, 1:], [-1])
        return F.cross_entropy(lg, lb)

    def fused_loss(self, input_ids):
        """Next-token LM loss straight from hidden states — the LM-head
        matmul and softmax-CE are fused so the fp32 [B,S,V] logits buffer
        never exists (ref fused softmax_with_cross_entropy capability,
        python/paddle/nn/functional/loss.py). Routed through dispatch.apply
        so eager ``loss.backward()`` records the op (via its custom_vjp) on
        the tape. Under tensor parallelism (mp>1) the head is vocab-sharded
        and the chunked scan would defeat that sharding, so this falls back
        to the plain sharded-logits path — same guard as gpt_hybrid."""
        from ..ops.fused_ce import fused_lm_loss
        from ..distributed import env as dist_env
        from ..tensor_impl import as_tensor_data
        mesh = dist_env.get_mesh()
        if mesh is not None and mesh.shape.get("mp", 1) > 1:
            return self.loss(self(input_ids), input_ids)
        hidden = self.gpt(input_ids)
        w = self.gpt.wte.weight if self.lm_head is None else self.lm_head.weight
        ids = as_tensor_data(input_ids)
        transpose = self.lm_head is None

        def f(h, w_):
            if transpose:
                w_ = w_.T
            return fused_lm_loss(h, w_.astype(h.dtype), ids)

        return _apply(f, hidden, w, op_name="fused_lm_loss")

    def num_params(self):
        return sum(p.size for p in self.parameters())

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=None, top_p=None, eos_token_id=None,
                 seed=0, num_beams=1, length_penalty=1.0,
                 stop_token_ids=None):
        """Single-XLA-program autoregressive decode with a static KV cache;
        num_beams > 1 switches to beam search (see models/generation.py)."""
        from .generation import generate as _generate
        return _generate(self, input_ids, max_new_tokens, do_sample,
                         temperature, top_k, top_p, eos_token_id, seed,
                         num_beams, length_penalty, stop_token_ids)


def gpt_loss_fn(logits, labels):
    V = logits.shape[-1]
    lg = M.reshape(logits[:, :-1, :], [-1, V])
    lb = M.reshape(labels[:, 1:], [-1])
    return F.cross_entropy(lg, lb)


# ---------------------------------------------------------------------------
# Pure-pytree block function for the pipeline/scan hybrid path: the same math
# as GPTBlock.forward over a {name: array} dict with full logical shapes.
def ln_fp32(x, g, b, eps):
    """fp32 LayerNorm cast back to x.dtype — shared by the block fn and the
    KV-cache decode path (models/generation.py)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g.astype(
        x.dtype) + b.astype(x.dtype)


def gpt_block_prelude_fn(config: GPTConfig, mesh=None):
    """The block minus its final down-projection: (p, x) -> (resid, gact)
    where resid is the post-attention residual stream and gact the gelu
    activation — the (r, x) operands of the boundary GEMM the fused pp
    backend runs in-kernel (fused_collectives.fused_gemm_ppsend). The
    full block is prelude + ``resid + (gact @ down_w + down_b)``."""
    nh = config.num_heads
    eps = config.layer_norm_epsilon

    def ln(x, g, b):
        return ln_fp32(x, g, b, eps)

    def prelude(p, x):
        B, S, H = x.shape
        d = H // nh
        h1 = ln(x, p["ln1_g"], p["ln1_b"])
        qkv = h1 @ p["qkv_w"].astype(x.dtype) + p["qkv_b"].astype(x.dtype)
        if getattr(config, "qkv_head_major", False):
            qkv4 = qkv.reshape(B, S, nh, 3, d)
            q, k, v = qkv4[..., 0, :], qkv4[..., 1, :], qkv4[..., 2, :]
        else:
            q3, k3, v3 = jnp.split(qkv.reshape(B, S, 3, nh, d), 3, axis=2)
            q, k, v = q3[:, :, 0], k3[:, :, 0], v3[:, :, 0]
        ctx = _attention(q, k, v, config.use_flash,
                         block_q=getattr(config, "flash_block_q", 256),
                         block_k=getattr(config, "flash_block_k", 256),
                         mesh=mesh)
        # named residual: remat_policy="save_attn" keeps ctx so the backward
        # pass skips the flash-forward rerun (flash bwd recomputes its own
        # tiles from q/k/v; rerunning fwd for ctx would be pure waste)
        from jax.ad_checkpoint import checkpoint_name
        ctx = checkpoint_name(ctx, "attn_ctx")
        attn_out = ctx.reshape(B, S, H) @ p["out_w"].astype(x.dtype) + \
            p["out_b"].astype(x.dtype)
        x = x + attn_out
        h2 = ln(x, p["ln2_g"], p["ln2_b"])
        up = h2 @ p["up_w"].astype(x.dtype) + p["up_b"].astype(x.dtype)
        up = jax.nn.gelu(up, approximate=True)
        return x, up

    return prelude


def gpt_block_fn(config: GPTConfig, mesh=None):
    """``mesh``: the mesh GSPMD partitions the caller over, if any (see
    ``_attention``)."""
    prelude = gpt_block_prelude_fn(config, mesh)

    def block(p, x):
        x, up = prelude(p, x)
        down = up @ p["down_w"].astype(x.dtype) + p["down_b"].astype(x.dtype)
        return x + down

    return block


def gpt_fused_boundary(config: GPTConfig, meta, rdma):
    """``boundary(last_layer_params, h)`` for ``run_pipeline(boundary=...)``
    (FLAGS_comm_backend='pp=fused'): the stage's LAST block runs with its
    down-projection GEMM fused with the boundary RDMA — the kernel's
    epilogue puts the stage output on the wire to the down-ring neighbor
    directly, returning (stage output, received up-neighbor output)."""
    prelude = gpt_block_prelude_fn(config)
    from ..ops.pallas_kernels import fused_collectives as _fc

    def boundary(p, h):
        B, S, H = h.shape
        resid, gact = prelude(p, h)
        inner = gact.shape[-1]
        y, recv = _fc.fused_gemm_ppsend(
            meta, rdma, (B, S), gact.reshape(B * S, inner),
            p["down_w"].astype(h.dtype), p["down_b"].astype(h.dtype),
            resid.reshape(B * S, H))
        return y.reshape(B, S, H), recv.reshape(B, S, H)

    return boundary


# functional block-param key -> submodule path inside one GPT block. THE
# name table: stack_block_params walks it as attributes, inference's
# _gpt_functional_params as capture_params qualified names ("gpt.h.{i}.{p}")
# — one place to touch when a block parameter is added or renamed.
BLOCK_PARAM_PATHS = {
    "ln1_g": "ln_1.weight", "ln1_b": "ln_1.bias",
    "qkv_w": "attn.qkv_proj.weight", "qkv_b": "attn.qkv_proj.bias",
    "out_w": "attn.out_proj.weight", "out_b": "attn.out_proj.bias",
    "ln2_g": "ln_2.weight", "ln2_b": "ln_2.bias",
    "up_w": "mlp.up_proj.weight", "up_b": "mlp.up_proj.bias",
    "down_w": "mlp.down_proj.weight", "down_b": "mlp.down_proj.bias",
}


def stack_block_params(model: GPTForCausalLM):
    """Collect per-block weights from a GPTForCausalLM into stacked arrays
    [L, ...] for the pipeline path."""
    blocks = list(model.gpt.h)

    def get(b, path):
        for part in path.split("."):
            b = getattr(b, part)
        return b

    return {k: jnp.stack([get(b, p)._data for b in blocks])
            for k, p in BLOCK_PARAM_PATHS.items()}
