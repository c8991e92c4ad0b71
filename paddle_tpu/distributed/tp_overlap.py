"""Tensor-parallel compute/communication overlap + sequence parallelism.

The GSPMD mp schedule (fleet/mp_layers dist_specs) is reference-shaped: two
blocking all-reduces per transformer block with activations fully replicated
across the mp group. This module makes the mp-axis schedule explicit under
`shard_map` so it can be restructured (papers: T3 arXiv:2401.16677 —
fine-grained overlap of compute & collectives; "Optimizing Distributed ML
Communication with Fused Computation-Collective Operations"
arXiv:2305.06942; Megatron-LM sequence parallelism arXiv:2205.05198):

  * sequence parallelism (`FLAGS_sequence_parallel`): activations between TP
    blocks live seq-sharded at 1/mp size; norms/residuals compute on the
    shard. The two per-block `psum`s become a reduce-scatter after each
    RowParallel matmul and an all-gather before each ColumnParallel matmul —
    same wire bytes as the all-reduce pair (ring AR = RS+AG by
    construction), but per-replica activation memory drops by mp;

  * ring-decomposed overlap (`FLAGS_mp_overlap`, requires sequence
    parallelism): the pre-QKV/FFN all-gather splits into mp-1 `ppermute`
    hops with each chunk's GEMM issued as soon as its shard arrives, and the
    RowParallel GEMM emits partial products chunk-by-chunk into a pipelined
    ring reduce-scatter. Each hop's transfer is independent of the GEMM
    consuming the previous chunk, so XLA's latency-hiding scheduler slides
    ICI transfers under MXU work instead of serializing at a collective.

Everything is gated: with both flags OFF nothing here is consulted and the
compiled program is byte-identical to the GSPMD schedule. The explicit
schedule is static per compiled step, so its wire bytes / collective counts
are computed up front (`gpt_step_record`) and recorded per executed step for
`paddle_tpu.profiler.mp_comm_counters()` — the mp-axis sibling of
grad_comm's dp counters.

jax 0.4.x partitioner note: the block `shard_map` binds EVERY mesh axis
manually (full-manual) — partial-manual regions with a live auto axis crash
XLA's SPMD partitioner on `ppermute`/`all_gather` (verified on 0.4.37), and
full-manual is also what makes shard_map's transpose insert the dp psum for
the replicated weight gradients. `resolve_gpt` therefore requires every
mesh axis besides dp/mp to be size 1.
"""
from __future__ import annotations

import logging
import threading
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

logger = logging.getLogger(__name__)

_warned = set()


def _warn_once(key, msg):
    if key not in _warned:
        _warned.add(key)
        logger.warning(msg)


def _flags():
    from .. import flags as _f
    return _f._FLAGS


def sequence_parallel_requested():
    return bool(_flags().get("FLAGS_sequence_parallel", False))


def mp_overlap_requested():
    return bool(_flags().get("FLAGS_mp_overlap", False))


def mp_backend_requested():
    """The mp-axis comm backend, resolved across FLAGS_comm_backend and the
    legacy flags: None (pure GSPMD, seed path), 'rsag' (sequence-parallel
    layout, whole RS/AG collectives), 'ring' (ppermute decomposition,
    PR 3's overlap), 'fused' (Pallas kernels). Naming mp=ring/fused in
    FLAGS_comm_backend implies the sequence-parallel layout."""
    from . import comm_backend
    req = comm_backend.requested("mp")
    if req is None:
        if not sequence_parallel_requested():
            return None
        return "ring" if mp_overlap_requested() else "rsag"
    if req == "gspmd":
        return "rsag" if sequence_parallel_requested() else None
    return req


def explicit_mp_requested():
    """Whether any flag asks for the explicit (shard_map) mp schedule."""
    return mp_backend_requested() is not None


# ---------------------------------------------------------------------------
# shard-space primitives (called inside a full-manual shard_map; `axis` is
# the bound mp axis name, `n` its static size)


def seq_all_gather(x, axis, n):
    """[B, s, ...] seq-shard -> [B, S, ...] full sequence (one collective)."""
    if n == 1:
        return x
    return lax.all_gather(x, axis, axis=1, tiled=True)


def seq_reduce_scatter(y, axis, n):
    """[B, S, ...] per-device partial -> [B, s, ...] reduced seq-shard."""
    if n == 1:
        return y
    return lax.psum_scatter(y, axis, scatter_dimension=1, tiled=True)


def _ring_perm(n):
    return [(i, (i + 1) % n) for i in range(n)]


def ring_ag_gemm(x, w, axis, n):
    """Fused all-gather+GEMM: x [B, s, H] seq-shard, w [H, F_shard] ->
    [B, S, F_shard], decomposed into mp-1 ppermute hops. The GEMM of the
    chunk in hand never depends on the hop fetching the next chunk, so the
    transfer hides behind MXU work (T3-style)."""
    if n == 1:
        return x @ w
    idx = lax.axis_index(axis)
    B, s, _ = x.shape
    out = jnp.zeros((B, n * s, w.shape[1]), x.dtype)
    perm = _ring_perm(n)
    chunk = x
    for t in range(n):
        src = (idx - t) % n  # owner of the chunk in hand
        out = lax.dynamic_update_slice_in_dim(out, chunk @ w, src * s, axis=1)
        if t < n - 1:
            chunk = lax.ppermute(chunk, axis, perm)
    return out


def gemm_ring_rs(y, w, axis, n):
    """Fused GEMM+reduce-scatter: y [B, S, F_shard], w [F_shard, H] ->
    [B, s, H] reduced seq-shard. The accumulator for chunk c rides the ring
    visiting every device once; each device adds its partial GEMM for the
    chunk currently passing through, so partial products stream into the
    collective chunk-by-chunk instead of materializing [B, S, H]."""
    if n == 1:
        return y @ w
    idx = lax.axis_index(axis)
    B, S, F = y.shape
    s = S // n
    perm = _ring_perm(n)
    acc = None
    for t in range(n):
        c = (idx - t - 1) % n  # chunk finishing at device c+t+1
        part = lax.dynamic_slice_in_dim(y, c * s, s, axis=1) @ w
        acc = part if acc is None else acc + part
        if t < n - 1:
            acc = lax.ppermute(acc, axis, perm)
    return acc


def column_parallel(x, w, b, axis, n, backend, meta=None):
    """Seq-sharded input -> full-seq, feature-sharded output (the all-gather
    'before ColumnParallel'). b is the per-device bias shard (or None).
    backend: 'rsag' (whole collectives), 'ring' (ppermute hops), 'fused'
    (Pallas AG+GEMM kernel — meta is its static RingMeta)."""
    if backend == "fused":
        from ..ops.pallas_kernels import fused_collectives as _fc
        out = _fc.fused_ag_gemm(meta, x, w)
    elif backend == "ring":
        out = ring_ag_gemm(x, w, axis, n)
    else:
        out = seq_all_gather(x, axis, n) @ w
    return out if b is None else out + b


def row_parallel(y, w, b, axis, n, backend, meta=None):
    """Full-seq, feature-sharded input -> seq-sharded reduced output (the
    reduce-scatter 'after RowParallel'). b is the FULL bias, added once
    after the cross-device reduction."""
    if backend == "fused":
        from ..ops.pallas_kernels import fused_collectives as _fc
        out = _fc.fused_gemm_rs(meta, y, w)
    elif backend == "ring":
        out = gemm_ring_rs(y, w, axis, n)
    else:
        out = seq_reduce_scatter(y @ w, axis, n)
    return out if b is None else out + b


# ---------------------------------------------------------------------------
# sequence-parallel GPT block (per-device shards; mirrors gpt.gpt_block_fn)


def qkv_head_major_perm(H, nh):
    """Column permutation [3H] taking the logical [3, nh, d] qkv layout to
    head-major [nh, 3, d]: position (h, a, dd) <- logical column (a, h, dd).
    Head-major is what makes a contiguous 1/mp column shard equal the
    q/k/v projections of exactly nh/mp heads; the logical layout interleaves
    head groups across shard boundaries, so a contiguous shard would
    regroup DIFFERENT columns into heads (a different model)."""
    d = H // nh
    a, h, dd = np.meshgrid(np.arange(3), np.arange(nh), np.arange(d),
                           indexing="ij")
    logical = (a * H + h * d + dd).reshape(3, nh, d)
    return logical.transpose(1, 0, 2).reshape(-1)


def to_qkv_head_major(blocks, H, nh):
    """Permute stacked qkv_w [L, H, 3H] / qkv_b [L, 3H] storage to
    head-major. A pure relabeling: with `config.qkv_head_major` set, every
    consumer indexes the permuted positions, so compute is bitwise
    identical to the logical layout."""
    perm = qkv_head_major_perm(H, nh)
    out = dict(blocks)
    out["qkv_w"] = jnp.asarray(blocks["qkv_w"])[..., perm]
    out["qkv_b"] = jnp.asarray(blocks["qkv_b"])[..., perm]
    return out


def sp_block_fn(config, n, axis="mp", backend="rsag", meta=None):
    """Pure (params, x) block on PER-DEVICE shards: x [B, S/mp, H]; matmul
    weights arrive mp-sharded (qkv_w [H, 3H/mp] head-major, out_w [H/mp, H],
    up_w [H, I/mp], down_w [I/mp, H]); norms/biases-of-row replicated.
    Attention runs heads-parallel (nh/mp heads, full sequence) exactly like
    the GSPMD schedule — only the inter-matmul activation layout changes.
    Requires config.qkv_head_major storage (resolve_gpt gates on it).
    backend selects the collective decomposition ('rsag' | 'ring' |
    'fused' — see FLAGS_comm_backend)."""
    from ..models.gpt import ln_fp32, _attention

    nh = config.num_heads
    eps = config.layer_norm_epsilon

    def block(p, x):
        B, s, H = x.shape
        nh_l = nh // n
        d = H // nh
        h1 = ln_fp32(x, p["ln1_g"], p["ln1_b"], eps)
        qkv = column_parallel(h1, p["qkv_w"].astype(x.dtype),
                              p["qkv_b"].astype(x.dtype), axis, n, backend,
                              meta)
        S = qkv.shape[1]
        qkv4 = qkv.reshape(B, S, nh_l, 3, d)  # head-major local columns
        q, k, v = qkv4[..., 0, :], qkv4[..., 1, :], qkv4[..., 2, :]
        ctx = _attention(q, k, v, config.use_flash,
                         block_q=getattr(config, "flash_block_q", 256),
                         block_k=getattr(config, "flash_block_k", 256))
        from jax.ad_checkpoint import checkpoint_name
        ctx = checkpoint_name(ctx, "attn_ctx")
        attn_out = row_parallel(ctx.reshape(B, S, nh_l * d),
                                p["out_w"].astype(x.dtype),
                                p["out_b"].astype(x.dtype), axis, n, backend,
                                meta)
        x = x + attn_out
        h2 = ln_fp32(x, p["ln2_g"], p["ln2_b"], eps)
        up = column_parallel(h2, p["up_w"].astype(x.dtype),
                             p["up_b"].astype(x.dtype), axis, n, backend,
                             meta)
        up = jax.nn.gelu(up, approximate=True)
        down = row_parallel(up, p["down_w"].astype(x.dtype),
                            p["down_b"].astype(x.dtype), axis, n, backend,
                            meta)
        return x + down

    return block


SP_BLOCK_PARAM_SPECS = {
    "ln1_g": P(None), "ln1_b": P(None),
    "qkv_w": P(None, "mp"), "qkv_b": P("mp"),
    "out_w": P("mp", None), "out_b": P(None),
    "ln2_g": P(None), "ln2_b": P(None),
    "up_w": P(None, "mp"), "up_b": P("mp"),
    "down_w": P("mp", None), "down_b": P(None),
}


def sp_activation_spec(batch_axis="dp"):
    """Inter-block activation layout: batch over dp, sequence over mp."""
    return P(batch_axis, "mp", None)


def make_sp_block(config, mesh, cfg):
    """shard_map-wrapped sequence-parallel block for the gpt_hidden layer
    scan: (layer_params, x[B,S,H] logical) -> x. Full-manual over every mesh
    axis (see module docstring for why partial-manual is not an option on
    jax 0.4.x); axes other than dp/mp are size-1 by `resolve_gpt` gating."""
    block = sp_block_fn(config, cfg.n, axis=cfg.axis, backend=cfg.backend,
                        meta=cfg.kernel_meta(mesh))
    x_spec = sp_activation_spec(cfg.batch_axis)
    return jax.shard_map(
        block, mesh=mesh,
        in_specs=(dict(SP_BLOCK_PARAM_SPECS), x_spec),
        out_specs=x_spec, check_vma=False)


# ---------------------------------------------------------------------------
# gating


@dataclass
class SPConfig:
    axis: str          # mp axis name
    n: int             # mp size
    backend: str       # 'rsag' | 'ring' | 'fused'
    batch_axis: str = "dp"     # None on a mesh without a dp axis

    @property
    def overlap(self):
        """PR 3 compatibility: whether the ring (ppermute) decomposition
        runs. The fused backend overlaps too, but in-kernel."""
        return self.backend == "ring"

    def kernel_meta(self, mesh):
        if self.backend != "fused":
            return None
        from ..ops.pallas_kernels import fused_collectives as _fc
        return _fc.meta_for(mesh, self.axis)


def resolve_gpt(config, mesh, batch=None, seq=None):
    """Decide whether the explicit sequence-parallel schedule applies to a
    gpt_hybrid step. Returns SPConfig or None (None = GSPMD schedule,
    byte-identical to the seed). Every bail warns once with the reason AND
    the exact flag setting that would fix it — the fallback rules
    documented in README ("Communication backends")."""
    backend = mp_backend_requested()
    if backend is None:
        if mp_overlap_requested():
            _warn_once("overlap-needs-sp",
                       "FLAGS_mp_overlap requires FLAGS_sequence_parallel; "
                       "ignoring (GSPMD schedule kept) — set "
                       "FLAGS_sequence_parallel=True (or "
                       "FLAGS_comm_backend='mp=ring') to enable the "
                       "explicit schedule")
        return None
    if mesh is None:
        return None
    mp = mesh.shape.get("mp", 1)
    if mp <= 1:
        return None

    def bail(key, msg):
        _warn_once(key, msg + " — falling back to the GSPMD mp schedule")
        return None

    allowed = ("dp", "mp")
    from . import comm_backend as _cb
    if _cb.pp_explicit_requested():
        # the explicit pipeline (comm_backend.resolve_pp) binds the whole
        # mesh manually and runs the per-shard sp block INSIDE its region —
        # an active pp axis composes instead of blocking the sp schedule
        allowed = ("dp", "mp", "pp")
    extra = [a for a in mesh.axis_names
             if a not in allowed and mesh.shape.get(a, 1) > 1]
    if extra:
        return bail(("axes", tuple(extra)),
                    f"sequence parallelism binds the whole mesh manually; "
                    f"axes {extra} must be size 1 (set them to 1 in "
                    f"create_hybrid_mesh, set FLAGS_comm_backend='pp=ring' "
                    f"to compose an active pp axis, or drop the explicit "
                    f"schedule with FLAGS_comm_backend='mp=gspmd')")
    H = config.hidden_size
    if H % mp or config.num_heads % mp or (config.ffn_mult * H) % mp:
        return bail(("dims", H, config.num_heads, mp),
                    f"hidden {H}/heads {config.num_heads}/ffn not divisible "
                    f"by mp={mp} (choose an mp degree dividing all three)")
    if not getattr(config, "qkv_head_major", False):
        # the sp block reads a contiguous qkv column shard as nh/mp whole
        # heads, which is only true of head-major storage; HybridTrainStep
        # permutes at init — a caller handing logical-layout params would
        # silently compute a different model
        return bail("qkv-layout",
                    "sequence parallelism needs head-major qkv storage "
                    "(config.qkv_head_major; HybridTrainStep sets it up)")
    if seq is not None and seq % mp:
        return bail(("seq", seq, mp),
                    f"sequence {seq} not divisible by mp={mp} (pad the "
                    f"sequence or lower the mp degree)")
    batch_axis = "dp" if "dp" in mesh.axis_names else None
    dp = mesh.shape.get("dp", 1)
    if batch is not None and dp > 1 and batch % dp:
        return bail(("batch", batch, dp),
                    f"batch {batch} not divisible by dp={dp} (adjust the "
                    f"global batch or the dp degree)")
    if backend == "fused":
        from ..ops.pallas_kernels import fused_collectives as _fc
        # lane dims the Mosaic kernels see: hidden (chunk/GEMM lane), the
        # qkv and ffn weight-shard widths
        ok, why = _fc.supported(
            mesh, shapes=(H, 3 * H // mp, config.ffn_mult * H // mp),
            why="mp axis")
        if not ok:
            _warn_once(("fused-mp", tuple(mesh.axis_names)),
                       f"fused mp backend unavailable: {why} — falling back "
                       f"to FLAGS_comm_backend='mp=ring'")
            backend = "ring"
    if backend == "ring" and jax.default_backend() == "cpu" and \
            jnp.dtype(config.compute_dtype or "float32") == jnp.bfloat16:
        # same XLA CPU abort as the bf16 ppermute pipeline (gpt_hidden's
        # pp>1 guard); plain RS/AG sequence parallelism is unaffected
        _warn_once("cpu-bf16-overlap",
                   "mp overlap uses ppermute, which the XLA CPU backend "
                   "cannot partition in bf16 — running sequence parallelism "
                   "without overlap on CPU (use compute_dtype='float32' on "
                   "CPU, or FLAGS_comm_backend='mp=fused' on a single-axis "
                   "mesh)")
        backend = "rsag"
    return SPConfig(axis="mp", n=int(mp), backend=backend,
                    batch_axis=batch_axis)


# ---------------------------------------------------------------------------
# serving entry points: the tensor-parallel serving engine's mp rung
# (serving/mp_forward.py) resolves its collective schedule here, next to
# the training schedule it mirrors


@dataclass(frozen=True)
class ServingMPConfig:
    """Static mp configuration of a serving engine (hashable — it keys the
    engine's memoized executable builders). ``backend`` names the serving
    RUNG: 'gspmd' (whole all-gather collectives — the schedule the
    partitioner would emit for a gather-only program), 'ring' (ppermute
    decomposition) or 'fused' (Pallas in-kernel rings). All three rungs
    run the SAME gather-only math, so engine output is bitwise identical
    across rungs AND to the single-chip engine."""
    axis: str
    n: int
    backend: str       # 'gspmd' | 'ring' | 'fused'
    shard_vocab: bool  # lm head + logits AG sharded over vocab (V % n == 0)

    def kernel_meta(self, mesh):
        if self.backend != "fused":
            return None
        from ..ops.pallas_kernels import fused_collectives as _fc
        return _fc.meta_for(mesh, self.axis)


def resolve_serving(config, mesh, backend=None):
    """Resolve the serving engine's mp schedule for ``mesh`` (a 1-D 'mp'
    mesh; other axes must be size 1). Returns ``ServingMPConfig`` or None
    when mp <= 1. Unlike ``resolve_gpt`` the serving schedule is
    GATHER-ONLY — every GEMM shards its OUTPUT dim and keeps the full
    contraction, so no cross-chip reduction ever happens and the engine's
    bitwise-parity contract with single-chip ``generate_from_params``
    survives sharding. Hard config errors raise (a serving deploy must not
    silently change layout); backend ineligibility degrades one rung with
    a warning naming the fix, like the training resolver."""
    if mesh is None:
        return None
    mp = int(mesh.shape.get("mp", 1))
    if mp <= 1:
        return None
    extra = [a for a in mesh.axis_names
             if a != "mp" and mesh.shape.get(a, 1) > 1]
    if extra:
        raise ValueError(
            f"serving mp mesh must be 1-D over 'mp'; axes {extra} have "
            f"size > 1 (build the replica mesh with "
            f"dist_env.create_single_axis_mesh('mp', n) or "
            f"serving.mp_replica_meshes)")
    H = config.hidden_size
    nh = config.num_heads
    I = config.ffn_mult * H
    if H % mp or nh % mp or I % mp:
        raise ValueError(
            f"serving mp={mp} must divide hidden {H}, heads {nh} and ffn "
            f"{I} (choose an mp degree dividing all three)")
    if backend is None:
        from . import comm_backend
        backend = comm_backend.serving_requested() or "gspmd"
    if backend == "fused":
        from ..ops.pallas_kernels import fused_collectives as _fc
        ok, why = _fc.supported(
            mesh, shapes=(H, 3 * H // mp, I // mp, H // mp),
            why="serving mp")
        if not ok:
            _warn_once(("fused-serving", tuple(mesh.axis_names)),
                       f"fused serving backend unavailable: {why} — "
                       f"falling back to FLAGS_comm_backend='mp=ring'")
            backend = "ring"
    if backend == "ring" and jax.default_backend() == "cpu" and \
            jnp.dtype(config.compute_dtype or "float32") == jnp.bfloat16:
        _warn_once("cpu-bf16-serving-ring",
                   "serving ring rung uses ppermute, which the XLA CPU "
                   "backend cannot partition in bf16 — using whole "
                   "collectives (gspmd rung) on CPU")
        backend = "gspmd"
    shard_vocab = config.vocab_size % mp == 0
    if not shard_vocab:
        _warn_once(("serving-vocab", config.vocab_size, mp),
                   f"vocab {config.vocab_size} not divisible by serving "
                   f"mp={mp}: the embedding stays feature-sharded but the "
                   f"lm head and logits stay replicated (pad the vocab to "
                   f"a multiple of mp to shard them)")
    return ServingMPConfig(axis="mp", n=mp, backend=str(backend),
                           shard_vocab=shard_vocab)


def serving_step_record(config, cfg: ServingMPConfig, B, T):
    """Static per-device mp wire ledger of ONE fused serving dispatch at
    window shape [B, T] (decode: [slots, 1]; prefill chunk: [1, rung]).
    Gather-only schedule — per block an AG of the attention context
    (contraction input of the out projection), the out projection's output
    blocks, the FFN activation and the down projection's output blocks,
    plus the embedding AG and (vocab-sharded) the logits AG. Recorded per
    executed dispatch into the SAME counters as the training schedule
    (``profiler.mp_comm_counters``)."""
    n = cfg.n
    item = jnp.dtype(config.compute_dtype or "float32").itemsize
    H = config.hidden_size
    I = config.ffn_mult * H
    L = config.num_layers
    R = B * T

    def ag(F, isz=item):
        # ring all-gather: each device sends its 1/n block to n-1 peers
        return R * F * isz * (n - 1) // n

    rec = MpStepRecord()
    rec.backend = cfg.backend
    total = ag(H) + L * (ag(H) + ag(H) + ag(I) + ag(H))
    colls = 1 + 4 * L
    if cfg.shard_vocab:
        # logits exist only at each slot's LAST position ([B, V] fp32),
        # not per window token — a chunk-prefill dispatch still gathers
        # one row per slot
        total += B * config.vocab_size * 4 * (n - 1) // n
        colls += 1
    rec.ag_bytes = total
    rec.collectives = colls
    rec.bytes_by_kind = {"all_gather": total}
    if cfg.backend == "ring":
        rec.ppermute_hops = colls * (n - 1)
    elif cfg.backend == "fused":
        rec.fused_dispatches = colls
    rec.activation_bytes = R * H * item
    return rec


# ---------------------------------------------------------------------------
# mp_layers routing (Column/RowParallelLinear explicit overlap path)


def layer_schedule(mesh):
    """What the mp layers should do under the current flags/mesh:
    'gspmd' — seed behavior; 'seq' — GSPMD with seq-sharded constraints
    (RS+AG emitted by the partitioner); 'explicit' — route the matmul
    through the shard_map ring kernels; 'fused' — route it through the
    Pallas fused GEMM+collective kernels. Inside an existing SPMD manual
    region (grad_comm's dp step, the pipeline) shard_map cannot nest, so
    the explicit paths degrade to 'seq' there."""
    if mesh is None or mesh.shape.get("mp", 1) <= 1:
        return "gspmd"
    backend = mp_backend_requested()
    if backend is None:
        return "gspmd"
    if backend == "rsag":
        return "seq"
    from .collective import _in_spmd
    if any(_in_spmd(a) for a in mesh.axis_names):
        return "seq"
    extra = [a for a in mesh.axis_names
             if a not in ("dp", "mp") and mesh.shape.get(a, 1) > 1]
    if extra:
        return "seq"
    if backend == "fused":
        from ..ops.pallas_kernels import fused_collectives as _fc
        ok, why = _fc.supported(mesh, shapes=(), why="mp layers")
        if not ok:
            _warn_once(("fused-layers", tuple(mesh.axis_names)),
                       f"fused mp backend unavailable for the mp layers: "
                       f"{why} — falling back to "
                       f"FLAGS_comm_backend='mp=ring'")
            return "explicit"
        return "fused"
    return "explicit"


def layer_shapes_ok(x, w, mesh, column):
    """Whether the explicit ring kernels can take this Column/Row matmul:
    3D activations with mp-divisible sequence and weight shard dims (and a
    dp-divisible batch when dp is active)."""
    if getattr(x, "ndim", 0) != 3:
        return False
    mp = mesh.shape.get("mp", 1)
    dp = mesh.shape.get("dp", 1)
    B, S, _ = x.shape
    if S % mp or (dp > 1 and B % dp):
        return False
    shard_dim = w.shape[1] if column else w.shape[0]
    return shard_dim % mp == 0


def _layer_backend(mesh):
    """Backend + kernel meta for the mp-layer wrappers ('explicit' mode ->
    ring, 'fused' mode -> Pallas kernels)."""
    if layer_schedule(mesh) == "fused":
        from ..ops.pallas_kernels import fused_collectives as _fc
        return "fused", _fc.meta_for(mesh, "mp")
    return "ring", None


def column_linear(x, w, b, mesh, gather_output):
    """Logical-shape ColumnParallelLinear forward on the explicit schedule:
    x [B,S,H] seq-sharded between blocks, w [H, F] mp-sharded on F. The
    bias (mp-sharded on F) is added on the logical output — elementwise, no
    extra collective."""
    mp = int(mesh.shape.get("mp", 1))
    batch_axis = "dp" if mesh.shape.get("dp", 1) > 1 else None
    x_spec = P(batch_axis, "mp", None)
    backend, meta = _layer_backend(mesh)

    def f(xs, ws):
        return column_parallel(xs, ws, None, "mp", mp, backend, meta)

    mapped = jax.shard_map(
        f, mesh=mesh, in_specs=(x_spec, P(None, "mp")),
        out_specs=P(batch_axis, None, "mp"), check_vma=False)
    out = mapped(x, w)
    if b is not None:
        out = out + b
    if gather_output:
        return jax.lax.with_sharding_constraint(
            out, jax.sharding.NamedSharding(mesh, P(batch_axis, None, None)))
    return out


def row_linear(x, w, b, mesh):
    """Logical-shape RowParallelLinear forward on the explicit schedule:
    x [B,S,F] mp-sharded on F, w [F, H] mp-sharded on F; output seq-sharded
    [B,S,H] (the next block's norms/residuals run on the shard). The full
    bias is added once on the logical reduced output."""
    mp = int(mesh.shape.get("mp", 1))
    batch_axis = "dp" if mesh.shape.get("dp", 1) > 1 else None
    backend, meta = _layer_backend(mesh)

    def f(xs, ws):
        return row_parallel(xs, ws, None, "mp", mp, backend, meta)

    mapped = jax.shard_map(
        f, mesh=mesh, in_specs=(P(batch_axis, None, "mp"), P("mp", None)),
        out_specs=P(batch_axis, "mp", None), check_vma=False)
    out = mapped(x, w)
    return out if b is None else out + b


# ---------------------------------------------------------------------------
# static schedule ledger + per-step counters (profiler.mp_comm_counters)


@dataclass
class MpStepRecord:
    """Per-device mp-axis wire traffic of one executed step's forward
    schedule (the backward mirrors it: the transpose of a seq all-gather is
    a seq reduce-scatter and vice versa)."""
    collectives: int = 0          # RS/AG issued (ring counts its hop group)
    ppermute_hops: int = 0        # individual ring hops (ring backend only)
    fused_dispatches: int = 0     # Pallas kernel launches (fused backend)
    backend: str = "gspmd"        # the mp-axis backend that produced this
    rs_bytes: int = 0
    ag_bytes: int = 0
    bytes_by_kind: dict = field(default_factory=dict)
    activation_bytes: int = 0     # inter-block activation residency/device


def gpt_step_record(config, cfg: SPConfig, batch, seq):
    """Ledger of the explicit schedule for one gpt_hybrid step: per block
    an AG before QKV, an RS after the attention output projection, an AG
    before the FFN up-projection, an RS after the down-projection. Under
    the fused backend the same four positions are Pallas kernel launches
    (fused_dispatches) moving the same wire bytes with ZERO XLA-level
    ppermute hops and no HBM-materialized gather buffer."""
    n = cfg.n
    item = jnp.dtype(config.compute_dtype or "float32").itemsize
    s = seq // n
    chunk = batch * s * config.hidden_size * item   # one seq-chunk
    per_coll = (n - 1) * chunk                      # RS and AG move the same
    L = config.num_layers
    rec = MpStepRecord()
    rec.rs_bytes = 2 * L * per_coll
    rec.ag_bytes = 2 * L * per_coll
    rec.collectives = 4 * L
    rec.backend = cfg.backend
    if cfg.backend == "ring":
        rec.ppermute_hops = 4 * L * (n - 1)
    elif cfg.backend == "fused":
        rec.fused_dispatches = 4 * L
    rec.bytes_by_kind = {"reduce_scatter": rec.rs_bytes,
                         "all_gather": rec.ag_bytes}
    rec.activation_bytes = chunk
    return rec


def gspmd_baseline_record(config, mp, batch, seq):
    """What the reference GSPMD schedule moves per step (two ring
    all-reduces of the full [B,S,H] activation per block) — the comparison
    row for tools_tp_smoke's ladder."""
    item = jnp.dtype(config.compute_dtype or "float32").itemsize
    full = batch * seq * config.hidden_size * item
    per_ar = 2 * (mp - 1) * full // mp
    L = config.num_layers
    rec = MpStepRecord()
    rec.collectives = 2 * L
    rec.bytes_by_kind = {"all_reduce": 2 * L * per_ar}
    rec.rs_bytes = 0
    rec.ag_bytes = 0
    rec.activation_bytes = full
    return rec


_lock = threading.Lock()


def _zero_counters():
    return {"steps": 0, "collectives": 0, "ppermute_hops": 0,
            "fused_dispatches": 0, "backend": {},
            "rs_bytes": 0, "ag_bytes": 0, "bytes_by_kind": {},
            "activation_bytes": 0}


_counters = _zero_counters()


def record_step(rec: MpStepRecord | None):
    if rec is None:
        return
    with _lock:
        _counters["steps"] += 1
        _counters["collectives"] += rec.collectives
        _counters["ppermute_hops"] += rec.ppermute_hops
        _counters["fused_dispatches"] += rec.fused_dispatches
        _counters["backend"]["mp"] = rec.backend
        _counters["rs_bytes"] += rec.rs_bytes
        _counters["ag_bytes"] += rec.ag_bytes
        _counters["activation_bytes"] = rec.activation_bytes
        for k, v in rec.bytes_by_kind.items():
            d = _counters["bytes_by_kind"]
            d[k] = d.get(k, 0) + v


def mp_counters():
    with _lock:
        out = dict(_counters)
        out["bytes_by_kind"] = dict(out["bytes_by_kind"])
        out["backend"] = dict(out["backend"])
    out["wire_bytes"] = sum(out["bytes_by_kind"].values())
    return out


def reset_mp_counters():
    global _counters
    with _lock:
        _counters = _zero_counters()
