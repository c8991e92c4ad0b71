"""GPT hybrid-parallel training step — the flagship performance path.

Capability target: Fleet GPT-3 hybrid-parallel pretraining (TP×PP×DP×sharding,
ref: python/paddle/distributed/fleet/meta_parallel + meta_optimizers). One pure
XLA program per step:

    (params, opt_state, ids, key) -> (loss, new_params, new_opt_state)

Layer stack is STACKED ([L, ...] leaves) and driven by `lax.scan` (single-block
trace => fast compiles, weight-stationary loop) with `jax.checkpoint` remat per
block. Parallelism:
  * dp/sharding — batch sharded P('dp','sharding'? no: batch over 'dp'); ZeRO
    via optimizer-slot sharding over 'sharding';
  * mp (tensor) — qkv/up weights P(..., 'mp'), out/down P('mp', ...), vocab
    embedding and lm head vocab-sharded; XLA inserts the Megatron collectives;
  * pp — stacked blocks sharded P('pp') on the layer axis, executed by the
    scan+ppermute GPipe schedule (distributed/pipeline.py);
  * sp — optional ring attention over the sequence axis.
All params fp32 (or bf16) with fp32 adam moments; compute in bf16 on the MXU.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .gpt import GPTConfig, gpt_block_fn
from ..distributed.pipeline import run_pipeline


def init_gpt_params(config: GPTConfig, key, param_dtype=jnp.float32):
    H = config.hidden_size
    L = config.num_layers
    V = config.vocab_size
    I = config.ffn_mult * H
    k = iter(jax.random.split(key, 20))
    std = config.initializer_range

    def norm(key_, shape):
        return (jax.random.normal(key_, shape, jnp.float32) * std).astype(param_dtype)

    blocks = {
        "ln1_g": jnp.ones((L, H), param_dtype),
        "ln1_b": jnp.zeros((L, H), param_dtype),
        "qkv_w": norm(next(k), (L, H, 3 * H)),
        "qkv_b": jnp.zeros((L, 3 * H), param_dtype),
        "out_w": norm(next(k), (L, H, H)),
        "out_b": jnp.zeros((L, H), param_dtype),
        "ln2_g": jnp.ones((L, H), param_dtype),
        "ln2_b": jnp.zeros((L, H), param_dtype),
        "up_w": norm(next(k), (L, H, I)),
        "up_b": jnp.zeros((L, I), param_dtype),
        "down_w": norm(next(k), (L, I, H)),
        "down_b": jnp.zeros((L, H), param_dtype),
    }
    return {
        "wte": norm(next(k), (V, H)),
        "wpe": norm(next(k), (config.max_seq_len, H)),
        "lnf_g": jnp.ones((H,), param_dtype),
        "lnf_b": jnp.zeros((H,), param_dtype),
        "head_w": norm(next(k), (H, V)),
        "blocks": blocks,
    }


def gpt_params_fingerprint(params):
    """Device-independent uint32 digest of a GPT param tree (the same
    bit-exact fingerprint the sdc sentinel fuses into check steps — see
    distributed/integrity.py). Two trees agree iff their raw bits agree:
    the serving shadow audit's ``audit_ref`` copy, a peer-repaired
    training replica, and a checkpoint round-trip can all be compared
    with one host int instead of a leaf-by-leaf array diff."""
    from ..distributed.integrity import fingerprint_arrays
    return int(jax.device_get(fingerprint_arrays(params)))


def gpt_param_specs(config: GPTConfig, pp=1, zero_stage=1):
    """PartitionSpecs per param. Block leaves get a leading 'pp' axis when
    pipelining; matmul weights shard over 'mp' Megatron-style.

    zero_stage >= 3 (ref: fleet/meta_parallel/sharding/
    group_sharded_stage3.py capability): block matrices additionally shard
    their non-'mp' dim over ('dp','sharding') — FSDP-style. Inside the
    layer scan GSPMD inserts the per-layer all-gather on use (the
    reference's stage-3 prefetch) and turns the weight-grad psum into a
    reduce-scatter; persistent per-chip param bytes drop by dpxsharding."""
    lead = ("pp",) if pp > 1 else (None,)
    z3 = ("dp", "sharding") if zero_stage >= 3 else None
    blocks = {
        "ln1_g": P(*lead, None), "ln1_b": P(*lead, None),
        "qkv_w": P(*lead, z3, "mp"), "qkv_b": P(*lead, "mp"),
        "out_w": P(*lead, "mp", z3), "out_b": P(*lead, None),
        "ln2_g": P(*lead, None), "ln2_b": P(*lead, None),
        "up_w": P(*lead, z3, "mp"), "up_b": P(*lead, "mp"),
        "down_w": P(*lead, "mp", z3), "down_b": P(*lead, None),
    }
    return {
        # wte is NOT hidden-FSDP-sharded at stage 3: a z3 spec turns the
        # embedding lookup into a gather whose output GSPMD can only reshard
        # to the batch-sharded activation layout via full rematerialization
        # (an all-gather of [B,S,H] every step). Vocab-over-mp only: with
        # batch-sharded ids the gather output is born in the right sharding.
        "wte": P("mp", None),
        "wpe": P(),
        "lnf_g": P(), "lnf_b": P(),
        "head_w": P(z3, "mp"),
        "blocks": blocks,
    }


def _lm_loss(logits, ids):
    """Shifted next-token CE in fp32. logits [B,S,V], ids [B,S].

    Kept for the mp>1 path (vocab-sharded logits: per-chip memory is already
    V/mp) and as the numeric reference for the fused loss below."""
    lg = logits[:, :-1].astype(jnp.float32)
    lb = ids[:, 1:]
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, lb[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def gpt_hidden(params, ids, config: GPTConfig, mesh=None, num_microbatches=1):
    """Pure forward to final-layernorm hidden states [B,S,H] (compute dtype).
    Under a mesh with pp>1 uses the pipeline. With FLAGS_sequence_parallel
    and mp>1 the layer scan runs the explicit shard_map schedule
    (distributed/tp_overlap.py): activations between blocks are seq-sharded
    at 1/mp size and each block's two all-reduces become RS+AG (ring-
    decomposed ppermute hops under FLAGS_mp_overlap)."""
    compute = jnp.dtype(config.compute_dtype or "float32")
    B, S = ids.shape
    x = params["wte"].astype(compute)[ids] + \
        params["wpe"].astype(compute)[None, :S]
    from ..distributed import tp_overlap as _tp
    from ..distributed import comm_backend as _cb
    sp = _tp.resolve_gpt(config, mesh, batch=B, seq=S) \
        if mesh is not None else None
    pp = mesh.shape.get("pp", 1) if mesh is not None else 1
    ppc = _cb.resolve_pp(config, mesh, batch=B,
                         num_microbatches=num_microbatches, sp=sp) \
        if pp > 1 else None
    if pp > 1 and ppc is None and sp is not None:
        # resolve_gpt admitted the pp axis on the explicit schedule's
        # behalf, but resolve_pp fell back — the GSPMD pipeline cannot run
        # the per-shard sp block, so both axes run GSPMD this step
        _cb._warn_once("pp-sp-gspmd",
                       "the explicit mp schedule composes with pp>1 only "
                       "through the explicit pp schedule, which just fell "
                       "back (see the pp warning above) — running GSPMD on "
                       "both axes")
        sp = None
    x_spec = None
    if mesh is not None:
        # seq-parallel entry: the vocab-sharded embedding's psum lands
        # directly in the seq-sharded layout (a reduce-scatter, GSPMD-emitted
        # from this constraint) instead of replicating [B,S,H]. Meshes
        # without a dp axis (the single-axis mp meshes interpret-mode fused
        # kernels need) replicate the batch dim.
        batch_axis = "dp" if "dp" in mesh.axis_names else None
        x_spec = _tp.sp_activation_spec(sp.batch_axis) if sp is not None \
            else P(batch_axis, None, None)
        x = jax.lax.with_sharding_constraint(x, NamedSharding(mesh, x_spec))
    block = gpt_block_fn(config, mesh)
    from ..distributed.recompute import POLICIES
    pol_name = getattr(config, "remat_policy", "full") or "full"
    if pol_name not in POLICIES:
        raise ValueError(f"unknown remat_policy {pol_name!r}; "
                         f"choose from {sorted(POLICIES)}")
    if pp > 1:
        if (ppc is None and jax.default_backend() == "cpu"
                and jnp.dtype(compute) == jnp.dtype(jnp.bfloat16)):
            # XLA's CPU backend hard-aborts ("Invalid binary instruction
            # opcode copy", hlo_instruction.cc:1585) PARTITIONING the
            # bf16 ppermute pipeline — fail with a catchable error instead
            # of killing the interpreter. TPU (the real target) is fine,
            # and so is the explicit full-manual schedule (nothing left
            # for the partitioner to partition): bf16 pipelines run on CPU
            # under FLAGS_comm_backend='pp=ring' (or 'pp=fused').
            raise ValueError(
                "pipeline parallelism with compute_dtype='bfloat16' "
                "crashes the XLA CPU backend under the GSPMD pp schedule; "
                "set FLAGS_comm_backend='pp=ring' (the explicit schedule "
                "wires bf16 fine) or compute_dtype='float32' for CPU runs")
        schedule = ppc.schedule if ppc is not None \
            else getattr(config, "pp_schedule", "1f1b")
        pol = POLICIES[pol_name]
        if pol is not None and schedule != "1f1b":
            import warnings
            warnings.warn(
                f"remat_policy={pol_name!r} needs the 1f1b schedule; the "
                "gpipe autodiff path derives recompute from the scan — "
                "falling back to full recompute")
            pol = None
        # 1f1b/VPP: the selective-save policy applies to the per-tick stage
        # vjp (stage-input checkpointing stays; the policy decides which
        # per-layer residuals the tick keeps — e.g. 'dots' pins MXU
        # outputs). The GPipe autodiff path keeps scan-derived recompute.
        # Under VPP the hybrid step stores blocks in vpp_storage_perm order
        # (see HybridTrainStep.__post_init__), so reshaping to chunks is
        # contiguous and needs no cross-device reshard.
        pk = {}
        if ppc is not None:
            # explicit (full-manual) schedule: hand run_pipeline the real
            # stacked-leaf specs and the activation spec so every input is
            # sharded INTO the region — no tensor is replicated-then-
            # repartitioned, so the partitioner never sees the stage
            # selects (the involuntary-remat warnings die structurally)
            blocks_specs = gpt_param_specs(config, pp=pp)["blocks"]
            blocks_specs = {
                k: P(*(a if (a is None or a in mesh.axis_names) else None
                       for a in tuple(s)))
                for k, s in blocks_specs.items()}
            boundary = None
            if ppc.backend == "fused":
                from .gpt import gpt_fused_boundary
                boundary = gpt_fused_boundary(config, ppc.kernel_meta(mesh),
                                              ppc.fused_rdma)
            if sp is not None:
                # per-shard sp block runs UNWRAPPED inside the pipeline's
                # full-manual region (make_sp_block's own shard_map would
                # nest); the pipeline in_specs deliver the mp-sharded
                # weights and seq-sharded activations it expects
                block = _tp.sp_block_fn(config, sp.n, axis=sp.axis,
                                        backend=sp.backend,
                                        meta=sp.kernel_meta(mesh))
            pk = dict(backend=ppc.backend, pp_param_specs=blocks_specs,
                      x_spec=x_spec, wire_dtype=ppc.wire_dtype,
                      boundary=boundary)
        x = run_pipeline(block, params["blocks"], x, num_microbatches, mesh=mesh,
                         schedule=schedule,
                         interleave=getattr(config, "pp_interleave", 1),
                         vpp_stage_major=getattr(config, "vpp_stage_major",
                                                 False),
                         remat_policy=pol, **pk)
    else:
        if sp is not None:
            block = _tp.make_sp_block(config, mesh, sp)
        ck_block = jax.checkpoint(block, policy=POLICIES[pol_name])

        def scan_body(h, layer_params):
            return ck_block(layer_params, h), None
        x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    # final layernorm is elementwise over H — it runs on the seq shard when
    # sequence parallelism is active (the head matmul's all-gather is the
    # first point the full sequence rematerializes)
    return final_ln_fp32(x, params["lnf_g"], params["lnf_b"],
                         config.layer_norm_epsilon).astype(compute)


def final_ln_fp32(x, g, b, eps):
    """Final layernorm in fp32 (shared by the hybrid and stage-3 steps);
    returns fp32 — callers cast back to their compute dtype."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    xn = (xf - mu) * jax.lax.rsqrt(var + eps)
    return xn * g.astype(jnp.float32) + b.astype(jnp.float32)


def gpt_forward(params, ids, config: GPTConfig, mesh=None, num_microbatches=1):
    """Pure forward to logits (inference / mp-sharded loss path)."""
    compute = jnp.dtype(config.compute_dtype or "float32")
    xn = gpt_hidden(params, ids, config, mesh, num_microbatches)
    return xn @ params["head_w"].astype(compute)


@dataclass
class HybridTrainStep:
    """Compiled hybrid-parallel GPT train step."""
    config: GPTConfig
    optimizer: object            # paddle_tpu Optimizer (functional API)
    mesh: object = None
    num_microbatches: int = 1
    param_dtype: object = jnp.float32
    seed: int = 0
    # ZeRO stage on the flagship path: 1 = optimizer slots sharded (via
    # optimizer._shard_opt_states_axis), 3 = + params FSDP-sharded over
    # ('dp','sharding') with per-layer all-gather in the scan
    zero_stage: int = 1
    # host offload of optimizer moments (ref: fleet group_sharded_stage3.py:84
    # cpu offload): slots live in pinned host memory between steps; on TPU the
    # compiled step streams them to HBM for the update and back. Moves the
    # 8-bytes/param fp32 adam moments off the 16G chip — the single-chip
    # enabler for 2.7B-class configs.
    offload: bool = False

    def __post_init__(self):
        key = jax.random.key(self.seed)
        self.params = init_gpt_params(self.config, key, self.param_dtype)
        pp = self.mesh.shape.get("pp", 1) if self.mesh is not None else 1
        V = getattr(self.config, "pp_interleave", 1)
        if pp > 1 and V > 1:
            # stage-major storage so VPP chunk placement == 'pp' sharding;
            # the config flag records the layout for gpt_hidden/run_pipeline
            from ..distributed.pipeline import vpp_storage_perm
            perm = jnp.asarray(
                vpp_storage_perm(self.config.num_layers, pp, V))
            self.params["blocks"] = jax.tree_util.tree_map(
                lambda a: a[perm], self.params["blocks"])
            self.config.vpp_stage_major = True
        mp = self.mesh.shape.get("mp", 1) if self.mesh is not None else 1
        from ..distributed import tp_overlap as _tp
        from ..distributed import comm_backend as _cb
        if self.zero_stage >= 3 and not getattr(self.config, "zero3_params",
                                                False):
            # record FSDP-sharded params on a private config copy so
            # trace-time resolvers (comm_backend.resolve_pp) can see it —
            # the explicit pp schedule cannot emit the per-layer stage-3
            # all-gather and must bail on such steps
            import copy
            self.config = copy.copy(self.config)
            self.config.zero3_params = True
        if (_tp.explicit_mp_requested() and mp > 1
                and (pp == 1 or _cb.pp_explicit_requested())
                and self.config.hidden_size % mp == 0
                and self.config.num_heads % mp == 0):
            # head-major qkv storage so a contiguous 1/mp column shard is
            # whole heads (see tp_overlap.qkv_head_major_perm); the config
            # flag records the layout and makes every block-fn consumer
            # interpret it consistently — even if resolve_gpt later falls
            # back to GSPMD at trace time. The layout flag must travel with
            # THIS instance's permuted params only, and callers often hand
            # in a shared config (GPT_CONFIGS) — mutate a private copy.
            import copy
            self.config = copy.copy(self.config)
            self.params["blocks"] = _tp.to_qkv_head_major(
                self.params["blocks"], self.config.hidden_size,
                self.config.num_heads)
            self.config.qkv_head_major = True
        flat, self._treedef = jax.tree_util.tree_flatten_with_path(self.params)
        self._names = ["/".join(str(p) for p in path) for path, _ in flat]
        self.opt_state = self.optimizer.init_state(self._flat(self.params))
        if getattr(self.optimizer, "_offload_opt_states", False):
            self.offload = True
        from ..framework import offload as _ol
        self._offload_in_jit = _ol.in_jit_transfers_supported()
        if self.mesh is not None:
            self._place()
        if self.offload:
            self.opt_state = self._move_opt(self.opt_state,
                                            self._opt_host_shardings())
        self._jitted = None
        self._step_count = 0
        # live step telemetry (FLAGS_step_telemetry): flops/tokens derive
        # from the config and batch shape at call time — live MFU uses the
        # SAME estimator as bench.py (observability/flops.py)
        from ..observability.step_telemetry import StepSampler
        self._tel = StepSampler("HybridTrainStep")

    # -- host offload helpers (mirror jit/train_step.py) ---------------------
    def _opt_dev_shardings(self):
        if self.mesh is not None:
            mesh = self.mesh
            # recompute the same placement _place() used
            flat_specs = self._flat(self._specs())
            zero_axis = getattr(self.optimizer, "_shard_opt_states_axis", None)

            def spec_of(name, arr):
                if jnp.ndim(arr) == 0:
                    return NamedSharding(mesh, P())
                base = flat_specs[name]
                replicated = all(a is None for a in tuple(base)) \
                    if len(tuple(base)) else True
                if (zero_axis and mesh.shape.get(zero_axis, 1) > 1
                        and replicated
                        and arr.shape[0] % mesh.shape[zero_axis] == 0):
                    return NamedSharding(
                        mesh, P(zero_axis, *([None] * (arr.ndim - 1))))
                return NamedSharding(mesh, base)
            return {"step": NamedSharding(mesh, P()),
                    "slots": {n: {k: spec_of(n, v) for k, v in s.items()}
                              for n, s in self.opt_state["slots"].items()}}
        from ..framework import offload as _ol
        dev = _ol.with_memory_kind(None, "device")
        return jax.tree_util.tree_map(lambda a: dev, self.opt_state)

    def _opt_host_shardings(self):
        from ..framework import offload as _ol
        return _ol.host_shardings(self.opt_state, self._opt_dev_shardings())

    @staticmethod
    def _move_opt(opt_state, shardings):
        from ..framework import offload as _ol
        return _ol.move_opt(opt_state, shardings)

    def _flat(self, tree):
        leaves = jax.tree_util.tree_leaves(tree)
        return dict(zip(self._names, leaves))

    def _unflat(self, d):
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self.params), [d[n] for n in self._names])

    def _specs(self):
        pp = self.mesh.shape.get("pp", 1) if self.mesh is not None else 1
        return gpt_param_specs(self.config, pp=pp, zero_stage=self.zero_stage)

    def _place(self):
        specs = self._specs()
        mesh = self.mesh
        self.params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            self.params, specs)
        # ZeRO: sharded slots follow params; scalars replicated — the single
        # source of slot placement is _opt_dev_shardings (shared with the
        # host-offload fetch/stash path)
        self.opt_state = self._move_opt(self.opt_state,
                                        self._opt_dev_shardings())

    def _build(self):
        from ..framework.compilation_cache import ensure_persistent_cache
        ensure_persistent_cache()
        config, mesh, M = self.config, self.mesh, self.num_microbatches
        optimizer = self.optimizer
        unflat = self._unflat
        flat = self._flat

        mp = mesh.shape.get("mp", 1) if mesh is not None else 1
        from ..framework import offload as _ol
        offload_in = self.offload and self._offload_in_jit
        # single-chip offload streams stacked block slots one layer at a
        # time (bulk fetch would put the whole moment set back in HBM —
        # the 2.7B OOM); on a multi-device mesh the slots are ZeRO- or
        # pp-sharded over the leading dim, which conflicts with layer
        # slicing, so bulk fetch/stash remains that path. A trivial
        # all-ones mesh shards nothing and streams like mesh=None.
        stream = offload_in and (
            mesh is None or all(s == 1 for s in mesh.shape.values()))
        fetch_opt, stash_opt = _ol.fetch_stash(
            offload_in and not stream,
            self._opt_dev_shardings() if offload_in else None,
            self._opt_host_shardings() if offload_in else None)
        # stream only the 3D matrix leaves: a [1, H, X] slice DMAs whole
        # sublane tiles, while [1, H] slices of the 2D bias/norm leaves trip
        # the TPU dynamic-index emitter's sublane-multiple check (observed
        # compiler crash) — and their moments are only ~5MB total anyway
        stacked = {n for n, a in self._flat(self.params).items()
                   if "blocks" in n and a.ndim >= 3}

        def step_fn(flat_params, opt_state, ids, lr):
            def loss_fn(fp):
                p = unflat(fp)
                if mp == 1:
                    # fused head+CE: never materializes fp32 [B,S,V] logits
                    from ..ops.fused_ce import fused_lm_loss
                    hidden = gpt_hidden(p, ids, config, mesh, M)
                    return fused_lm_loss(
                        hidden, p["head_w"].astype(hidden.dtype), ids)
                # mp>1: logits are vocab-sharded (V/mp per chip) — the plain
                # logsumexp stays within budget and XLA keeps it sharded
                logits = gpt_forward(p, ids, config, mesh, M)
                return _lm_loss(logits, ids)
            loss, grads = jax.value_and_grad(loss_fn)(flat_params)
            clip = getattr(optimizer, "_grad_clip", None)
            if clip is not None:
                names = list(grads)
                clipped = clip.apply_arrays([grads[n] for n in names])
                grads = dict(zip(names, clipped))
            # flat names are bracketed tree paths (e.g. "['blocks']/['up_b']")
            # — match on the unwrapped leaf name, not the raw string
            def _leaf(n):
                return n.rsplit("/", 1)[-1].strip("[]'\"")
            wd_mask = {n: not (_leaf(n).endswith("_b") or "ln" in _leaf(n)
                               or _leaf(n) == "wpe")
                       for n in flat_params}
            if stream:
                new_params, new_opt = _ol.streamed_apply_gradients(
                    optimizer, flat_params, grads, opt_state, lr, wd_mask,
                    stacked,
                    to_dev=lambda a: jax.device_put(
                        a, _ol.with_memory_kind(None, "device")),
                    to_host=lambda a: jax.device_put(
                        a, _ol.with_memory_kind(None, "pinned_host")))
                return loss, new_params, new_opt
            new_params, new_opt = optimizer.apply_gradients(
                flat_params, grads, fetch_opt(opt_state), lr, wd_mask=wd_mask)
            return loss, new_params, stash_opt(new_opt)

        jit_kwargs = dict(donate_argnums=(0, 1))
        if mesh is not None:
            batch_axis = "dp" if "dp" in mesh.axis_names else None
            data_sh = NamedSharding(mesh, P(batch_axis, None))
            rep = NamedSharding(mesh, P())
            jit_kwargs["in_shardings"] = (None, None, data_sh, rep)
        return jax.jit(step_fn, **jit_kwargs)

    def __call__(self, ids):
        ids = jnp.asarray(ids)
        if self._jitted is None:
            self._jitted = self._build()
        # static mp-axis comm ledger of the compiled schedule
        # (profiler.mp_comm_counters evidence), keyed per batch shape —
        # jax.jit retraces per shape and gpt_hidden re-resolves the
        # schedule at trace time, so the ledger must follow suit
        recs = getattr(self, "_mp_records", None)
        if recs is None:
            recs = self._mp_records = {}
        shape_key = tuple(ids.shape)
        if shape_key not in recs:
            from ..distributed import tp_overlap as _tp
            from ..distributed import comm_backend as _cb
            from ..distributed import pipeline as _pl
            B, S = ids.shape
            sp = _tp.resolve_gpt(self.config, self.mesh, batch=B, seq=S) \
                if self.mesh is not None else None
            pp = self.mesh.shape.get("pp", 1) if self.mesh is not None else 1
            ppc = _cb.resolve_pp(self.config, self.mesh, batch=B,
                                 num_microbatches=self.num_microbatches,
                                 sp=sp) if pp > 1 else None
            if pp > 1 and ppc is None:
                sp = None  # mirrors gpt_hidden's trace-time fallback
            sp_rec = _tp.gpt_step_record(self.config, sp, B, S) \
                if sp is not None else None
            pp_rec = _pl.gpt_pp_step_record(
                self.config, ppc, B, S, self.num_microbatches, S=pp,
                mp=sp.n if sp is not None else 1) if pp > 1 else None
            recs[shape_key] = (sp_rec, pp_rec)
        sp_rec, pp_rec = recs[shape_key]
        if sp_rec is not None:
            from ..distributed import tp_overlap as _tp
            _tp.record_step(sp_rec)
        if pp_rec is not None:
            from ..distributed import pipeline as _pl
            _pl.record_pp_step(pp_rec)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        flat_params = self._flat(self.params)
        offload_out = self.offload and not self._offload_in_jit
        if offload_out:  # backend without in-jit memory transfers (CPU)
            self.opt_state = self._move_opt(self.opt_state,
                                            self._opt_dev_shardings())
        t_tel = self._tel.begin(self._step_count)
        # the dispatch as a step on the profiler's clock (a no-op check
        # outside a profiler session)
        with jax.profiler.StepTraceAnnotation("pt.train.step",
                                              step_num=self._step_count):
            loss, flat_params, self.opt_state = self._jitted(
                flat_params, self.opt_state, ids, lr)
        if t_tel is not None:
            from ..observability.flops import train_step_flops
            B, S = ids.shape
            flops, _ = train_step_flops(self.config, B, S)
            wire = None
            if sp_rec is not None:
                wire = int(sp_rec.rs_bytes + sp_rec.ag_bytes)
            if pp_rec is not None and pp_rec.boundary_bytes:
                wire = (wire or 0) + int(pp_rec.boundary_bytes)
            self._tel.end(t_tel, self._step_count, loss, tokens=B * S,
                          flops=flops, wire_bytes=wire)
        if offload_out:
            self.opt_state = self._move_opt(self.opt_state,
                                            self._opt_host_shardings())
        self.params = self._unflat(flat_params)
        self._step_count += 1
        return loss

    def loss_only(self, ids):
        """Forward-only loss on the CURRENT params (no grads, no update) —
        the bench's step-time-breakdown probe. Shares the live param
        buffers; only activation workspace is added."""
        if not hasattr(self, "_fwd_jitted"):
            config, mesh, M = self.config, self.mesh, self.num_microbatches
            unflat = self._unflat
            mp = mesh.shape.get("mp", 1) if mesh is not None else 1

            def fwd(fp, ids):
                p = unflat(fp)
                if mp == 1:
                    from ..ops.fused_ce import fused_lm_loss
                    hidden = gpt_hidden(p, ids, config, mesh, M)
                    return fused_lm_loss(
                        hidden, p["head_w"].astype(hidden.dtype), ids)
                return _lm_loss(gpt_forward(p, ids, config, mesh, M), ids)

            self._fwd_jitted = jax.jit(fwd)
        return self._fwd_jitted(self._flat(self.params), jnp.asarray(ids))

    def num_params(self):
        return int(sum(np.prod(l.shape) for l in
                       jax.tree_util.tree_leaves(self.params)))
