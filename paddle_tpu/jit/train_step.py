"""TrainStep — compiled training step.

The reference runs training as: dygraph forward -> C++ backward engine ->
optimizer op kernels, or via Fleet's distributed graph passes. The TPU-native
design compiles ONE pure XLA program per step:

    (params, opt_state, lr, key, batch) -> (loss, new_params, new_opt_state)

with `jax.value_and_grad` for the backward, the optimizer's functional rule
fused in, buffers donated (in-place param update in HBM), and GSPMD shardings
from each Parameter's `dist_spec` (set by fleet/parallel layers). XLA inserts
all collectives (dp grad allreduce, tp activation collectives, ZeRO
gather/scatter) from the sharding annotations — the ProcessGroupNCCL layer of
the reference has no analog here because the compiler emits it.

When the explicit gradient-communication layer is enabled
(distributed/grad_comm.py; FLAGS_weight_update_sharding /
FLAGS_allreduce_dtype / FLAGS_grad_comm), the data-parallel step instead
compiles under shard_map over the dp axis so the grad-reduce schedule is
ours, not GSPMD's: bucketed reduce-scatter of local grads, the fused
optimizer update on each replica's 1/n flat shard (optimizer slots stored
packed+sharded, zero slot communication), then a bucketed all-gather of the
updated params — the weight-update-sharding schedule of arXiv:2004.13336,
with optional bf16/int8 wire compression (arXiv:2506.17615). With
accumulate_steps>1 the reduce-scatter of micro-step t is issued inside
micro-step t's program while micro-step t+1's host dispatch proceeds
asynchronously, so per-bucket communication overlaps the next micro-batch's
compute instead of bunching at the update barrier.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..tensor_impl import Tensor
from ..framework.random import next_key
from .functional import capture_params, capture_buffers, param_specs, functional_call


# -- anomaly-guard counters (profiler.fault_counters surface) ----------------
# The compiled guard's host cost model is auditable from here: `host_syncs`
# counts ONE combined (loss, step_ok...) fetch per UPDATE step — the loss
# fetch the caller was doing anyway. With accumulate_steps>1 the micro-steps'
# flags stay device-resident and ride to the fire boundary in the same single
# fetch (the async micro-dispatch overlap is untouched), so host_syncs equals
# the number of fire steps, steps/accumulate_steps. Anything above that ratio
# means a sync snuck in. `skipped_updates` counts updates that were actually
# due and skipped (k==1 bad steps); under accumulation a poisoned micro only
# drops its contribution and the boundary update still runs, so only
# `bad_steps` moves.
_anomaly_counters = {"steps": 0, "host_syncs": 0, "bad_steps": 0,
                     "skipped_updates": 0, "rollbacks": 0}


def anomaly_counters():
    return dict(_anomaly_counters)


def reset_anomaly_counters():
    for k in _anomaly_counters:
        _anomaly_counters[k] = 0


class TrainStep:
    def __init__(self, model, loss_fn, optimizer, mesh=None, donate=True,
                 remat=False, batch_spec=None, loss_has_model_kw=False,
                 extra_loss_args=0, accumulate_steps=None):
        """loss_fn(outputs, *labels) -> scalar Tensor (written in eager API).

        accumulate_steps=k fuses gradient accumulation (the reference's
        gradient merge, ref: fleet/meta_optimizers/gradient_merge_optimizer
        .py) into the compiled step: grads average into a persistent
        accumulator and the optimizer fires every k-th call (lax.cond —
        one compiled program for both phases).
        """
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self.donate = donate
        self.remat = remat
        self.batch_spec = batch_spec
        if accumulate_steps is None:
            accumulate_steps = getattr(optimizer, "_gradient_merge_k", 1)
        self.accumulate_steps = max(int(accumulate_steps), 1)
        self._params = capture_params(model)
        self._buffers = capture_buffers(model)
        self._specs = param_specs(model)
        self._opt_state = optimizer.init_state(self._params)
        # host offload of optimizer states (ref: fleet sharding stage-3
        # offload, group_sharded_stage3.py:84): slots live in pinned host
        # memory between steps. On TPU the compiled step streams them
        # chip-side and back (in-jit device_put, overlapped by XLA); other
        # backends move them around the jit call (the CPU backend has no
        # annotate_device_placement kernel).
        from ..framework import offload as _ol
        self._offload = bool(getattr(optimizer, "_offload_opt_states", False))
        self._offload_in_jit = _ol.in_jit_transfers_supported()
        self._grad_accum = (
            {n: jnp.zeros_like(a) for n, a in self._params.items()}
            if self.accumulate_steps > 1 else None)
        self._micro = jnp.zeros((), jnp.int32)
        self._micro_py = 0
        self._jitted = None
        self._step = 0
        # explicit gradient-communication schedule (grad_comm.py); resolved
        # from flags at first call, None = default GSPMD schedule
        self._gc_cfg = None
        self._comm_records = None
        # extra args of the compiled grad-comm step (the dp-sharded replica
        # arange of the mp-composed partial-manual mode); empty otherwise
        self._gc_extra = ()
        # compiled anomaly guard (FLAGS_anomaly_policy, resolved at first
        # call): None = unguarded program (byte-identical to the seed), or
        # ("skip"|"rollback", K). The policy layer below consumes the
        # step_ok flag that rides back with the loss.
        self._anomaly = None
        self._bad_streak = 0
        self.last_step_ok = True
        # device-resident step_ok flags of the current accumulation window,
        # fetched together with the fire step's loss (no per-micro syncs)
        self._pending_ok = []
        # fault-tolerance attachments: checkpoint manager (rollback source +
        # periodic auto-save), data loader / grad scaler whose state rides
        # along in state_dict() for exact resume
        self._ckpt_mgr = None
        self._ckpt_every = 0
        self._attached_loader = None
        self._attached_scaler = None
        self._on_rollback = None
        # live step telemetry (observability/step_telemetry.py;
        # FLAGS_step_telemetry): sampled host-side records — dispatch/sync
        # wall split, memory watermark, wire bytes from the static
        # grad-comm record, and MFU once flops_per_step is set (e.g. via
        # observability.train_step_flops). Off by default: one dict
        # lookup per step, never a traced operand or a retrace.
        from ..observability.step_telemetry import StepSampler
        self._tel = StepSampler("jit.TrainStep")
        self.flops_per_step = None
        self.tokens_per_step = None
        # silent-data-corruption sentinel (FLAGS_sdc_check_every, resolved
        # at first call): every Nth step dispatches a separate executable
        # with a per-replica integrity fingerprint fused in; the verdict
        # rides the combined host fetch and a minority replica is repaired
        # in place from a healthy peer (distributed/integrity.py). 0 = off
        # — the regular executable is byte-identical to flags-off.
        self._sdc_every = 0
        self._sdc_jitted = None
        self._sdc_devices = None

    # -- sharding helpers ----------------------------------------------------
    def _sharding_for(self, spec):
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, spec if spec is not None else P())

    def _opt_dev_shardings(self):
        """Device-memory sharding per optimizer-state leaf (mesh GSPMD specs
        when there is a mesh, single-device placement otherwise)."""
        from ..framework import offload as _ol
        if self.mesh is not None:
            return self._opt_shardings()
        dev = _ol.with_memory_kind(None, "device")
        return jax.tree_util.tree_map(lambda a: dev, self._opt_state)

    def _opt_host_shardings(self):
        from ..framework import offload as _ol
        return _ol.host_shardings(self._opt_state, self._opt_dev_shardings())

    def _move_opt(self, opt_state, shardings):
        from ..framework import offload as _ol
        return _ol.move_opt(opt_state, shardings)

    def _param_shardings(self):
        return {n: self._sharding_for(self._specs.get(n)) for n in self._params}

    def _opt_shardings(self):
        # weight-update sharding (grad_comm): slots live in the packed
        # (n, cols) layout with the leading axis sharded over the dp axis —
        # each replica persistently holds the 1/n flat shard its update
        # touches, and the compiled step moves zero slot bytes.
        if self._gc_cfg is not None and self._gc_cfg.weight_update_sharding:
            ax = self._gc_cfg.axis
            packed = self._sharding_for(P(ax, None))
            return {"step": self._sharding_for(P()),
                    "slots": {n: {k: packed for k in s}
                              for n, s in self._opt_state["slots"].items()}}
        # slots mirror param shapes -> same sharding; scalars replicated.
        # ZeRO stage>=1 (fleet sharding): slots of replicated params shard
        # over the 'sharding' axis (ref: fleet sharding stage1/2 optimizer
        # state partitioning) — XLA gathers shards during the fused update.
        p_sh = self._param_shardings()
        zero_axis = getattr(self.optimizer, "_shard_opt_states_axis", None)
        zero_n = self.mesh.shape.get(zero_axis, 1) if (
            self.mesh is not None and zero_axis) else 1

        def slot_sharding(name, slots):
            out = {}
            for k, v in slots.items():
                if jnp.ndim(v) == 0:
                    out[k] = self._sharding_for(P())
                elif (zero_n > 1 and self._specs.get(name) is None
                      and v.shape[0] % zero_n == 0):
                    out[k] = self._sharding_for(
                        P(zero_axis, *([None] * (v.ndim - 1))))
                else:
                    out[k] = p_sh[name]
            return out
        return {"step": self._sharding_for(P()),
                "slots": {n: slot_sharding(n, s)
                          for n, s in self._opt_state["slots"].items()}}

    def shard_params(self):
        """Place current params/opt state onto the mesh per spec."""
        if self.mesh is None:
            return
        p_sh = self._param_shardings()
        self._params = {n: jax.device_put(a, p_sh[n]) for n, a in self._params.items()}
        o_sh = self._opt_host_shardings() if self._offload \
            else self._opt_shardings()
        self._opt_state = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, s), self._opt_state, o_sh,
            is_leaf=lambda x: isinstance(x, jax.Array))
        if self._grad_accum is not None:
            if self._gc_cfg is not None and self._gc_cfg.weight_update_sharding:
                acc_sh = self._sharding_for(P(self._gc_cfg.axis, None))
                self._grad_accum = {n: jax.device_put(a, acc_sh)
                                    for n, a in self._grad_accum.items()}
            else:
                self._grad_accum = {n: jax.device_put(a, p_sh[n])
                                    for n, a in self._grad_accum.items()}

    # -- compiled step -------------------------------------------------------
    def _effective_donate(self):
        """Constructor `donate` AND the global FLAGS_donate_buffers knob."""
        from .. import flags as _flags
        return bool(self.donate and
                    _flags._FLAGS.get("FLAGS_donate_buffers", True))

    def _build(self, batch_treedef, n_inputs, sdc=False):
        from ..framework.compilation_cache import ensure_persistent_cache
        ensure_persistent_cache()
        model, loss_fn, optimizer = self.model, self.loss_fn, self.optimizer
        grad_clip = getattr(optimizer, "_grad_clip", None)
        mesh = self.mesh
        remat = self.remat
        # TPU host offload: slots arrive in pinned host memory; the step
        # streams them to HBM for the fused update and back (XLA overlaps
        # the copies with compute)
        from ..framework import offload as _ol
        offload_in = self._offload and self._offload_in_jit
        o_host_tree = self._opt_host_shardings() if offload_in else None
        fetch_opt, stash_opt = _ol.fetch_stash(
            offload_in, self._opt_dev_shardings() if offload_in else None,
            o_host_tree)

        def loss_from(params, buffers, key, inputs, labels):
            out, new_buffers = functional_call(model, params, buffers, inputs,
                                               rng_key=key)
            from ..framework import state as _st
            with _st.functional_trace():
                wrapped = jax.tree_util.tree_map(Tensor, out)
                wrapped_labels = jax.tree_util.tree_map(
                    lambda x: Tensor(x) if hasattr(x, "dtype") else x, labels)
                loss_t = loss_fn(wrapped, *wrapped_labels) if isinstance(
                    wrapped_labels, (list, tuple)) else loss_fn(wrapped, wrapped_labels)
            loss = loss_t._data if isinstance(loss_t, Tensor) else loss_t
            return loss.astype(jnp.float32), new_buffers

        if remat:
            loss_from = jax.checkpoint(loss_from, static_argnums=())

        k = self.accumulate_steps

        def apply_update(params, grads, opt_state, lr):
            if grad_clip is not None:
                names = list(grads)
                clipped = grad_clip.apply_arrays([grads[n] for n in names])
                grads = dict(zip(names, clipped))
            return optimizer.apply_gradients(params, grads, opt_state, lr)

        if self._gc_cfg is not None:
            return self._build_grad_comm(loss_from, apply_update, sdc=sdc)

        # compiled anomaly guard: an all-finite reduction over loss+grads is
        # fused into the executable and the update is gated on it with
        # lax.cond — a NaN/Inf step leaves params, slots, and buffers
        # untouched, and the host learns from the step_ok flag riding back
        # with the loss (no extra sync). Guard off: programs identical to
        # the seed.
        guard = self._anomaly is not None
        from ..distributed.elastic import all_finite

        def step_fn(params, opt_state, buffers, lr, key, inputs, labels):
            (loss, new_buffers), grads = jax.value_and_grad(
                loss_from, has_aux=True)(params, buffers, key, inputs, labels)
            opt_in = fetch_opt(opt_state)
            if not guard:
                new_params, new_opt = apply_update(params, grads, opt_in, lr)
                return loss, new_params, stash_opt(new_opt), new_buffers
            ok = all_finite(loss, grads)

            def do(_):
                new_p, new_o = apply_update(params, grads, opt_in, lr)
                return new_p, new_o, new_buffers

            def skip(_):
                return params, opt_in, buffers

            new_params, new_opt, out_buffers = lax.cond(ok, do, skip, None)
            return loss, ok, new_params, stash_opt(new_opt), out_buffers

        def accum_step_fn(params, opt_state, buffers, gacc, micro, lr, key,
                          inputs, labels):
            opt_state = fetch_opt(opt_state)
            (loss, new_buffers), grads = jax.value_and_grad(
                loss_from, has_aux=True)(params, buffers, key, inputs, labels)

            # mean over the k micro-batches == one big-batch gradient
            def add_contrib(_):
                return jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(a.dtype) / k, gacc, grads)

            if guard:
                # a poisoned micro-batch contributes nothing to the
                # accumulator (and leaves buffers alone); the boundary
                # update still fires from the clean contributions
                ok = all_finite(loss, grads)
                gacc = lax.cond(ok, add_contrib, lambda _: gacc, None)
                out_buffers = lax.cond(ok, lambda _: new_buffers,
                                       lambda _: buffers, None)
            else:
                gacc = add_contrib(None)
                out_buffers = new_buffers
            fire = (micro + 1) % k == 0

            def do_update(_):
                new_p, new_o = apply_update(params, gacc, opt_state, lr)
                zeroed = jax.tree_util.tree_map(jnp.zeros_like, gacc)
                return new_p, new_o, zeroed

            def no_update(_):
                return params, opt_state, gacc

            new_params, new_opt, new_gacc = jax.lax.cond(
                fire, do_update, no_update, None)
            if guard:
                return (loss, ok, new_params, stash_opt(new_opt), out_buffers,
                        new_gacc, micro + 1)
            return (loss, new_params, stash_opt(new_opt), out_buffers,
                    new_gacc, micro + 1)

        if k > 1:
            # params, opt state, buffers and the grad accumulator are all
            # same-shape in->out: donating them makes the whole step update
            # in place in HBM (no transient second copy of the model state)
            donate = (0, 1, 2, 3) if self._effective_donate() else ()
            if mesh is not None:
                p_sh = self._param_shardings()
                o_sh = o_host_tree if offload_in else self._opt_shardings()
                rep = NamedSharding(mesh, P())
                b_sh = {n: rep for n in self._buffers}
                dp_axes = tuple(a for a in ("dp", "sdp")
                                if a in mesh.axis_names)
                data_sh = NamedSharding(mesh, P(dp_axes if dp_axes else None))
                data_tree = lambda t: jax.tree_util.tree_map(
                    lambda _: data_sh, t)
                in_sh = (p_sh, o_sh, b_sh, p_sh, rep, rep, rep,
                         data_tree(self._sample_inputs),
                         data_tree(self._sample_labels))
                out_sh = ((rep,) if guard else ()) + (
                    rep, p_sh, o_sh, b_sh, p_sh, rep)
                return jax.jit(accum_step_fn, donate_argnums=donate,
                               in_shardings=in_sh, out_shardings=out_sh)
            return jax.jit(accum_step_fn, donate_argnums=donate)

        donate = (0, 1, 2) if self._effective_donate() else ()
        if mesh is not None:
            p_sh = self._param_shardings()
            o_sh = o_host_tree if offload_in else self._opt_shardings()
            rep = NamedSharding(mesh, P())
            b_sh = {n: rep for n in self._buffers}
            dp_axes = tuple(a for a in ("dp", "sdp") if a in mesh.axis_names)
            data_spec = P(dp_axes if dp_axes else None)
            data_sh = NamedSharding(mesh, data_spec)
            in_shardings = (p_sh, o_sh, b_sh, rep, rep,
                            jax.tree_util.tree_map(lambda _: data_sh,
                                                   self._sample_inputs),
                            jax.tree_util.tree_map(lambda _: data_sh,
                                                   self._sample_labels))
            out_shardings = ((rep,) if guard else ()) + (rep, p_sh, o_sh, b_sh)
            return jax.jit(step_fn, donate_argnums=donate,
                           in_shardings=in_shardings, out_shardings=out_shardings)
        return jax.jit(step_fn, donate_argnums=donate)

    # -- explicit gradient-communication step (grad_comm.py) ----------------
    def _build_grad_comm(self, loss_from, apply_update, sdc=False):
        """Compile the step under shard_map over the dp axis with the
        explicit bucketed reduce-scatter / sharded-update / all-gather
        schedule (or the explicit all-reduce baseline when weight-update
        sharding is off). Returns one jitted fn, or for accumulate_steps>1
        a {"micro", "fire"} pair — micro steps issue only the per-bucket
        reduce-scatter into the sharded accumulator, so their collectives
        overlap the (asynchronously dispatched) next micro-batch compute.

        ``sdc=True`` (k==1, non-composed only) builds the check-step
        variant: a per-replica integrity fingerprint over the device-local
        input state is fused in, the dp-gathered fingerprint vector rides
        the output tuple (after the anomaly flag), and the update is gated
        on cross-replica agreement — a mismatch step performs NO update, so
        the host can peer-repair and re-dispatch the SAME step. The check
        variant is built WITHOUT donation so the (possibly corrupt) input
        state stays alive for in-place repair."""
        from ..distributed import grad_comm as _gc
        from ..distributed import integrity as _integrity
        shard_map = functools.partial(jax.shard_map, check_vma=False)
        cfg = self._gc_cfg
        mesh, axis, n = self.mesh, cfg.axis, cfg.n
        optimizer = self.optimizer
        grad_clip = getattr(optimizer, "_grad_clip", None)
        plan = _gc.BucketPlan.build(self._params, n, cfg.bucket_bytes)
        cfg.plan = plan
        wus = cfg.weight_update_sharding
        wire = cfg.wire_dtype
        k = self.accumulate_steps
        names = list(self._params)
        # mp composition (cfg.auto_axes): bind ONLY the dp axis manually and
        # leave mp to GSPMD, so the model's tensor-parallel constraints keep
        # partitioning inside the region. jax 0.4.x cannot partition
        # all_gather/axis_index there — all_gather_shards takes the emulated
        # psum path, and the replica index arrives as an extra dp-sharded
        # arange argument (a trace-time constant through psum_scatter also
        # aborts the partitioner).
        composed = bool(cfg.auto_axes)
        manual = frozenset({axis}) if composed else frozenset()
        # only the explicit-allreduce baseline's grad gather is emulated in
        # composed mode; the sharded-update path hands its param gather to
        # GSPMD outside the manual region (native all-gather bytes)
        emu = composed and not wus
        # fused backend: bucket RS/AG ride the Pallas in-kernel rings
        # (single-axis meshes); the composed step's bf16 wire rides the
        # int16 fixed-point psum_scatter (grad_comm._fixed16_reduce_row)
        fused_meta = None
        if cfg.fused_kernels:
            from ..ops.pallas_kernels import fused_collectives as _fc
            fused_meta = _fc.meta_for(mesh, axis)
        fixed16 = cfg.fixed16

        rec_kw = dict(emulated_gather=emu, backend=cfg.backend,
                      fused_kernels=cfg.fused_kernels, fixed16=fixed16)
        self._comm_records = {
            "step": _gc.make_step_record(plan, wire, wus, **rec_kw),
            "micro": _gc.make_step_record(plan, wire, wus, with_update=False,
                                          **rec_kw),
            "fire": _gc.make_step_record(plan, wire, wus, **rec_kw),
            # integrity check step: + one fingerprint all-gather
            "sdc": _gc.make_step_record(plan, wire, wus, sdc=True, **rec_kw),
        }
        self._gc_extra = (jnp.arange(n, dtype=jnp.int32),) if composed \
            else ()

        def replica_idx(ridx):
            # ridx: () when fully manual, (arange-shard,) when composed
            return ridx[0][0] if ridx else lax.axis_index(axis)

        def gather_full(shards, idx):
            return _gc.all_gather_shards(
                plan, shards, axis, idx=idx if composed else None,
                meta=fused_meta)

        def local_loss_grads(params, buffers, key, inputs, labels, idx):
            # decorrelate per-replica dropout: the replicas see different
            # batch shards, so their masks must differ too
            key = jax.random.fold_in(key, idx)
            (loss, new_buffers), grads = jax.value_and_grad(
                loss_from, has_aux=True)(params, buffers, key, inputs, labels)
            return loss, new_buffers, grads

        def sync_buffers(bufs):
            # replicas update running stats (BN etc.) from their local shard;
            # pmean restores the replicated invariant
            return {nm: (lax.pmean(v, axis)
                         if jnp.issubdtype(v.dtype, jnp.floating) else v)
                    for nm, v in bufs.items()}

        def sharded_update_core(params, opt_state, gshards, lr, idx):
            """Fused optimizer update on each replica's 1/n flat shard —
            the PURE (collective-free) part, so the anomaly guard can gate
            it with lax.cond and still run the publish collectives
            unconditionally outside the branch. Elementwise rules make
            shard-of-update == update-of-shard bitwise. Returns (current
            param shards, updated param shards, updated opt state)."""
            pshards = {nm: _gc.shard_of(plan, nm, params[nm], idx)
                       for nm in names}
            slots_sh = {nm: {kk: v.reshape(-1) for kk, v in sl.items()}
                        for nm, sl in opt_state["slots"].items()}
            new_psh, new_state = optimizer.apply_gradients(
                pshards, gshards, {"step": opt_state["step"],
                                   "slots": slots_sh}, lr)
            new_opt = {"step": new_state["step"],
                       "slots": {nm: {kk: v.reshape(1, -1)
                                      for kk, v in sl.items()}
                                 for nm, sl in new_state["slots"].items()}}
            return pshards, new_psh, new_opt

        def publish_shards(psh, idx):
            """Updated (or passthrough) param shards -> step output: a
            bucketed all-gather in-region when fully manual, or packed
            (1, cols) rows handed to GSPMD outside the region (composed
            mode — the jax 0.4.x partitioner miscompiles an in-region
            param gather when jit-level params are mp-sharded; out_spec
            P(axis, None) reassembles the logical (n, cols) layout for
            the jit-level unpack)."""
            if composed:
                return {nm: psh[nm][None] for nm in names}
            return gather_full(psh, idx)

        def unpack_params(packed):
            """jit-level (GSPMD, outside the manual region) unpack of the
            packed (n, cols) rows back to logical param shapes — the
            reshape is where GSPMD inserts the native dp all-gather."""
            out = {}
            for nm in names:
                e = plan.entries[nm]
                out[nm] = packed[nm].reshape(-1)[:e.size].reshape(
                    e.shape).astype(e.dtype)
            return out

        def reduce_mean_shards(grads, idx):
            return _gc.reduce_scatter_grads(plan, grads, axis, wire, denom=n,
                                            meta=fused_meta, fixed16=fixed16,
                                            idx=idx)

        # anomaly guard in shard space: each replica checks its own local
        # loss and its 1/n reduced grad shards (the shards already contain
        # every replica's contribution post reduce-scatter), then one psum
        # of the bad-count makes the verdict identical on all replicas —
        # no per-param reductions over gathered grads, no host sync.
        guard = self._anomaly is not None
        from ..distributed.elastic import all_finite

        def shard_ok(loss, gshards):
            local = all_finite(loss, gshards)
            bad = lax.psum(jnp.logical_not(local).astype(jnp.int32), axis)
            return bad == 0

        # -- specs/shardings ------------------------------------------------
        P_rep, P_packed, P_data = P(), P(axis, None), P(axis)
        p_spec = {nm: P_rep for nm in self._params}
        b_spec = {nm: P_rep for nm in self._buffers}
        # composed mode: shard_map specs mention ONLY the manual dp axis
        # (params are dp-replicated), while the jit-level shardings keep
        # each param's mp dist_spec so the tensor-parallel placement
        # survives the explicit dp schedule
        p_jit = ({nm: (self._specs.get(nm) or P_rep) for nm in self._params}
                 if composed else p_spec)
        if wus:
            o_spec = {"step": P_rep,
                      "slots": {nm: {kk: P_packed for kk in sl}
                                for nm, sl in self._opt_state["slots"].items()}}
        else:
            o_spec = jax.tree_util.tree_map(lambda _: P_rep, self._opt_state)
        data_spec = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda _: P_data, t)
        to_sh = lambda spec_tree: jax.tree_util.tree_map(  # noqa: E731
            lambda s: NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))
        # jit-level opt-state placement must equal what shard_params did:
        # _opt_shardings (packed+dp-sharded under wus; slots mirroring the
        # param dist_specs otherwise — which keeps mp-sharded slots
        # mp-sharded in composed mode)
        o_jit = self._opt_shardings() if composed else to_sh(o_spec)
        in_data = data_spec(self._sample_inputs)
        in_lab = data_spec(self._sample_labels)

        ridx_spec = (P_data,) if composed else ()

        # params leave the shard_map packed (dp-sharded rows) in composed
        # wus mode and are unpacked at the jit level
        p_out_spec = ({nm: P_packed for nm in self._params}
                      if composed and wus else p_spec)

        if k == 1:
            def body(params, opt_state, buffers, lr, key, inputs, labels,
                     *ridx):
                idx = replica_idx(ridx)
                if sdc:
                    # per-replica integrity fingerprint over the device-LOCAL
                    # input bytes (params; plus the slots when they are
                    # replicated — packed wus shards legitimately differ per
                    # replica and carry no peer redundancy). The all_gather
                    # makes the full per-replica vector visible to every
                    # replica AND to the host via the step's one combined
                    # fetch — zero extra syncs.
                    fp = _integrity.fingerprint_arrays(
                        (params,) if wus else (params, opt_state))
                    fps = lax.all_gather(fp, axis, tiled=False)
                    fp_ok = jnp.all(fps == fps[0])
                loss, new_buffers, grads = local_loss_grads(
                    params, buffers, key, inputs, labels, idx)
                gshards = reduce_mean_shards(grads, idx)
                ok = shard_ok(loss, gshards) if guard else None
                # update gate: anomaly verdict, fingerprint verdict, or both
                # — a gated-off step passes all state through untouched
                gate = ok
                if sdc:
                    gate = fp_ok if gate is None else jnp.logical_and(
                        gate, fp_ok)
                gated = gate is not None
                if grad_clip is not None:
                    gshards = _gc.clip_shards(grad_clip, gshards, axis)
                if wus:
                    pshards, new_psh, upd_opt = sharded_update_core(
                        params, opt_state, gshards, lr, idx)
                    if gated:
                        # pure select; the publish gather below runs
                        # unconditionally (no collectives under the cond)
                        sel_psh, new_opt = lax.cond(
                            gate, lambda _: (new_psh, upd_opt),
                            lambda _: (pshards, opt_state), None)
                    else:
                        sel_psh, new_opt = new_psh, upd_opt
                    new_params = publish_shards(sel_psh, idx)
                else:
                    # explicit all-reduce baseline: finish the reduce with a
                    # grad all-gather (ring AR = RS+AG), replicated update
                    grads_full = gather_full(gshards, idx)
                    if gated:
                        new_params, new_opt = lax.cond(
                            gate, lambda _: optimizer.apply_gradients(
                                params, grads_full, opt_state, lr),
                            lambda _: (params, opt_state), None)
                    else:
                        new_params, new_opt = optimizer.apply_gradients(
                            params, grads_full, opt_state, lr)
                synced = sync_buffers(new_buffers)
                out_bufs = (lax.cond(gate, lambda _: synced,
                                     lambda _: buffers, None)
                            if gated else synced)
                return (lax.pmean(loss, axis),) + \
                    ((ok,) if guard else ()) + ((fps,) if sdc else ()) + \
                    (new_params, new_opt, out_bufs)

            ok_spec = ((P_rep,) if guard else ()) + ((P_rep,) if sdc else ())
            smap = shard_map(
                body, mesh=mesh,
                in_specs=(p_spec, o_spec, b_spec, P_rep, P_rep, in_data,
                          in_lab) + ridx_spec,
                out_specs=(P_rep,) + ok_spec + (p_out_spec, o_spec, b_spec),
                axis_names=manual)
            if composed and wus:
                def stepped(*args):
                    loss, *rest = smap(*args)
                    *flag, packed, new_opt, bufs = rest
                    return (loss, *flag, unpack_params(packed), new_opt,
                            bufs)
            else:
                stepped = smap
            # the sdc check variant keeps its inputs alive (no donation):
            # on a fingerprint mismatch the gated step produced no update
            # and the host repairs the INPUT state in place, then re-runs
            # the same step — donated buffers would already be dead
            donate = ((0, 1, 2)
                      if self._effective_donate() and not sdc else ())
            return jax.jit(
                stepped, donate_argnums=donate,
                in_shardings=(to_sh(p_jit), o_jit, to_sh(b_spec),
                              to_sh(P_rep), to_sh(P_rep), to_sh(in_data),
                              to_sh(in_lab)) + to_sh(ridx_spec),
                out_shardings=(to_sh(P_rep),) + to_sh(ok_spec) +
                              (to_sh(p_jit), o_jit, to_sh(b_spec)))

        # accumulate_steps > 1: separate micro/fire programs selected by the
        # host-side micro counter (deterministic), instead of lax.cond —
        # micro programs contain ONLY the reduce-scatter collectives
        acc_spec = ({nm: P_packed for nm in self._params} if wus
                    else {nm: P_rep for nm in self._params})

        def micro_body(params, opt_state, buffers, gacc, micro, lr, key,
                       inputs, labels, *ridx):
            idx = replica_idx(ridx)
            loss, new_buffers, grads = local_loss_grads(
                params, buffers, key, inputs, labels, idx)
            gshards = reduce_mean_shards(grads, idx)
            ok = shard_ok(loss, gshards) if guard else None
            if wus:
                cand = {nm: gacc[nm] +
                        (gshards[nm] / k).astype(gacc[nm].dtype
                                                 ).reshape(1, -1)
                        for nm in names}
            else:
                grads_full = gather_full(gshards, idx)
                cand = {nm: gacc[nm] +
                        (grads_full[nm] / k).astype(gacc[nm].dtype)
                        for nm in names}
            synced = sync_buffers(new_buffers)
            if guard:
                # a poisoned micro-batch contributes nothing: accumulator
                # and buffers pass through, the boundary update fires from
                # the clean contributions only
                new_gacc = lax.cond(ok, lambda _: cand, lambda _: gacc, None)
                out_bufs = lax.cond(ok, lambda _: synced,
                                    lambda _: buffers, None)
            else:
                new_gacc, out_bufs = cand, synced
            return (lax.pmean(loss, axis),) + ((ok,) if guard else ()) + \
                (params, opt_state, out_bufs, new_gacc, micro + 1)

        def fire_body(params, opt_state, buffers, gacc, micro, lr, key,
                      inputs, labels, *ridx):
            idx = replica_idx(ridx)
            loss, new_buffers, grads = local_loss_grads(
                params, buffers, key, inputs, labels, idx)
            gshards = reduce_mean_shards(grads, idx)
            ok = shard_ok(loss, gshards) if guard else None
            if wus:
                flat_acc = {nm: gacc[nm].reshape(-1) for nm in names}
                cand = {nm: flat_acc[nm] +
                        (gshards[nm] / k).astype(gacc[nm].dtype)
                        for nm in names}
                # the boundary update always applies (from the accumulated
                # clean micro-grads); only a poisoned fire micro-batch's own
                # contribution is dropped
                acc = (lax.cond(ok, lambda _: cand, lambda _: flat_acc, None)
                       if guard else cand)
                if grad_clip is not None:
                    acc = _gc.clip_shards(grad_clip, acc, axis)
                _, new_psh, new_opt = sharded_update_core(
                    params, opt_state, acc, lr, idx)
                new_params = publish_shards(new_psh, idx)
                zeroed = {nm: jnp.zeros_like(gacc[nm]) for nm in names}
            else:
                grads_full = gather_full(gshards, idx)
                cand = {nm: gacc[nm] + (grads_full[nm] / k
                                        ).astype(gacc[nm].dtype)
                       for nm in names}
                acc = (lax.cond(ok, lambda _: cand, lambda _: gacc, None)
                       if guard else cand)
                new_params, new_opt = apply_update(params, acc, opt_state, lr)
                zeroed = {nm: jnp.zeros_like(gacc[nm]) for nm in names}
            synced = sync_buffers(new_buffers)
            out_bufs = (lax.cond(ok, lambda _: synced, lambda _: buffers,
                                 None) if guard else synced)
            return (lax.pmean(loss, axis),) + ((ok,) if guard else ()) + \
                (new_params, new_opt, out_bufs, zeroed, micro + 1)

        acc_jit = acc_spec if wus else p_jit
        in_specs = (p_spec, o_spec, b_spec, acc_spec, P_rep, P_rep, P_rep,
                    in_data, in_lab) + ridx_spec
        in_jit = (to_sh(p_jit), o_jit, to_sh(b_spec), to_sh(acc_jit),
                  to_sh(P_rep), to_sh(P_rep), to_sh(P_rep), to_sh(in_data),
                  to_sh(in_lab)) + to_sh(ridx_spec)
        ok_spec = (P_rep,) if guard else ()
        out_jit = (to_sh(P_rep),) + to_sh(ok_spec) + (
            to_sh(p_jit), o_jit, to_sh(b_spec), to_sh(acc_jit), to_sh(P_rep))
        donate = (0, 1, 2, 3) if self._effective_donate() else ()
        jits = {}
        for tag, body in (("micro", micro_body), ("fire", fire_body)):
            # micro steps return params untouched (replicated); only the
            # fire step's updated params leave packed in composed wus mode
            packs = composed and wus and tag == "fire"
            out_specs = (P_rep,) + ok_spec + (
                p_out_spec if packs else p_spec, o_spec,
                b_spec, acc_spec, P_rep)
            smap = shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, axis_names=manual)
            if packs:
                def stepped(*args, _smap=smap):
                    loss, *rest = _smap(*args)
                    *flag, packed, new_opt, bufs, gacc, micro = rest
                    return (loss, *flag, unpack_params(packed), new_opt,
                            bufs, gacc, micro)
            else:
                stepped = smap
            jits[tag] = jax.jit(stepped, donate_argnums=donate,
                                in_shardings=in_jit,
                                out_shardings=out_jit)
        return jits

    def build_eval(self):
        """Jitted (params, buffers, inputs, labels) -> (loss, outputs) over
        the SAME forward+loss tracing and data shardings as the train step
        (hapi Model.eval_batch's compiled path)."""
        model, loss_fn = self.model, self.loss_fn
        mesh = self.mesh

        def eval_fn(params, buffers, inputs, labels):
            out, _ = functional_call(model, params, buffers, inputs)
            from ..framework import state as _st
            with _st.functional_trace():
                wrapped = jax.tree_util.tree_map(Tensor, out)
                wrapped_labels = jax.tree_util.tree_map(
                    lambda x: Tensor(x) if hasattr(x, "dtype") else x, labels)
                loss_t = loss_fn(wrapped, *wrapped_labels)
            loss = loss_t._data if isinstance(loss_t, Tensor) else loss_t
            return loss.astype(jnp.float32), out

        if mesh is not None and getattr(self, "_sample_inputs", None) is not None:
            p_sh = self._param_shardings()
            rep = NamedSharding(mesh, P())
            b_sh = {n: rep for n in self._buffers}
            dp_axes = tuple(a for a in ("dp", "sdp") if a in mesh.axis_names)
            data_sh = NamedSharding(mesh, P(dp_axes if dp_axes else None))
            data_tree = lambda t: jax.tree_util.tree_map(  # noqa: E731
                lambda _: data_sh, t)
            return jax.jit(eval_fn, in_shardings=(
                p_sh, b_sh, data_tree(self._sample_inputs),
                data_tree(self._sample_labels)))
        return jax.jit(eval_fn)

    def __call__(self, inputs, labels):
        """inputs: Tensor or tuple of Tensors fed to model; labels likewise."""
        if not isinstance(inputs, (list, tuple)):
            inputs = (inputs,)
        if not isinstance(labels, (list, tuple)):
            labels = (labels,)
        in_arrays = tuple(x._data if isinstance(x, Tensor) else jnp.asarray(x)
                          for x in inputs)
        lab_arrays = tuple(x._data if isinstance(x, Tensor) else jnp.asarray(x)
                           for x in labels)
        # deterministic chaos hooks (utils/fault_injection.py): inactive =
        # one attribute check, arrays untouched, executables unchanged
        from ..utils import fault_injection as _fi
        if _fi._plan is not None:
            _fi.maybe_preempt(self._step)
            in_arrays, lab_arrays = _fi.maybe_poison(
                self._step, in_arrays, lab_arrays)
        if self._jitted is None:
            from .. import flags as _flags
            policy = _flags._FLAGS.get("FLAGS_anomaly_policy", "off")
            if policy in ("skip", "rollback"):
                self._anomaly = (policy, max(1, int(_flags._FLAGS.get(
                    "FLAGS_anomaly_max_bad_steps", 3))))
            elif policy not in ("off", False, None, "0"):
                raise ValueError(
                    f"FLAGS_anomaly_policy must be off|skip|rollback, "
                    f"got {policy!r}")
            self._sample_inputs = in_arrays
            self._sample_labels = lab_arrays
            from ..distributed import grad_comm as _gc
            self._gc_cfg = _gc.resolve(
                self.mesh, self.optimizer, opt_state=self._opt_state,
                params=self._params, offload=self._offload,
                param_specs=self._specs)
            if self._gc_cfg is not None and self._gc_cfg.weight_update_sharding:
                self._opt_state = _gc.pack_opt_state(
                    self._opt_state, self._params, self._gc_cfg.n)
                if self._grad_accum is not None:
                    self._grad_accum = _gc.pack_accum(
                        self._grad_accum, self._params, self._gc_cfg.n)
            else:
                # a checkpoint saved under weight-update sharding restores
                # packed (n, cols) slots; normalize back to param-shaped
                # when this step runs a replicated-update schedule
                self._opt_state = _gc.unpack_opt_state(self._opt_state,
                                                       self._params)
                if self._grad_accum is not None:
                    self._grad_accum = _gc.unpack_accum(self._grad_accum,
                                                        self._params)
            if self.mesh is not None:
                self.shard_params()
            elif self._offload:
                self._opt_state = self._move_opt(self._opt_state,
                                                 self._opt_host_shardings())
            self._jitted = self._build(None, len(in_arrays))
            every = int(_flags._FLAGS.get("FLAGS_sdc_check_every", 0) or 0)
            if every > 0:
                # sdc sentinel needs per-replica redundancy AND a manual dp
                # region to gather per-device fingerprints from: the
                # explicit grad-comm schedule on a pure-dp mesh, single-shot
                # (k==1), dp>=2. Anything else: warn once and stay off.
                cfg = self._gc_cfg
                if (cfg is not None and self.accumulate_steps == 1
                        and cfg.n >= 2 and not cfg.auto_axes
                        and self.mesh is not None
                        and self.mesh.devices.size == cfg.n):
                    self._sdc_every = every
                    self._sdc_devices = list(self.mesh.devices.flat)
                else:
                    import warnings
                    warnings.warn(
                        "FLAGS_sdc_check_every requires the explicit dp "
                        "grad-comm schedule (FLAGS_grad_comm / dp mesh) "
                        "with dp>=2 and accumulate_steps=1; the "
                        "silent-data-corruption sentinel is disabled")
        # deterministic chaos: FaultPlan.bitflip_at makes ONE replica's
        # param copy diverge by a single bit — after shard_params, so the
        # divergent-copy state matches what a flaky chip leaves behind
        if _fi._plan is not None and _fi._plan.bitflip_at:
            flips = _fi.param_bitflips(self._step)
            if flips:
                from ..distributed import integrity as _integrity
                devs = self._sdc_devices
                if devs is None and self.mesh is not None:
                    devs = list(self.mesh.devices.flat)
                self._params = _integrity.inject_bitflips(
                    self._params, flips, devs or jax.devices()[:1])
        # offload on backends without in-jit memory transfers (CPU): move the
        # slots chip-side around the compiled call instead
        offload_out = self._offload and not self._offload_in_jit
        if offload_out:
            self._opt_state = self._move_opt(self._opt_state,
                                             self._opt_dev_shardings())
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        guard = self._anomaly is not None
        ok = None
        sdc_now = False
        t_tel = self._tel.begin(self._step)
        # the dispatch as a step on the profiler's clock (a no-op check
        # outside a profiler session)
        with jax.profiler.StepTraceAnnotation("pt.train.step",
                                              step_num=self._step):
            if self.accumulate_steps > 1:
                if isinstance(self._jitted, dict):
                    # grad_comm pair: the boundary is host-deterministic, so
                    # the micro program (reduce-scatter only) and the fire
                    # program (update + param all-gather) are separate
                    # executables
                    fire = (self._micro_py + 1) % self.accumulate_steps == 0
                    fn = self._jitted["fire" if fire else "micro"]
                    rec = self._comm_records["fire" if fire else "micro"]
                else:
                    fn, rec = self._jitted, None
                out = fn(self._params, self._opt_state, self._buffers,
                         self._grad_accum, self._micro, lr, next_key(),
                         in_arrays, lab_arrays, *self._gc_extra)
                if guard:
                    (loss, ok, self._params, self._opt_state, self._buffers,
                     self._grad_accum, self._micro) = out
                else:
                    (loss, self._params, self._opt_state, self._buffers,
                     self._grad_accum, self._micro) = out
                self._micro_py += 1
            else:
                sdc_now = bool(self._sdc_every) and \
                    (self._step + 1) % self._sdc_every == 0
                rec = (self._comm_records["sdc" if sdc_now else "step"]
                       if self._comm_records else None)
                if sdc_now:
                    loss, ok = self._sdc_step(lr, in_arrays, lab_arrays, guard)
                else:
                    out = self._jitted(
                        self._params, self._opt_state, self._buffers, lr,
                        next_key(), in_arrays, lab_arrays, *self._gc_extra)
                    if guard:
                        loss, ok, self._params, self._opt_state, \
                            self._buffers = out
                    else:
                        loss, self._params, self._opt_state, \
                            self._buffers = out
        if rec is not None:
            from ..distributed import grad_comm as _gc
            _gc.record_step(rec)
        if t_tel is not None:
            wire = None
            if rec is not None:
                wire = int(sum(getattr(rec, "reduce_bytes_by_dtype",
                                       {}).values())
                           + getattr(rec, "gather_bytes", 0))
            self._tel.end(t_tel, self._step, loss,
                          tokens=self.tokens_per_step,
                          flops=self.flops_per_step, wire_bytes=wire)
        if offload_out:
            self._opt_state = self._move_opt(self._opt_state,
                                             self._opt_host_shardings())
        self._step += 1
        self.optimizer._step_count = self._step
        if guard:
            _anomaly_counters["steps"] += 1
            if self.accumulate_steps > 1:
                # micro flags stay on device until the boundary — the host
                # never blocks mid-window, preserving the async micro-batch
                # dispatch overlap of the grad_comm accumulation path
                self._pending_ok.append(ok)
                if self._micro_py % self.accumulate_steps == 0:
                    loss = self._anomaly_policy_flush(loss)
            else:
                loss = self._anomaly_policy_step(loss, ok, fetched=sdc_now)
        self._maybe_autosave()
        return Tensor(loss)

    # -- silent-data-corruption check step (distributed/integrity.py) --------
    def _sdc_step(self, lr, in_arrays, lab_arrays, guard):
        """Dispatch the fingerprint-fused check-step executable and act on
        the verdict. The per-replica fingerprint vector rides the ONE
        combined host fetch the guard was paying for anyway (host_syncs is
        audited either way). On a localized mismatch the gated executable
        performed NO update, so the minority replica's input state is
        peer-repaired in place and the SAME step re-dispatched with the
        same key and batch — zero disk restores, zero steps lost."""
        from ..distributed import integrity as _integrity
        if self._sdc_jitted is None:
            self._sdc_jitted = self._build(None, len(in_arrays), sdc=True)
        key = next_key()
        devs = self._sdc_devices

        def dispatch():
            out = self._sdc_jitted(
                self._params, self._opt_state, self._buffers, lr, key,
                in_arrays, lab_arrays, *self._gc_extra)
            if guard:
                f_loss, f_ok, f_fps = jax.device_get(
                    (out[0], out[1], out[2]))
                rest = out[3:]
            else:
                f_loss, f_fps = jax.device_get((out[0], out[1]))
                f_ok = None
                rest = out[2:]
            _anomaly_counters["host_syncs"] += 1
            return f_loss, f_ok, f_fps, rest

        loss, ok, fps, rest = dispatch()
        _integrity._count("fingerprint_checks")
        bad = _integrity.localize_minority(fps)
        if bad:
            # majority vote localized the minority replica(s); the check
            # executable is built without donation, so the corrupt input
            # state is still alive — overwrite the bad replica buffers
            # with a healthy peer's bytes and re-run this step
            _integrity._count("fingerprint_mismatches")
            for r in bad:
                _integrity.note_repair(r)
            _integrity._count("repairs", len(bad))
            self._params = _integrity.repair_tree(self._params, bad, devs)
            self._opt_state = _integrity.repair_tree(
                self._opt_state, bad, devs)
            self._buffers = _integrity.repair_tree(self._buffers, bad, devs)
            _integrity._count("repair_redispatches")
            loss, ok, fps, rest = dispatch()
        elif bad is None:
            # dp=2 tie: detected but unlocalizable. The gate already
            # skipped the update; surface it through the anomaly flag so
            # the skip/rollback policy takes over
            _integrity._count("fingerprint_mismatches")
            if ok is not None:
                ok = False
        self._params, self._opt_state, self._buffers = rest
        return loss, ok

    # -- anomaly policy layer (host side of the compiled guard) --------------
    def _anomaly_policy_step(self, loss, ok, fetched=False):
        """Consume the step_ok flag: ONE combined (loss, step_ok) device
        fetch — the loss fetch the caller was doing anyway — then streak
        accounting and, under the rollback policy, checkpoint restore after
        K consecutive bad steps. Returns the host-resident loss.
        ``fetched=True`` (sdc check steps): loss/ok are already host values
        from the check step's own combined fetch, counted there."""
        policy, max_bad = self._anomaly
        if not fetched:
            loss, ok = jax.device_get((loss, ok))
            _anomaly_counters["host_syncs"] += 1
        self.last_step_ok = bool(ok)
        if self.last_step_ok:
            self._bad_streak = 0
            return loss
        self._bad_streak += 1
        _anomaly_counters["bad_steps"] += 1
        _anomaly_counters["skipped_updates"] += 1  # an update was due
        if policy == "rollback" and self._bad_streak >= max_bad:
            self._rollback()
        return loss

    def _anomaly_policy_flush(self, loss):
        """Fire-boundary flush under accumulation: fetch the fire loss and
        the whole window's step_ok flags in ONE device_get, then run streak
        accounting over them oldest-first. A poisoned micro only dropped
        its contribution (the boundary update ran from the clean rest), so
        bad flags count toward the rollback streak but not
        skipped_updates."""
        policy, max_bad = self._anomaly
        fetched = jax.device_get((loss, *self._pending_ok))
        loss, oks = fetched[0], fetched[1:]
        self._pending_ok = []
        _anomaly_counters["host_syncs"] += 1
        for ok in oks:
            self.last_step_ok = bool(ok)
            if self.last_step_ok:
                self._bad_streak = 0
                continue
            self._bad_streak += 1
            _anomaly_counters["bad_steps"] += 1
            if policy == "rollback" and self._bad_streak >= max_bad:
                self._rollback()  # resets the streak; later flags belong
                break             # to the pre-rollback trajectory — drop
        return loss

    def _rollback(self):
        """Restore the attached CheckpointManager's newest good checkpoint
        and fast-forward the RNG stream past the poison batches: the data
        loader keeps streaming forward (batch position is NOT rewound), so
        training resumes from known-good weights on the next fresh batch."""
        from ..distributed.elastic import NonFiniteError
        mgr = self._ckpt_mgr
        if mgr is None:
            raise NonFiniteError(
                f"anomaly policy 'rollback' hit {self._bad_streak} "
                f"consecutive bad steps but no CheckpointManager is "
                f"attached (TrainStep.attach_checkpoint)")
        try:
            mgr.wait()
        except Exception:
            pass  # a failed async save must not block recovery
        target = self._step  # batches consumed so far
        state = mgr.restore(None)
        if state is None:
            raise NonFiniteError(
                f"anomaly policy 'rollback' hit {self._bad_streak} "
                f"consecutive bad steps before the first checkpoint")
        # the data stream keeps moving forward: do NOT rewind the attached
        # loader to the checkpoint's position (that would re-serve batches
        # the forwarded RNG stream has already accounted past)
        state = dict(state)
        state.pop("loader", None)
        self.load_state_dict(state)
        restored = self._step
        from ..framework import random as _rnd
        _rnd.advance(max(0, target - restored))
        self._step = target
        self.optimizer._step_count = target
        self._bad_streak = 0
        _anomaly_counters["rollbacks"] += 1
        if self._on_rollback is not None:
            self._on_rollback(restored, target)

    def _maybe_autosave(self):
        if (self._ckpt_mgr is None or not self._ckpt_every
                or self._step % self._ckpt_every != 0):
            return
        if self._anomaly is not None and not self.last_step_ok:
            return  # never publish a checkpoint taken off a bad step
        self._ckpt_mgr.save(self._step, self.state_dict())

    # -- fault-tolerance attachments -----------------------------------------
    def attach_checkpoint(self, manager, save_every=0, on_rollback=None):
        """Wire a CheckpointManager in: ``save_every>0`` auto-saves
        ``state_dict()`` every N good steps, and the rollback anomaly
        policy restores from it. ``on_rollback(restored_step,
        resume_step)`` is invoked after a restore so the data pipeline can
        resynchronize if it tracks position externally."""
        self._ckpt_mgr = manager
        self._ckpt_every = int(save_every)
        if on_rollback is not None:
            self._on_rollback = on_rollback
        return self

    def attach_loader(self, loader):
        """DataLoader whose epoch position rides along in state_dict()."""
        self._attached_loader = loader
        return self

    def attach_scaler(self, scaler):
        """amp.GradScaler whose scaling state rides along in state_dict()."""
        self._attached_scaler = scaler
        return self

    def memory_analysis(self):
        """Compiled-executable memory analysis (argument/output/temp bytes)
        of the current step — the evidence hook for ZeRO sharding tests."""
        if self._jitted is None:
            raise RuntimeError("call the step once to compile first")
        jitted = (self._jitted["fire"] if isinstance(self._jitted, dict)
                  else self._jitted)
        if self.accumulate_steps > 1:
            args = (self._params, self._opt_state, self._buffers,
                    self._grad_accum, self._micro,
                    jnp.zeros((), jnp.float32), next_key(),
                    self._sample_inputs, self._sample_labels)
        else:
            args = (self._params, self._opt_state, self._buffers,
                    jnp.zeros((), jnp.float32), next_key(),
                    self._sample_inputs, self._sample_labels)
        return jitted.lower(*args, *self._gc_extra).compile() \
            .memory_analysis()

    def sync_to_model(self):
        """Write the device-resident params/buffers back into the Layer tensors."""
        named = dict(self.model.named_parameters())
        for n, arr in self._params.items():
            if n in named:
                named[n]._data = arr
        named_b = dict(self.model.named_buffers())
        for n, arr in self._buffers.items():
            if n in named_b:
                named_b[n]._data = arr

    @property
    def params(self):
        return self._params

    @property
    def opt_state(self):
        return self._opt_state

    def state_for_checkpoint(self):
        # Host copies: live device buffers would be donated (deleted) by the
        # next step, leaving the checkpoint pointing at freed memory.
        snap = jax.tree_util.tree_map(lambda a: np.asarray(jax.device_get(a)),
                                      (self._params, self._opt_state, self._buffers))
        state = {"params": snap[0], "opt_state": snap[1], "buffers": snap[2],
                 "step": self._step}
        if self._grad_accum is not None:
            state["grad_accum"] = jax.tree_util.tree_map(
                lambda a: np.asarray(jax.device_get(a)), self._grad_accum)
            state["micro"] = int(jax.device_get(self._micro))
        return state

    def topology(self):
        """Topology/flags metadata stamped into ``state_dict()`` (and into
        the CheckpointManager manifest, CRC-covered): mesh axis sizes, the
        dp axis size the packed slot layout was produced for, weight-
        update-sharding and accumulation flags, the wire dtype, and the
        bucket-plan fingerprint. ``load_state_dict`` uses the record to
        reshard a checkpoint onto a DIFFERENT mesh
        (distributed/topology.py) — or to name the differing fields when it
        cannot. Reflects the STORED layout: ``wus``/``dp`` come from the
        resolved grad-comm config once compiled, from the mesh hint
        before."""
        from .. import flags as _flags
        mesh_axes = {}
        if self.mesh is not None:
            mesh_axes = {a: int(self.mesh.shape[a])
                         for a in self.mesh.axis_names
                         if int(self.mesh.shape[a]) > 1}
        cfg = self._gc_cfg
        wus = bool(cfg is not None and cfg.weight_update_sharding)
        if cfg is not None:
            dp = int(cfg.n)
        else:
            dp = next((mesh_axes[a] for a in ("dp", "sharding")
                       if a in mesh_axes), 1)
        return {
            "format": 1,
            "mesh_axes": mesh_axes,
            "dp": dp,
            "wus": wus,
            "accumulate_steps": int(self.accumulate_steps),
            "wire_dtype": str(_flags._FLAGS.get("FLAGS_allreduce_dtype",
                                                "float32")),
            "bucket_plan": (cfg.plan.fingerprint()
                            if cfg is not None and cfg.plan is not None
                            else None),
        }

    def state_dict(self):
        """Complete training state for EXACT resume: params, buffers,
        optimizer slots (packed dp-sharded layout preserved as stored —
        no full materialization on either side), gradient accumulator +
        micro position, the global RNG stream (framework/random), the LR
        scheduler, and — when attached — GradScaler scaling state and the
        DataLoader's epoch position. A run killed at step t and
        ``load_state_dict``-resumed reproduces the uninterrupted
        trajectory bitwise. The ``topology`` record makes the snapshot
        loadable on a DIFFERENT mesh: ``load_state_dict`` reshards the
        packed slot layout for the destination dp size (reshard-on-load),
        so a dp=8 checkpoint resumes on the dp=4 mesh that survives a
        host loss."""
        state = self.state_for_checkpoint()
        state["topology"] = self.topology()
        from ..framework import random as _rnd
        state["rng"] = _rnd.state_dict()
        from ..optimizer.lr import LRScheduler
        if isinstance(self.optimizer._learning_rate, LRScheduler):
            state["lr_sched"] = self.optimizer._learning_rate.state_dict()
        if self._attached_scaler is not None:
            state["scaler"] = self._attached_scaler.state_dict()
        if self._attached_loader is not None and hasattr(
                self._attached_loader, "state_dict"):
            state["loader"] = self._attached_loader.state_dict()
        state["format_version"] = 2
        return state

    def load_state_dict(self, state):
        """Restore a ``state_dict()`` snapshot (also accepts the bare
        ``state_for_checkpoint`` layout). Slot layout differences between
        the saving and restoring schedule (packed (n, cols) vs
        param-shaped) are normalized; under a mesh the leaves are
        device_put straight to their target shardings — a packed
        dp-sharded slot checkpoint restores shard-wise without ever
        materializing the full slot tensors in one buffer."""
        self.restore_from_checkpoint(state)
        if "rng" in state:
            from ..framework import random as _rnd
            _rnd.set_state_dict(state["rng"])
        if "lr_sched" in state:
            from ..optimizer.lr import LRScheduler
            if isinstance(self.optimizer._learning_rate, LRScheduler):
                self.optimizer._learning_rate.set_state_dict(
                    dict(state["lr_sched"]))
        if "scaler" in state and self._attached_scaler is not None:
            self._attached_scaler.load_state_dict(dict(state["scaler"]))
        if "loader" in state and self._attached_loader is not None and \
                hasattr(self._attached_loader, "load_state_dict"):
            self._attached_loader.load_state_dict(dict(state["loader"]))
        self._bad_streak = 0
        self.last_step_ok = True
        self._pending_ok = []
        self.optimizer._step_count = self._step

    def restore_from_checkpoint(self, state):
        # under a mesh, keep host (numpy) leaves as-is: shard_params below
        # device_puts each leaf straight to its target sharding (packed
        # dp-sharded slots restore shard-wise, no replicated intermediate);
        # without a mesh, arrays go to the default device here
        from ..distributed import topology as _rs
        from .. import flags as _flags
        src_topo = state.get("topology")
        # wrong-model loads fail HERE with the differing params named,
        # not deep inside a slot reshape
        _rs.check_params(state.get("params"), self._params)
        # strict mode: refuse a cross-topology load up front — BEFORE the
        # compiled/uncompiled split, so an uncompiled step cannot slip the
        # reshard through its first-call pack path
        if src_topo is not None and \
                not _flags._FLAGS.get("FLAGS_elastic_reshard", True):
            dst_topo = self.topology()
            if (src_topo.get("dp") != dst_topo.get("dp")
                    or src_topo.get("mesh_axes") != dst_topo.get(
                        "mesh_axes")):
                diffs = _rs.diff_topology(src_topo, dst_topo)
                _rs.note_rejected()
                raise _rs.TopologyMismatchError(
                    "FLAGS_elastic_reshard is off and the checkpoint "
                    "topology differs — " + _rs.describe_diff(diffs))
        state = dict(state)
        if src_topo is not None and "grad_accum" in state:
            # a k change across the restore is only legal at a window
            # boundary (named diagnosis otherwise); at a boundary the
            # window count restarts under the new k
            micro = _rs.check_accum_window(state, src_topo,
                                           self.accumulate_steps)
            if self.accumulate_steps > 1:
                state["micro"] = 0 if micro is None else micro
            else:
                # boundary snapshot into a non-accumulating step: the
                # accumulator is zeros — drop it
                state.pop("grad_accum")
                state.pop("micro", None)
        if self._jitted is not None:
            # the compiled step fixed a slot layout at build time:
            # reshard-on-load maps whatever the checkpoint stored —
            # param-shaped, packed for THIS axis size, or packed for a
            # DIFFERENT mesh's — onto it, leaf by leaf in host numpy
            # (streamed; the full optimizer state never materializes in
            # one buffer), before any device placement
            wus = (self._gc_cfg is not None
                   and self._gc_cfg.weight_update_sharding)
            n_dst = self._gc_cfg.n if wus else None
            pshapes = {nm: tuple(np.shape(a))
                       for nm, a in state["params"].items()}
            resharded = 0
            state["opt_state"], moved = _rs.reshard_opt_state(
                state["opt_state"], pshapes, n_dst)
            resharded += moved
            if "grad_accum" in state and self.accumulate_steps > 1:
                state["grad_accum"], moved = _rs.reshard_accum(
                    state["grad_accum"], pshapes, n_dst)
                resharded += moved
            if resharded:
                _rs.note_load(resharded)
        if self.mesh is not None:
            put = lambda tree: tree  # noqa: E731
        else:
            put = lambda tree: jax.tree_util.tree_map(  # noqa: E731
                jnp.asarray, tree)
        self._params = put(state["params"])
        self._opt_state = put(state["opt_state"])
        self._buffers = jax.tree_util.tree_map(jnp.asarray, state["buffers"])
        self._step = int(state["step"])
        if "grad_accum" in state and self.accumulate_steps > 1:
            self._grad_accum = put(state["grad_accum"])
            self._micro = jnp.asarray(state["micro"], jnp.int32)
            self._micro_py = int(state["micro"])
        elif self.accumulate_steps > 1:
            # checkpoint from a non-accumulating run: start a FRESH window
            # — keeping this step's live accumulator/micro would mix
            # pre-restore partial gradients into the first update
            self._grad_accum = jax.tree_util.tree_map(jnp.zeros_like,
                                                      self._grad_accum)
            self._micro = jnp.zeros((), jnp.int32)
            self._micro_py = 0
        # not compiled yet: leaves keep the checkpoint's layout — the first
        # __call__ resolves the schedule and pack_opt_state/_pack_leaf
        # reshards any foreign-packed leaves then (resolve() accepts them)
        if self.mesh is not None:
            self.shard_params()
        self.sync_to_model()
