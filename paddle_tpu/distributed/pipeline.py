"""Pipeline parallelism.

Re-design of fleet.meta_parallel.PipelineParallel (ref: python/paddle/
distributed/fleet/meta_parallel/pipeline_parallel.py, pp_utils/
p2p_communication.py). The reference implements 1F1B with explicit NCCL
send/recv between per-rank processes and a Python scheduler.

TPU-native: the schedule is a `lax.scan` over T = M + S - 1 ticks inside a
`shard_map` manual over the 'pp' mesh axis. Each tick every stage applies its
block stack and `ppermute`s the activation one hop around the ICI ring — a
circular GPipe. The BACKWARD schedule is not hand-written at all: jax
differentiates the scan+ppermute program, which yields the reversed-ring,
reversed-time schedule automatically, and XLA overlaps the collective with
compute. Bubble fraction matches GPipe: (S-1)/(M+S-1).

Stage bodies must be homogeneous (same program on every device — SPMD), which
matches the transformer use-case: embed/head run outside the pipelined region,
the repeated blocks run inside. Layer params are stacked on a leading [S,
layers_per_stage] axis, sharded P('pp') on axis 0.
"""
from __future__ import annotations

import functools
import threading
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from . import env


def pipeline_spmd(block_fn, stage_params, x_mb, *, axis_name="pp"):
    """Run inside a shard_map manual over `axis_name`.

    block_fn: (layer_params, activation) -> activation — ONE block; it is
        scanned over the local layers of the stage.
    stage_params: pytree, leaves [1, local_L, ...] (this stage's slice).
    x_mb: [M, mb, ...] microbatches (same on all stages; only stage 0 reads).
    Returns [M, mb, ...]: on the LAST stage these are the pipeline outputs;
    other stages return garbage that the caller discards (out_specs selects
    from the last stage).
    """
    S = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    M = x_mb.shape[0]
    T = M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]
    local_params = jax.tree_util.tree_map(lambda a: a[0], stage_params)
    full_stage_fn = _stage_fn_of(block_fn)

    def stage_fn(act):
        return full_stage_fn(local_params, act)

    outputs0 = _varying(jnp.zeros_like(x_mb), axis_name)
    hold0 = _varying(jnp.zeros(x_mb.shape[1:], x_mb.dtype), axis_name)

    def tick(carry, t):
        outputs, prev_out = carry
        shifted = lax.ppermute(prev_out, axis_name, perm)
        mb_idx = jnp.clip(t, 0, M - 1)
        first_in = lax.dynamic_index_in_dim(x_mb, mb_idx, 0, keepdims=False)
        inp = jnp.where(stage == 0, first_in, shifted)
        out = stage_fn(inp)
        out_idx = jnp.clip(t - (S - 1), 0, M - 1)
        cur = lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
        write = jnp.logical_and(stage == S - 1, t >= S - 1)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(write, out, cur), out_idx, 0)
        return (outputs, out), None

    (outputs, _), _ = lax.scan(tick, (outputs0, hold0), jnp.arange(T))
    # broadcast the last stage's outputs to every stage (replicated result):
    # mask + psum over the ring — cheap relative to the per-tick traffic
    masked = jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs))
    return lax.psum(masked, axis_name)


def _stage_fn_of(block_fn, remat_policy=None):
    """remat_policy (jax.checkpoint policy or None=full recompute) controls
    which per-layer residuals the stage vjp keeps during a backward tick —
    the per-tick analog of the single-chip selective-save policies
    (distributed/recompute.py POLICIES). Only meaningful on the hand-written
    1f1b backward paths, where jax.vjp(stage_fn, ...) runs within one tick.
    """
    if remat_policy is not None:
        block_fn = jax.checkpoint(block_fn, policy=remat_policy)

    def stage_fn(local_params, act):
        def scan_layer(h, layer_params):
            return block_fn(layer_params, h), None
        out, _ = lax.scan(scan_layer, act, local_params)
        return out
    return stage_fn


def _varying(a, axis_name):
    try:
        return lax.pcast(a, (axis_name,), to="varying")
    except ValueError:
        return a  # already varying over axis_name


def _gated_fwd(stage_fn, axis_name, active, pv, inp):
    """stage forward, skipped entirely (lax.cond) on inactive schedule slots
    so warmup/cooldown ticks don't burn MXU time on masked garbage."""
    return lax.cond(
        active,
        lambda a: stage_fn(pv, a),
        lambda a: _varying(jnp.zeros(inp.shape, inp.dtype), axis_name),
        inp)


def _gated_vjp(stage_fn, axis_name, active, pv, inp, gout):
    """(param_grads, input_grad) of the stage at `inp`, cond-gated like
    _gated_fwd."""
    def run(args):
        i, go = args
        _, vjp_fn = jax.vjp(stage_fn, pv, i)
        return vjp_fn(go)

    def zero(args):
        i, _ = args
        return (jax.tree_util.tree_map(
            lambda a: _varying(jnp.zeros_like(a), axis_name), pv),
            _varying(jnp.zeros(i.shape, i.dtype), axis_name))

    return lax.cond(active, run, zero, (inp, gout))


def pipeline_spmd_1f1b(block_fn, stage_params, x_mb, *, axis_name="pp",
                       remat_policy=None):
    """1F1B-scheduled pipeline (ref: fleet/meta_parallel/pipeline_parallel.py:230
    `forward_backward_pipeline`, the "1f1b scheduling strategy").

    Same contract as `pipeline_spmd`, but the backward pass is hand-scheduled
    instead of autodiff'd through the forward scan. Why: autodiff of the GPipe
    scan stores per-tick residuals for all T = M+S-1 ticks — activation
    residency O(M). Here the backward runs its own combined schedule: each tick
    does one forward (recomputing the activation stream) and one backward
    microbatch per stage, with a circular stash of at most K = 2S-1 in-flight
    stage *inputs* — residency O(S), independent of the microbatch count.

    Scheduling (stage s, tick t, microbatch indices):
      forward  of mb  fm = t - s
      backward of mb  bm = t - 2(S-1) + s     (same tick as fm on last stage)
      T = M + 2S - 2 ticks; stash slot = mb mod K, lifetime exactly <= K ticks.

    Cost: stage-input checkpointing (Megatron "full recompute" mode) — the
    backward recomputes each stage forward from the stashed input rather than
    stashing per-layer residuals, because vjp residuals would carry K copies of
    (cast) stage params. ~1 extra forward vs GPipe+autodiff, in exchange for
    O(S) instead of O(M) activation memory.
    """
    S = lax.axis_size(axis_name)
    M = x_mb.shape[0]
    stage_fn = _stage_fn_of(block_fn, remat_policy)

    @jax.custom_vjp
    def pipe(sp, xm):
        return pipeline_spmd(block_fn, sp, xm, axis_name=axis_name)

    def pipe_fwd(sp, xm):
        return pipe(sp, xm), (sp, xm)

    def pipe_bwd(res, g):
        sp, xm = res
        local_params = jax.tree_util.tree_map(lambda a: a[0], sp)
        stage = lax.axis_index(axis_name)
        K = 2 * S - 1
        T = M + 2 * S - 2
        perm_down = [(i, (i + 1) % S) for i in range(S)]
        perm_up = [(i, (i - 1) % S) for i in range(S)]
        mb_shape = x_mb.shape[1:]

        def vv(a):
            return _varying(a, axis_name)

        stash0 = vv(jnp.zeros((K,) + mb_shape, xm.dtype))
        send_f0 = vv(jnp.zeros(mb_shape, xm.dtype))
        send_b0 = vv(jnp.zeros(mb_shape, g.dtype))
        pgrads0 = jax.tree_util.tree_map(
            lambda a: vv(jnp.zeros(a.shape, a.dtype)), local_params)
        gx0 = vv(jnp.zeros_like(xm))

        def tick(carry, t):
            stash, send_f, send_b, pgrads, gx = carry
            recv_f = lax.ppermute(send_f, axis_name, perm_down)
            recv_b = lax.ppermute(send_b, axis_name, perm_up)

            # ---- forward sub-tick: recompute the activation stream
            fm = t - stage
            f_act = jnp.logical_and(fm >= 0, fm < M)
            first_in = lax.dynamic_index_in_dim(
                xm, jnp.clip(fm, 0, M - 1), 0, keepdims=False)
            inp = jnp.where(stage == 0, first_in, recv_f)
            out_f = _gated_fwd(stage_fn, axis_name, f_act, local_params, inp)
            slot_f = jnp.mod(fm, K)
            cur = lax.dynamic_index_in_dim(stash, slot_f, 0, keepdims=False)
            stash = lax.dynamic_update_index_in_dim(
                stash, jnp.where(f_act, inp, cur), slot_f, 0)

            # ---- backward sub-tick
            bm = t - 2 * (S - 1) + stage
            b_act = jnp.logical_and(bm >= 0, bm < M)
            slot_b = jnp.mod(bm, K)
            stashed_in = lax.dynamic_index_in_dim(
                stash, slot_b, 0, keepdims=False)
            g_last = lax.dynamic_index_in_dim(
                g, jnp.clip(bm, 0, M - 1), 0, keepdims=False)
            g_out = jnp.where(stage == S - 1, g_last.astype(send_b.dtype),
                              recv_b)
            gp, gi = _gated_vjp(stage_fn, axis_name, b_act, local_params,
                                stashed_in, g_out.astype(stashed_in.dtype))
            pgrads = jax.tree_util.tree_map(
                lambda acc, gg: acc + gg.astype(acc.dtype), pgrads, gp)
            write_gx = jnp.logical_and(b_act, stage == 0)
            cur_gx = lax.dynamic_index_in_dim(
                gx, jnp.clip(bm, 0, M - 1), 0, keepdims=False)
            gx = lax.dynamic_update_index_in_dim(
                gx, jnp.where(write_gx, gi.astype(gx.dtype), cur_gx),
                jnp.clip(bm, 0, M - 1), 0)
            return (stash, out_f, gi.astype(send_b.dtype), pgrads, gx), None

        carry0 = (stash0, send_f0, send_b0, pgrads0, gx0)
        (_, _, _, pgrads, gx), _ = lax.scan(tick, carry0, jnp.arange(T))
        # grads wrt the [1, L, ...] per-device param slice; x grads live on
        # stage 0 only (shard_map psums replicated-input cotangents).
        g_sp = jax.tree_util.tree_map(lambda a: a[None], pgrads)
        # xm entered replicated (in_spec P()), so its cotangent must leave
        # replicated/invariant too: mask to stage 0's contribution and psum.
        gx = lax.psum(jnp.where(stage == 0, gx, jnp.zeros_like(gx)), axis_name)
        return g_sp, gx

    pipe.defvjp(pipe_fwd, pipe_bwd)
    return pipe(stage_params, x_mb)


def pipeline_spmd_interleaved_1f1b(block_fn, stage_params, x_mb, *,
                                   num_virtual, axis_name="pp",
                                   remat_policy=None):
    """Interleaved ("virtual pipeline") 1F1B (ref: fleet/meta_parallel/
    pipeline_parallel.py:613 interleaved schedule / VPP).

    Device s hosts V = num_virtual chunks — virtual stages p = s, s+S, ...,
    s+(V-1)S of a flat S' = V*S stage pipeline. Per tick each device runs its
    active virtual-stage chunks (fwd of mb t-p, bwd of mb t-2(S'-1)+p),
    `lax.cond`-gated so inactive warmup/cooldown slots skip the matmuls
    (interleaving only pays off when idle slots are cheap). All V streams
    ride one stacked ppermute per direction; the lap boundary (device S-1 →
    device 0, lap v → v+1) is a roll of the stacked recv buffer.

    stage_params leaves: [1, V, L_chunk, ...] — this device's V chunks.
    x_mb: [M, mb...]; returns [M, mb...] like pipeline_spmd.
    """
    S = lax.axis_size(axis_name)
    V = num_virtual
    Sv = V * S
    M = x_mb.shape[0]
    stage_fn = _stage_fn_of(block_fn, remat_policy)
    mb_shape = x_mb.shape[1:]
    perm_down = [(i, (i + 1) % S) for i in range(S)]
    perm_up = [(i, (i - 1) % S) for i in range(S)]

    def chunk_params(sp, v):
        return jax.tree_util.tree_map(lambda a: a[0, v], sp)

    def gated_fwd(active, pv, inp):
        return _gated_fwd(stage_fn, axis_name, active, pv, inp)

    @jax.custom_vjp
    def pipe(sp, xm):
        stage = lax.axis_index(axis_name)
        T = M + Sv - 1

        def vv(a):
            return _varying(a, axis_name)

        fsend0 = vv(jnp.zeros((V,) + mb_shape, xm.dtype))
        outputs0 = vv(jnp.zeros_like(xm))

        def tick(carry, t):
            fsend, outputs = carry
            recv = lax.ppermute(fsend, axis_name, perm_down)
            # lap boundary: device 0's lap v reads device S-1's lap v-1
            recv = jnp.where(stage == 0, jnp.roll(recv, 1, axis=0), recv)
            outs = []
            for v in range(V):
                p = stage + v * S
                fm = t - p
                active = jnp.logical_and(fm >= 0, fm < M)
                first_in = lax.dynamic_index_in_dim(
                    xm, jnp.clip(fm, 0, M - 1), 0, keepdims=False)
                inp = recv[v]
                if v == 0:
                    inp = jnp.where(stage == 0, first_in, inp)
                outs.append(gated_fwd(active, chunk_params(sp, v), inp))
            out_last = outs[V - 1]
            out_idx = jnp.clip(t - (Sv - 1), 0, M - 1)
            write = jnp.logical_and(
                jnp.logical_and(stage == S - 1, t >= Sv - 1), t - (Sv - 1) < M)
            cur = lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
            outputs = lax.dynamic_update_index_in_dim(
                outputs, jnp.where(write, out_last, cur), out_idx, 0)
            return (jnp.stack(outs), outputs), None

        (_, outputs), _ = lax.scan(tick, (fsend0, outputs0), jnp.arange(T))
        masked = jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs))
        return lax.psum(masked, axis_name)

    def pipe_fwd(sp, xm):
        return pipe(sp, xm), (sp, xm)

    def pipe_bwd(res, g):
        sp, xm = res
        stage = lax.axis_index(axis_name)
        K = 2 * Sv - 1
        T = M + 2 * Sv - 2

        def vv(a):
            return _varying(a, axis_name)

        stash0 = vv(jnp.zeros((V, K) + mb_shape, xm.dtype))
        fsend0 = vv(jnp.zeros((V,) + mb_shape, xm.dtype))
        bsend0 = vv(jnp.zeros((V,) + mb_shape, g.dtype))
        pgrads0 = jax.tree_util.tree_map(
            lambda a: vv(jnp.zeros(a.shape[1:], a.dtype)), sp)  # [V, Lc, ...]
        gx0 = vv(jnp.zeros_like(xm))

        def gated_vjp(active, pv, inp, gout):
            return _gated_vjp(stage_fn, axis_name, active, pv, inp, gout)

        def tick(carry, t):
            stash, fsend, bsend, pgrads, gx = carry
            recv_f = lax.ppermute(fsend, axis_name, perm_down)
            recv_f = jnp.where(stage == 0, jnp.roll(recv_f, 1, axis=0), recv_f)
            recv_b = lax.ppermute(bsend, axis_name, perm_up)
            # lap boundary reversed: device S-1's lap v reads dev 0's lap v+1
            recv_b = jnp.where(stage == S - 1, jnp.roll(recv_b, -1, axis=0),
                               recv_b)

            f_outs, b_outs = [], []
            new_pgrads = []
            for v in range(V):
                p = stage + v * S
                pv = chunk_params(sp, v)
                # ---- forward sub-tick for chunk v
                fm = t - p
                f_act = jnp.logical_and(fm >= 0, fm < M)
                first_in = lax.dynamic_index_in_dim(
                    xm, jnp.clip(fm, 0, M - 1), 0, keepdims=False)
                inp = recv_f[v]
                if v == 0:
                    inp = jnp.where(stage == 0, first_in, inp)
                f_outs.append(gated_fwd(f_act, pv, inp))
                slot_f = jnp.mod(fm, K)
                cur = lax.dynamic_index_in_dim(stash[v], slot_f, 0,
                                               keepdims=False)
                stash = stash.at[v].set(lax.dynamic_update_index_in_dim(
                    stash[v], jnp.where(f_act, inp, cur), slot_f, 0))

                # ---- backward sub-tick for chunk v
                bm = t - 2 * (Sv - 1) + p
                b_act = jnp.logical_and(bm >= 0, bm < M)
                slot_b = jnp.mod(bm, K)
                stashed_in = lax.dynamic_index_in_dim(stash[v], slot_b, 0,
                                                      keepdims=False)
                g_last = lax.dynamic_index_in_dim(
                    g, jnp.clip(bm, 0, M - 1), 0, keepdims=False)
                gout = recv_b[v]
                if v == V - 1:
                    gout = jnp.where(stage == S - 1,
                                     g_last.astype(gout.dtype), gout)
                gp, gi = gated_vjp(b_act, pv, stashed_in,
                                   gout.astype(stashed_in.dtype))
                new_pgrads.append(gp)
                b_outs.append(gi.astype(bsend.dtype))
                if v == 0:
                    write_gx = jnp.logical_and(b_act, stage == 0)
                    cur_gx = lax.dynamic_index_in_dim(
                        gx, jnp.clip(bm, 0, M - 1), 0, keepdims=False)
                    gx = lax.dynamic_update_index_in_dim(
                        gx, jnp.where(write_gx, gi.astype(gx.dtype), cur_gx),
                        jnp.clip(bm, 0, M - 1), 0)

            pgrads = jax.tree_util.tree_map(
                lambda acc, *gs: acc + jnp.stack(gs).astype(acc.dtype),
                pgrads, *new_pgrads)
            return (stash, jnp.stack(f_outs), jnp.stack(b_outs), pgrads,
                    gx), None

        carry0 = (stash0, fsend0, bsend0, pgrads0, gx0)
        (_, _, _, pgrads, gx), _ = lax.scan(tick, carry0, jnp.arange(T))
        g_sp = jax.tree_util.tree_map(lambda a: a[None], pgrads)
        gx = lax.psum(jnp.where(stage == 0, gx, jnp.zeros_like(gx)), axis_name)
        return g_sp, gx

    pipe.defvjp(pipe_fwd, pipe_bwd)
    return pipe(stage_params, x_mb)


# ---------------------------------------------------------------------------
# explicit pp backend (FLAGS_comm_backend='pp=ring|fused'): the SAME schedules
# rewritten to run under a FULL-manual shard_map over every mesh axis. The
# partitioner never sees this region, so the `stage == k` selects operate on
# per-device shards — no replicated-then-repartitioned tensor exists for
# GSPMD to involuntarily rematerialize. Boundary sends are issued at the END
# of each scan tick (the ppermute start rides the ICI while the next tick's
# stage GEMMs run; the done lands where the next tick consumes it).
#
# Contract differences vs the gspmd schedules above:
#   * x_mb is the LOCAL batch shard [M, mb/dp, ...] (in_spec P(None, 'dp'...))
#     — not the replicated full microbatch array;
#   * the result is STAGE-MAJOR: [1, M, mb/dp, ...] per device, out_spec
#     P('pp', ...), and the caller slices stage S-1 outside the region. This
#     is load-bearing for autodiff: an out_spec that mentions 'pp' makes the
#     shard_map transpose hand each stage its own slice's cotangent verbatim
#     (an UNMENTIONED manual axis would divide the cotangent by S — observed);
#   * scan tick indices are explicitly int32: with jax_enable_x64 the default
#     int64 `jnp.arange` mixed with the int32 `lax.axis_index` produces
#     invalid partitioned HLO (s64/s32 compare) when the out_spec mentions
#     the manual axis.


def pipeline_ring_gpipe(block_fn, stage_params, x_mb, *, axis_name="pp",
                        wire_dtype=None, boundary=None):
    """Circular GPipe under full-manual: autodiff derives the backward
    (reversed-ring, reversed-time) schedule, including the transpose of the
    tick-end boundary send. `wire_dtype` compresses the boundary hop (e.g.
    bf16 wire under fp32 compute); `boundary` is the fused rung's hook —
    ``boundary(last_layer_params, h) -> (block_out, received)`` runs the
    stage's LAST layer with the boundary send fused into its final GEMM's
    epilogue (fused_collectives.fused_gemm_ppsend); the hook owns the hop,
    so no separate ppermute is issued for it."""
    S = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    M = x_mb.shape[0]
    T = M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]
    local_params = jax.tree_util.tree_map(lambda a: a[0], stage_params)
    wire = jnp.dtype(wire_dtype) if wire_dtype is not None else x_mb.dtype

    if boundary is None:
        full_stage_fn = _stage_fn_of(block_fn)

        def run_stage(act):
            out = full_stage_fn(local_params, act)
            recv = lax.ppermute(out.astype(wire), axis_name, perm)
            return out, recv
    else:
        head = jax.tree_util.tree_map(lambda a: a[:-1], local_params)
        last = jax.tree_util.tree_map(lambda a: a[-1], local_params)
        head_fn = _stage_fn_of(block_fn)

        def run_stage(act):
            h = head_fn(head, act)
            out, recv = boundary(last, h)
            return out, recv.astype(wire)

    outputs0 = jnp.zeros_like(x_mb)
    recv0 = jnp.zeros(x_mb.shape[1:], wire)

    def tick(carry, t):
        t = t.astype(stage.dtype)
        outputs, recv = carry
        mb_idx = jnp.clip(t, 0, M - 1)
        first_in = lax.dynamic_index_in_dim(x_mb, mb_idx, 0, keepdims=False)
        inp = jnp.where(stage == 0, first_in, recv.astype(x_mb.dtype))
        out, recv_next = run_stage(inp)
        out_idx = jnp.clip(t - (S - 1), 0, M - 1)
        cur = lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
        write = jnp.logical_and(stage == S - 1, t >= S - 1)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(write, out, cur), out_idx, 0)
        return (outputs, recv_next), None

    (outputs, _), _ = lax.scan(tick, (outputs0, recv0),
                               jnp.arange(T, dtype=jnp.int32))
    # stage-major result; the last ring hop's cotangent closes the loop in
    # the transpose — no masked-psum broadcast (its all-reduce is exactly
    # the replicated tensor this path exists to kill)
    return outputs[None]


def pipeline_ring_1f1b(block_fn, stage_params, x_mb, *, axis_name="pp",
                       wire_dtype=None, remat_policy=None):
    """1F1B under full-manual — `pipeline_spmd_1f1b`'s hand-scheduled
    backward (stash K=2S-1, combined fwd/bwd ticks, O(S) residency) with the
    explicit-backend contract: boundary activations ride a `wire_dtype` hop
    issued at tick end, cotangents ride the reversed ring the same way, and
    per-stage param grads accumulate in the PARAM dtype (fp32 master params
    give fp32 accumulation under a bf16 wire for free)."""
    S = lax.axis_size(axis_name)
    M = x_mb.shape[0]
    stage_fn = _stage_fn_of(block_fn, remat_policy)
    wire = jnp.dtype(wire_dtype) if wire_dtype is not None else x_mb.dtype

    @jax.custom_vjp
    def pipe(sp, xm):
        return pipeline_ring_gpipe(block_fn, sp, xm, axis_name=axis_name,
                                   wire_dtype=wire_dtype)

    def pipe_fwd(sp, xm):
        return pipe(sp, xm), (sp, xm)

    def pipe_bwd(res, g):
        sp, xm = res
        g = g[0]  # stage-major [1, M, mb, ...] output cotangent, this shard
        local_params = jax.tree_util.tree_map(lambda a: a[0], sp)
        stage = lax.axis_index(axis_name)
        K = 2 * S - 1
        T = M + 2 * S - 2
        perm_down = [(i, (i + 1) % S) for i in range(S)]
        perm_up = [(i, (i - 1) % S) for i in range(S)]
        mb_shape = xm.shape[1:]

        stash0 = jnp.zeros((K,) + mb_shape, xm.dtype)
        recv_f0 = jnp.zeros(mb_shape, wire)
        recv_b0 = jnp.zeros(mb_shape, wire)
        pgrads0 = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype), local_params)
        gx0 = jnp.zeros_like(xm)

        def tick(carry, t):
            t = t.astype(stage.dtype)
            stash, recv_f, recv_b, pgrads, gx = carry

            # ---- forward sub-tick: recompute the activation stream
            fm = t - stage
            f_act = jnp.logical_and(fm >= 0, fm < M)
            first_in = lax.dynamic_index_in_dim(
                xm, jnp.clip(fm, 0, M - 1), 0, keepdims=False)
            inp = jnp.where(stage == 0, first_in, recv_f.astype(xm.dtype))
            out_f = _gated_fwd(stage_fn, axis_name, f_act, local_params, inp)
            slot_f = jnp.mod(fm, K)
            cur = lax.dynamic_index_in_dim(stash, slot_f, 0, keepdims=False)
            stash = lax.dynamic_update_index_in_dim(
                stash, jnp.where(f_act, inp, cur), slot_f, 0)

            # ---- backward sub-tick
            bm = t - 2 * (S - 1) + stage
            b_act = jnp.logical_and(bm >= 0, bm < M)
            slot_b = jnp.mod(bm, K)
            stashed_in = lax.dynamic_index_in_dim(
                stash, slot_b, 0, keepdims=False)
            g_last = lax.dynamic_index_in_dim(
                g, jnp.clip(bm, 0, M - 1), 0, keepdims=False)
            g_out = jnp.where(stage == S - 1, g_last.astype(wire), recv_b)
            gp, gi = _gated_vjp(stage_fn, axis_name, b_act, local_params,
                                stashed_in, g_out.astype(stashed_in.dtype))
            pgrads = jax.tree_util.tree_map(
                lambda acc, gg: acc + gg.astype(acc.dtype), pgrads, gp)
            write_gx = jnp.logical_and(b_act, stage == 0)
            cur_gx = lax.dynamic_index_in_dim(
                gx, jnp.clip(bm, 0, M - 1), 0, keepdims=False)
            gx = lax.dynamic_update_index_in_dim(
                gx, jnp.where(write_gx, gi.astype(gx.dtype), cur_gx),
                jnp.clip(bm, 0, M - 1), 0)

            # ---- boundary sends, issued at tick end: both hops ride the
            # wire while the NEXT tick's stage fwd+bwd GEMMs run
            recv_f = lax.ppermute(out_f.astype(wire), axis_name, perm_down)
            recv_b = lax.ppermute(gi.astype(wire), axis_name, perm_up)
            return (stash, recv_f, recv_b, pgrads, gx), None

        carry0 = (stash0, recv_f0, recv_b0, pgrads0, gx0)
        (_, _, _, pgrads, gx), _ = lax.scan(tick, carry0,
                                            jnp.arange(T, dtype=jnp.int32))
        g_sp = jax.tree_util.tree_map(lambda a: a[None], pgrads)
        # xm entered as a batch shard replicated over 'pp' only; mask the
        # cotangent to stage 0's contribution WITHOUT a psum — shard_map's
        # transpose already psums over the in_spec-unmentioned pp axis
        gx = jnp.where(stage == 0, gx, jnp.zeros_like(gx))
        return g_sp, gx

    pipe.defvjp(pipe_fwd, pipe_bwd)
    return pipe(stage_params, x_mb)


def vpp_storage_perm(L, S, V):
    """Stage-major storage order for interleaved VPP: storage slot
    s*(V*Lc)+v*Lc+p holds logical layer (v*S+s)*Lc+p. Stacked params
    pre-permuted this way shard over 'pp' as a plain contiguous split —
    no cross-device reshard at the shard_map boundary (the layout the
    swapaxes in run_pipeline would otherwise create on the fly)."""
    Lc = L // (S * V)
    assert Lc * S * V == L, f"layers {L} != pp {S} x interleave {V} x chunk"
    return [(v * S + s) * Lc + p
            for s in range(S) for v in range(V) for p in range(Lc)]


def run_pipeline(block_fn, stacked_params, x, num_microbatches, mesh=None,
                 axis_name="pp", data_spec=P(), schedule="gpipe",
                 interleave=1, vpp_stage_major=False, remat_policy=None,
                 backend=None, pp_param_specs=None, x_spec=None,
                 wire_dtype=None, boundary=None):
    """Host-side wrapper: shard_map(manual over 'pp', auto elsewhere).

    stacked_params: pytree, leaves [S * local_L, ...] stacked layer params.
    x: [B, ...] activations entering the pipelined blocks.
    Returns [B, ...] outputs of the last stage (broadcast to all stages).

    With ``vpp_stage_major`` the caller stores stacked params in
    `vpp_storage_perm` order so the interleaved reshape is contiguous and
    the 'pp' sharding of storage matches chunk placement exactly (avoids
    XLA's involuntary full rematerialization of every block param).

    ``backend`` 'ring'|'fused' (comm_backend.resolve_pp) switches to the
    FULL-manual explicit schedules (`pipeline_ring_*`): every mesh axis is
    bound, so ``pp_param_specs`` must give the stacked leaves' full specs
    (leading 'pp'; e.g. gpt_param_specs' blocks) and ``x_spec`` the batch
    activation spec — any axis they name is sharded INTO the region instead
    of replicated-then-repartitioned by the partitioner. ``boundary`` is the
    fused rung's last-GEMM hook (see pipeline_ring_gpipe); 'fused' without a
    boundary runs identically to 'ring'.
    """
    mesh = mesh or env.get_mesh()
    S = mesh.shape[axis_name]
    M = num_microbatches
    B = x.shape[0]
    V = interleave
    assert B % M == 0, f"batch {B} not divisible by microbatches {M}"

    if V > 1 and vpp_stage_major:
        def reshape_stages(a):
            Lc = a.shape[0] // (V * S)
            return a.reshape((S, V, Lc) + a.shape[1:])  # contiguous
    elif V > 1:
        # chunk c of V*S covers layers [c*Lc, (c+1)*Lc); device c%S, lap c//S
        def reshape_stages(a):
            Lc = a.shape[0] // (V * S)
            vs_major = a.reshape((V, S, Lc) + a.shape[1:])
            return jnp.swapaxes(vs_major, 0, 1)          # [S, V, Lc, ...]
    else:
        def reshape_stages(a):
            return a.reshape((S, a.shape[0] // S) + a.shape[1:])

    staged = jax.tree_util.tree_map(reshape_stages, stacked_params)
    x_mb = x.reshape((M, B // M) + x.shape[1:])

    param_specs = jax.tree_util.tree_map(
        lambda a: P("pp", *([None] * (a.ndim - 1))), staged)

    if backend in ("ring", "fused"):
        if V > 1:
            raise ValueError(
                "the explicit pp backend does not interleave virtual stages"
                " (comm_backend.resolve_pp gates this)")
        if pp_param_specs is not None:
            # stacked-leaf specs (leading 'pp' over [L, ...]) -> staged
            # [S, L/S, ...]: the layer dim splits in two, sharding unchanged
            param_specs = jax.tree_util.tree_map(
                lambda a, s: P("pp", None, *tuple(s)[1:]),
                staged, pp_param_specs)
        xs = tuple(x_spec) if x_spec is not None else ()
        if schedule == "1f1b":
            inner = functools.partial(
                pipeline_ring_1f1b, block_fn, axis_name=axis_name,
                wire_dtype=wire_dtype, remat_policy=remat_policy)
        else:
            if remat_policy is not None:
                raise ValueError(
                    "remat_policy requires the 1f1b schedule (the gpipe "
                    "autodiff path derives its own recompute from the scan)")
            inner = functools.partial(
                pipeline_ring_gpipe, block_fn, axis_name=axis_name,
                wire_dtype=wire_dtype, boundary=boundary)
        mapped = jax.shard_map(
            lambda p, xm: inner(p, xm), mesh=mesh,
            in_specs=(param_specs, P(None, *xs)),
            out_specs=P("pp", None, *xs), check_vma=False)
        out_smb = mapped(staged, x_mb)
        # stage-major [S, M, mb, ...]: slice the last stage's outputs (the
        # one cross-stage broadcast of the step, replacing the seed's
        # masked-psum of the whole output buffer every scan tick)
        out_mb = lax.index_in_dim(out_smb, S - 1, 0, keepdims=False)
        return out_mb.reshape((B,) + out_mb.shape[2:])

    if V > 1:
        assert schedule == "1f1b", "interleaving requires the 1f1b schedule"
        spmd = functools.partial(pipeline_spmd_interleaved_1f1b,
                                 num_virtual=V, remat_policy=remat_policy)
    elif schedule == "1f1b":
        spmd = functools.partial(pipeline_spmd_1f1b,
                                 remat_policy=remat_policy)
    else:
        if remat_policy is not None:
            raise ValueError(
                "remat_policy requires the 1f1b schedule (the gpipe autodiff "
                "path derives its own recompute from the scan)")
        spmd = pipeline_spmd
    inner = functools.partial(spmd, block_fn, axis_name=axis_name)
    mapped = jax.shard_map(
        lambda p, xm: inner(p, xm),
        mesh=mesh, in_specs=(param_specs, P()), out_specs=P(),
        axis_names=frozenset({axis_name}), check_vma=False)
    out_mb = mapped(staged, x_mb)
    return out_mb.reshape((B,) + out_mb.shape[2:])


# ---------------------------------------------------------------------------
# static schedule ledger + per-step counters (profiler.pp_comm_counters —
# the pp-axis sibling of tp_overlap's mp ledger and grad_comm's dp ledger)


@dataclass
class PpStepRecord:
    """Per-device pp-axis boundary traffic of one executed step (fwd+bwd).
    ``bubble_fraction`` is the schedule's idle-slot estimate — gpipe
    (S-1)/(M+S-1), 1f1b (2S-2)/(M+2S-2) — not a measurement."""
    backend: str = "gspmd"       # the pp backend that produced this step
    schedule: str = "gpipe"
    stages: int = 1
    microbatches: int = 1
    boundary_bytes: int = 0      # wire bytes over the boundary hops
    ppermute_hops: int = 0       # explicit ppermutes issued (ring/fused)
    fused_dispatches: int = 0    # boundary Pallas kernel launches (fused)
    bubble_fraction: float = 0.0


def bubble_fraction(schedule, S, M):
    """Idle-slot fraction of the schedule at S stages, M microbatches."""
    if S <= 1:
        return 0.0
    if schedule == "1f1b":
        return (2 * S - 2) / (M + 2 * S - 2)
    return (S - 1) / (M + S - 1)


def gpt_pp_step_record(config, ppc, batch, seq, num_microbatches, S=None,
                       mp=1):
    """Ledger of one gpt_hybrid pipelined step. ``ppc`` is the resolved
    comm_backend.PpConfig or None (None = GSPMD schedule: backend label and
    bubble estimate only — the partitioner owns that wire traffic)."""
    import jax.numpy as _jnp
    S = int(ppc.n if ppc is not None else S)
    M = int(num_microbatches)
    sched = (ppc.schedule if ppc is not None
             else (getattr(config, "pp_schedule", "1f1b") or "1f1b"))
    rec = PpStepRecord(backend=ppc.backend if ppc is not None else "gspmd",
                       schedule=sched, stages=S, microbatches=M,
                       bubble_fraction=bubble_fraction(sched, S, M))
    if ppc is None:
        return rec
    compute = _jnp.dtype(config.compute_dtype or "float32")
    wire = _jnp.dtype(ppc.wire_dtype) if ppc.wire_dtype is not None \
        else compute
    # one boundary hop moves the LOCAL microbatch activation shard
    hop_bytes = (batch // M) * (seq // mp) * config.hidden_size \
        * wire.itemsize
    T_fwd = M + S - 1
    if sched == "1f1b":
        # fwd = the gpipe stream (custom-vjp primal), bwd = T=M+2S-2
        # combined ticks x (one activation hop down + one cotangent hop up)
        hops = T_fwd + 2 * (M + 2 * S - 2)
    else:
        hops = 2 * T_fwd  # autodiff'd transpose mirrors the fwd hops
    rec.boundary_bytes = hops * hop_bytes
    if ppc.backend == "fused" and ppc.fused_rdma:
        rec.fused_dispatches = 2 * T_fwd  # one boundary kernel per tick
        # the kernel epilogue's RDMA replaces the fwd/bwd boundary
        # ppermutes; only the 1f1b-style scheduling hops remain (none
        # on the gpipe schedule the fused rung runs)
        hops -= 2 * T_fwd
    rec.ppermute_hops = hops
    return rec


_pp_lock = threading.Lock()


def _zero_pp_counters():
    return {"steps": 0, "boundary_bytes": 0, "ppermute_hops": 0,
            "fused_dispatches": 0, "backend": {}, "schedule": "",
            "stages": 0, "microbatches": 0, "bubble_fraction": 0.0}


_pp_counters = _zero_pp_counters()


def record_pp_step(rec: PpStepRecord | None):
    if rec is None:
        return
    with _pp_lock:
        _pp_counters["steps"] += 1
        _pp_counters["boundary_bytes"] += rec.boundary_bytes
        _pp_counters["ppermute_hops"] += rec.ppermute_hops
        _pp_counters["fused_dispatches"] += rec.fused_dispatches
        _pp_counters["backend"]["pp"] = rec.backend
        _pp_counters["schedule"] = rec.schedule
        _pp_counters["stages"] = rec.stages
        _pp_counters["microbatches"] = rec.microbatches
        _pp_counters["bubble_fraction"] = rec.bubble_fraction


def pp_counters():
    with _pp_lock:
        out = dict(_pp_counters)
        out["backend"] = dict(out["backend"])
    return out


def reset_pp_counters():
    global _pp_counters
    with _pp_lock:
        _pp_counters = _zero_pp_counters()


# ---------------------------------------------------------------------------
# fleet-style API surface (ref: fleet/meta_parallel/parallel_layers/pp_layers.py)
class LayerDesc:
    def __init__(self, layer_cls, *args, **kwargs):
        self.layer_cls = layer_cls
        self.args = args
        self.kwargs = kwargs

    def build_layer(self):
        return self.layer_cls(*self.args, **self.kwargs)


class SharedLayerDesc(LayerDesc):
    def __init__(self, key, layer_cls, forward_func=None, shared_weight_attr="weight",
                 *args, **kwargs):
        super().__init__(layer_cls, *args, **kwargs)
        self.layer_name = key
        self.forward_func = forward_func
        self.shared_weight_attr = shared_weight_attr


class PipelineLayer:
    """API-parity container describing a pipelined model. On TPU the pipeline
    executes via `run_pipeline` (scan+ppermute); this class assigns descs to
    stages and materializes the homogeneous middle blocks for stacking."""

    def __init__(self, layers, num_stages=None, topology=None, loss_fn=None,
                 seg_method="uniform", recompute_interval=0, **kwargs):
        self.descs = layers
        self.num_stages = num_stages or (env.get_mesh().shape.get("pp", 1)
                                         if env.get_mesh() else 1)
        self.loss_fn = loss_fn
        self._layers = [d.build_layer() if isinstance(d, LayerDesc) else d
                        for d in layers]

    def get_stage_from_index(self, idx):
        per = max(len(self._layers) // self.num_stages, 1)
        return min(idx // per, self.num_stages - 1)

    def forward(self, x):
        for l in self._layers:
            x = l(x) if callable(l) else l.forward(x)
        return x

    def __call__(self, x):
        return self.forward(x)

    def sublayers(self):
        return list(self._layers)

    def parameters(self):
        out = []
        for l in self._layers:
            if hasattr(l, "parameters"):
                out.extend(l.parameters())
        return out
