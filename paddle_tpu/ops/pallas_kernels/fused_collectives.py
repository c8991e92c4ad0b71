"""Fused GEMM+collective Pallas kernels — the ``fused`` comm backend.

PR 3's ring backend (``tp_overlap.ring_ag_gemm``/``gemm_ring_rs``) overlaps
at the SCHEDULING level: each all-gather/reduce-scatter decomposes into
mp-1 ``ppermute`` hops with chunk GEMMs issued on arrival — but every hop
still materializes its chunk in HBM before the GEMM reads it. These
kernels fuse at the KERNEL level (papers: "Optimizing Distributed ML
Communication with Fused Computation-Collective Operations"
arXiv:2305.06942; T3 arXiv:2401.16677; EQuARX arXiv:2506.17615):

* ``fused_ag_gemm`` — all-gather + GEMM: each ring step issues the async
  remote copy (RDMA + semaphore wait) of the NEXT chunk into the other
  half of a double-buffered VMEM scratch while the chunk in hand runs its
  tile GEMM; gathered activations never exist in HBM.
* ``fused_gemm_rs`` — GEMM + reduce-scatter: the per-chunk partial GEMM's
  epilogue accumulates (fp32) directly into the traveling scatter
  destination, which is RDMA'd to the next device; the full-size partial
  product ``[B, S, H]`` is never materialized.
* ``fused_ag_accum_gemm`` — the weight-gradient sibling: ring-gathers the
  activation (or cotangent) chunks while accumulating the transposed
  per-chunk GEMMs into the weight-shaped output.
* ``fused_rs_bucket`` / ``fused_ag_bucket`` — grad_comm's bucketed flat
  (n, cols) reduce-scatter / all-gather as in-kernel rings; the RS
  epilogue optionally quantizes the traveling accumulator to a bf16 wire
  (EQuARX-style: compressed on the wire, fp32 local accumulation).
* ``fused_gemm_ag`` — the SERVING engine's column-parallel projection:
  the full-contraction block GEMM's epilogue feeds the ring all-gather
  of the output directly (no HBM round trip between GEMM and
  collective). Gather-only and full-K, so the result is BITWISE equal
  to the unsharded GEMM — the sharded engine's exactness contract.

CPU tier-1 parity runs the SAME kernels in Pallas interpret mode (the
``paged_attention`` kernel set this precedent); real-TPU routing is gated
by ``supported()``. jax<0.5's interpret-mode discharge rule for remote
DMAs supports exactly ONE named mesh axis, so interpret-mode eligibility
requires a single-axis mesh (``Mesh(devs, ('mp',))``); on a real TPU the
kernels compute flat logical device ids from every bound axis and any
full-manual mesh works.

Gradients: jax cannot differentiate through DMA kernels, so
``fused_ag_gemm``/``fused_gemm_rs`` carry custom VJPs whose backward
passes are themselves fused kernels (the transpose of an AG+GEMM is a
GEMM+RS of the cotangent and vice versa — the ring reverses for free).

Every wrapper counts its trace-time dispatches (``trace_counts()``) — the
audit hook for "the fused kernel actually runs" gates; the per-step
execution ledger lives with the schedule owners (tp_overlap / grad_comm).
"""
from __future__ import annotations

import functools
import logging
import threading

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger("paddle_tpu.fused_collectives")

_VMEM = pl.BlockSpec(memory_space=pltpu.VMEM)
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)

# distinct Mosaic collective ids per kernel family (barrier semaphores of
# concurrently-compiled kernels must not alias)
_CID = {"ag_gemm": 0, "gemm_rs": 1, "ag_accum": 2, "rs_bucket": 3,
        "ag_bucket": 4, "gemm_ag": 5, "gemm_ag_q": 6,
        "gemm_ppsend": 7, "gemm_pprecv": 8}


def interpret_default():
    """Interpret mode on every non-TPU backend (the tier-1 CPU path)."""
    return jax.default_backend() != "tpu"


def supported(mesh, shapes=(), why=""):
    """Routing predicate for the fused kernels (same pattern as
    ``paged_attention.paged_kernel_supported``): interpret mode needs a
    single-named-axis mesh (jax<0.5 remote-DMA discharge rule); a real TPU
    additionally wants Mosaic-friendly lane dims — pass the trailing
    (lane) dims the kernels will see in ``shapes`` where the caller knows
    them (resolve_gpt passes hidden + weight-shard widths; callers that
    only learn shapes later pass none and rely on Mosaic's own check).
    Returns (ok, reason) with the reason naming what would fix it."""
    if interpret_default():
        if len(mesh.axis_names) != 1:
            return False, (
                f"interpret-mode remote DMA (jax<0.5) supports exactly one "
                f"named mesh axis, mesh has {tuple(mesh.axis_names)} — use a "
                f"single-axis mesh (e.g. Mesh(devices, ('mp',))) for CPU "
                f"runs" + (f" [{why}]" if why else ""))
        return True, ""
    reasons = [f"dim {d} not a multiple of 128" for d in shapes
               if d % 128 != 0]
    if reasons:
        return False, ("; ".join(reasons) +
                       (f" [{why}]" if why else ""))
    return True, ""


# ---------------------------------------------------------------------------
# trace-time dispatch counters


_lock = threading.Lock()
_trace_counts = {}


def _count(name):
    with _lock:
        _trace_counts[name] = _trace_counts.get(name, 0) + 1


def trace_counts():
    """{kernel name: wrapper invocations at trace time}. Under a
    ``lax.scan`` layer stack each block position counts ONCE per trace
    (the scan body traces once), so a forward GPT trace shows exactly the
    per-block kernel positions."""
    with _lock:
        return dict(_trace_counts)


def reset_trace_counts():
    with _lock:
        _trace_counts.clear()


# ---------------------------------------------------------------------------
# ring topology helpers


def ring_ids(axis, n, mesh_axes):
    """(my ring index, right neighbor's, left neighbor's flat LOGICAL
    device id) as traced int32 scalars. ``mesh_axes`` is the static
    ((name, size), ...) tuple in mesh order; a neighbor's flat id is the
    row-major index over every bound axis with the ring axis's coordinate
    advanced by +-1 — on a single-axis mesh this degenerates to
    (idx +- 1) % n."""
    idx = lax.axis_index(axis).astype(jnp.int32)

    def flat(delta):
        if len(mesh_axes) == 1:
            return lax.rem(idx + jnp.int32(delta + n), jnp.int32(n))
        out = jnp.int32(0)
        for name, size in mesh_axes:
            coord = lax.axis_index(name).astype(jnp.int32)
            if name == axis:
                coord = lax.rem(coord + jnp.int32(delta + n), jnp.int32(n))
            out = out * jnp.int32(size) + coord
        return out

    return idx, flat(1), flat(-1)


def _rdma(src, dst, send_sem, recv_sem, right):
    return pltpu.make_async_remote_copy(
        src_ref=src, dst_ref=dst, send_sem=send_sem, recv_sem=recv_sem,
        device_id=right, device_id_type=pltpu.DeviceIdType.LOGICAL)


def _compiler_params(name, interpret):
    """Mosaic params for the real-TPU build: a collective id for the
    cross-device barrier semaphore, side effects pinned so the DMA chain
    is never DCE'd. Interpret mode takes none."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        collective_id=_CID[name], has_side_effects=True)}


def _barrier(interpret):
    """Neighbor barrier before the first RDMA (real TPU only): devices may
    enter the kernel skewed; a send landing before the receiver allocated
    its scratch corrupts memory. Interpret mode executes in lockstep."""
    if interpret:
        return

    def emit(left, right):
        sem = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(sem, inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_signal(sem, inc=1, device_id=right,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(sem, 2)
    return emit


# ---------------------------------------------------------------------------
# kernel bodies (run per device inside a full-manual shard_map)


def _ag_gemm_kernel(nbr_ref, x_ref, w_ref, o_ref, comm_ref, send_sem,
                    recv_sem, cap_sem, *, n, out_dtype, interpret):
    """Ring all-gather + GEMM. comm_ref is a double-buffered VMEM chunk:
    step t GEMMs the chunk in hand (owned by src = idx - t) into its
    block-row of the output while the RDMA pushing that chunk onward is
    in flight — the transfer hides behind the MXU work, and the gathered
    operand never exists outside VMEM."""
    idx, right, left = nbr_ref[0], nbr_ref[1], nbr_ref[2]
    barrier = _barrier(interpret)
    if barrier:
        barrier(left, right)
    comm_ref[0] = x_ref[...]

    def step(t, _):
        t = t.astype(jnp.int32)
        cur = lax.rem(t, jnp.int32(2))
        nxt = lax.rem(t + jnp.int32(1), jnp.int32(2))
        src = lax.rem(idx - t + jnp.int32(n), jnp.int32(n))
        dma = _rdma(comm_ref.at[cur], comm_ref.at[nxt], send_sem.at[cur],
                    recv_sem.at[nxt], right)

        @pl.when(t < n - 1)
        def _():
            if not interpret:
                # back-pressure: the remote slot we write must have been
                # consumed (its GEMM done) — the receiver signals capacity
                # after each step. Slots start free, so hop 0 skips it.
                @pl.when(t > 0)
                def _():
                    pltpu.semaphore_wait(cap_sem, 1)
            dma.start()

        o_ref[src] = lax.dot_general(
            comm_ref[cur], w_ref[...], (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(out_dtype)

        @pl.when(t < n - 1)
        def _():
            dma.wait()
            if not interpret:
                pltpu.semaphore_signal(
                    cap_sem, inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
        return 0

    lax.fori_loop(0, n, step, 0)


def _gemm_rs_kernel(nbr_ref, y_ref, w_ref, o_ref, acc_ref, send_ref,
                    recv_ref, send_sem, recv_sem, cap_sem, *, n, out_dtype,
                    interpret):
    """GEMM + ring reduce-scatter. The accumulator for chunk c rides the
    ring visiting every device once; each step's partial tile GEMM
    accumulates (fp32) directly into the traveling scatter destination in
    the epilogue — the full-size per-device partial product is never
    materialized. Accumulation order matches ``tp_overlap.gemm_ring_rs``
    exactly (devices c+1, c+2, ..., c), so the two backends agree
    bitwise in fp32."""
    idx, right, left = nbr_ref[0], nbr_ref[1], nbr_ref[2]
    barrier = _barrier(interpret)
    if barrier:
        barrier(left, right)

    def step(t, _):
        t = t.astype(jnp.int32)
        c = lax.rem(idx - t - jnp.int32(1) + jnp.int32(2 * n), jnp.int32(n))
        # GEMM first: the previous hop's transfer is still in flight
        part = lax.dot_general(
            y_ref[c], w_ref[...], (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dma_prev = _rdma(send_ref, recv_ref, send_sem.at[0], recv_sem.at[0],
                         right)

        @pl.when(t > 0)
        def _():
            dma_prev.wait()
            acc_ref[...] = recv_ref[...].astype(jnp.float32) + part
            if not interpret:
                # hop t-1 consumed: recv_ref is free again — credit the
                # sender so it may overwrite it with hop t
                pltpu.semaphore_signal(
                    cap_sem, inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)

        @pl.when(t == 0)
        def _():
            acc_ref[...] = part

        @pl.when(t < n - 1)
        def _():
            if not interpret:
                # hop t overwrites the receiver's single recv_ref, so it
                # must wait for the receiver's hop t-1 consumption credit
                # (hop 0's buffer starts free)
                @pl.when(t > 0)
                def _():
                    pltpu.semaphore_wait(cap_sem, 1)
            send_ref[...] = acc_ref[...].astype(send_ref.dtype)
            _rdma(send_ref, recv_ref, send_sem.at[0], recv_sem.at[0],
                  right).start()
        return 0

    lax.fori_loop(0, n, step, 0)
    o_ref[...] = acc_ref[...].astype(out_dtype)


def _ag_accum_kernel(nbr_ref, r_ref, st_ref, o_ref, comm_ref, acc_ref,
                     send_sem, recv_sem, cap_sem, *, n, interpret):
    """Ring all-gather + accumulated transpose-GEMM (the weight-grad
    kernel): chunks of the ring operand arrive like _ag_gemm_kernel, but
    each step contracts the chunk against the matching block of the
    stationary operand and accumulates into the weight-shaped output —
    sum_c ring_c^T @ stat_c without gathering ring into HBM."""
    idx, right, left = nbr_ref[0], nbr_ref[1], nbr_ref[2]
    barrier = _barrier(interpret)
    if barrier:
        barrier(left, right)
    comm_ref[0] = r_ref[...]

    def step(t, _):
        t = t.astype(jnp.int32)
        cur = lax.rem(t, jnp.int32(2))
        nxt = lax.rem(t + jnp.int32(1), jnp.int32(2))
        src = lax.rem(idx - t + jnp.int32(n), jnp.int32(n))
        dma = _rdma(comm_ref.at[cur], comm_ref.at[nxt], send_sem.at[cur],
                    recv_sem.at[nxt], right)

        @pl.when(t < n - 1)
        def _():
            if not interpret:
                @pl.when(t > 0)
                def _():
                    pltpu.semaphore_wait(cap_sem, 1)
            dma.start()

        part = lax.dot_general(
            comm_ref[cur], st_ref[src], (((0, 1), (0, 1)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(t == 0)
        def _():
            acc_ref[...] = part

        @pl.when(t > 0)
        def _():
            acc_ref[...] += part

        @pl.when(t < n - 1)
        def _():
            dma.wait()
            if not interpret:
                pltpu.semaphore_signal(
                    cap_sem, inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
        return 0

    lax.fori_loop(0, n, step, 0)
    o_ref[...] = acc_ref[...]


def _rs_bucket_kernel(nbr_ref, x_ref, o_ref, acc_ref, send_ref, recv_ref,
                      send_sem, recv_sem, cap_sem, *, n, interpret):
    """grad_comm bucket ring reduce-scatter: x (n, cols) local rows, out
    (cols,) = this replica's reduced row, fp32. The traveling accumulator
    is cast to the wire dtype of send_ref/recv_ref for each hop and
    dequantized + accumulated in fp32 on receipt (EQuARX-style: the wire
    is compressed, the accumulation is not)."""
    idx, right, left = nbr_ref[0], nbr_ref[1], nbr_ref[2]
    barrier = _barrier(interpret)
    if barrier:
        barrier(left, right)

    def step(t, _):
        t = t.astype(jnp.int32)
        c = lax.rem(idx - t - jnp.int32(1) + jnp.int32(2 * n), jnp.int32(n))
        part = x_ref[c].astype(jnp.float32)
        dma_prev = _rdma(send_ref, recv_ref, send_sem.at[0], recv_sem.at[0],
                         right)

        @pl.when(t > 0)
        def _():
            dma_prev.wait()
            acc_ref[...] = recv_ref[...].astype(jnp.float32) + part
            if not interpret:
                # hop t-1 consumed: credit the sender (see _gemm_rs_kernel)
                pltpu.semaphore_signal(
                    cap_sem, inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)

        @pl.when(t == 0)
        def _():
            acc_ref[...] = part

        @pl.when(t < n - 1)
        def _():
            if not interpret:
                @pl.when(t > 0)
                def _():
                    pltpu.semaphore_wait(cap_sem, 1)
            send_ref[...] = acc_ref[...].astype(send_ref.dtype)
            _rdma(send_ref, recv_ref, send_sem.at[0], recv_sem.at[0],
                  right).start()
        return 0

    lax.fori_loop(0, n, step, 0)
    o_ref[...] = acc_ref[...]


def _ag_bucket_kernel(nbr_ref, x_ref, o_ref, comm_ref, send_sem, recv_sem,
                      cap_sem, *, n, interpret):
    """grad_comm bucket ring all-gather: row (cols,) -> (n, cols)."""
    idx, right, left = nbr_ref[0], nbr_ref[1], nbr_ref[2]
    barrier = _barrier(interpret)
    if barrier:
        barrier(left, right)
    comm_ref[0] = x_ref[...]

    def step(t, _):
        t = t.astype(jnp.int32)
        cur = lax.rem(t, jnp.int32(2))
        nxt = lax.rem(t + jnp.int32(1), jnp.int32(2))
        src = lax.rem(idx - t + jnp.int32(n), jnp.int32(n))
        dma = _rdma(comm_ref.at[cur], comm_ref.at[nxt], send_sem.at[cur],
                    recv_sem.at[nxt], right)

        @pl.when(t < n - 1)
        def _():
            if not interpret:
                @pl.when(t > 0)
                def _():
                    pltpu.semaphore_wait(cap_sem, 1)
            dma.start()

        o_ref[src] = comm_ref[cur]

        @pl.when(t < n - 1)
        def _():
            dma.wait()
            if not interpret:
                pltpu.semaphore_signal(
                    cap_sem, inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
        return 0

    lax.fori_loop(0, n, step, 0)


def _gemm_ag_kernel(nbr_ref, x_ref, w_ref, o_ref, comm_ref, send_sem,
                    recv_sem, cap_sem, *, n, out_dtype, interpret):
    """GEMM + ring all-gather of the OUTPUT (the serving engine's
    column-parallel projections): each device computes its full-contraction
    column block ``x @ w_shard`` straight into the ring buffer and the
    blocks ride the ring into every device's output — the pre-collective
    block never takes an HBM round trip between the GEMM epilogue and the
    transfer. Full-contraction per block, so the gathered result is
    BITWISE identical to slicing the unsharded GEMM (the serving
    exactness contract)."""
    idx, right, left = nbr_ref[0], nbr_ref[1], nbr_ref[2]
    barrier = _barrier(interpret)
    if barrier:
        barrier(left, right)
    # plain matmul, NOT dot_general-with-preferred-fp32: the block must be
    # bitwise equal to the column slice of the unsharded `x @ w` the
    # single-chip engine computes (a preferred_element_type dot takes a
    # different accumulation path on CPU — observed ~1e-6 drift)
    comm_ref[0] = (x_ref[...] @ w_ref[...]).astype(out_dtype)

    def step(t, _):
        t = t.astype(jnp.int32)
        cur = lax.rem(t, jnp.int32(2))
        nxt = lax.rem(t + jnp.int32(1), jnp.int32(2))
        src = lax.rem(idx - t + jnp.int32(n), jnp.int32(n))
        dma = _rdma(comm_ref.at[cur], comm_ref.at[nxt], send_sem.at[cur],
                    recv_sem.at[nxt], right)

        @pl.when(t < n - 1)
        def _():
            if not interpret:
                @pl.when(t > 0)
                def _():
                    pltpu.semaphore_wait(cap_sem, 1)
            dma.start()

        o_ref[src] = comm_ref[cur]

        @pl.when(t < n - 1)
        def _():
            dma.wait()
            if not interpret:
                pltpu.semaphore_signal(
                    cap_sem, inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
        return 0

    lax.fori_loop(0, n, step, 0)


def _gemm_ag_q_kernel(nbr_ref, x_ref, w_ref, s_ref, o_ref, comm_ref,
                      send_sem, recv_sem, cap_sem, *, n, out_dtype,
                      interpret):
    """Quantized-weight variant of ``_gemm_ag_kernel``: w is the raw
    int8/fp8 column shard and s its per-output-channel fp32 dequant
    scale — the convert + scale multiply live in the GEMM epilogue, so
    the fp weight block never exists (not in HBM, not on the wire).
    Same algebra as the jnp path ``(x @ wq.astype(dt)) * s`` — the
    quantized serving rungs' bitwise contract."""
    idx, right, left = nbr_ref[0], nbr_ref[1], nbr_ref[2]
    barrier = _barrier(interpret)
    if barrier:
        barrier(left, right)
    comm_ref[0] = ((x_ref[...] @ w_ref[...].astype(out_dtype)) *
                   s_ref[...].astype(out_dtype)).astype(out_dtype)

    def step(t, _):
        t = t.astype(jnp.int32)
        cur = lax.rem(t, jnp.int32(2))
        nxt = lax.rem(t + jnp.int32(1), jnp.int32(2))
        src = lax.rem(idx - t + jnp.int32(n), jnp.int32(n))
        dma = _rdma(comm_ref.at[cur], comm_ref.at[nxt], send_sem.at[cur],
                    recv_sem.at[nxt], right)

        @pl.when(t < n - 1)
        def _():
            if not interpret:
                @pl.when(t > 0)
                def _():
                    pltpu.semaphore_wait(cap_sem, 1)
            dma.start()

        o_ref[src] = comm_ref[cur]

        @pl.when(t < n - 1)
        def _():
            dma.wait()
            if not interpret:
                pltpu.semaphore_signal(
                    cap_sem, inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
        return 0

    lax.fori_loop(0, n, step, 0)


# ---------------------------------------------------------------------------
# kernel-call wrappers (per-device shards, inside full-manual shard_map)


class RingMeta(tuple):
    """Hashable static config for the fused kernels: (axis, n, mesh_axes,
    interpret). mesh_axes is ((name, size), ...) in mesh order — the flat
    logical-id basis for multi-axis (real TPU) meshes."""
    __slots__ = ()

    def __new__(cls, axis, n, mesh_axes, interpret):
        return super().__new__(cls, (axis, int(n), tuple(mesh_axes),
                                     bool(interpret)))

    axis = property(lambda self: self[0])
    n = property(lambda self: self[1])
    mesh_axes = property(lambda self: self[2])
    interpret = property(lambda self: self[3])


def meta_for(mesh, axis, interpret=None):
    return RingMeta(axis, int(mesh.shape[axis]),
                    tuple((a, int(mesh.shape[a])) for a in mesh.axis_names),
                    interpret_default() if interpret is None else interpret)


def _nbr(meta):
    return jnp.stack(ring_ids(meta.axis, meta.n, meta.mesh_axes))


def _sems():
    return [pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR]


def _sems1():
    return [pltpu.SemaphoreType.DMA((1,)), pltpu.SemaphoreType.DMA((1,)),
            pltpu.SemaphoreType.REGULAR]


def _ag_gemm_call(meta, x, w):
    """[B, s, A] seq-chunk, [A, F] -> [B, n*s, F] (full sequence)."""
    _count("ag_gemm")
    n = meta.n
    B, s, A = x.shape
    F = w.shape[1]
    out = pl.pallas_call(
        functools.partial(_ag_gemm_kernel, n=n, out_dtype=x.dtype,
                          interpret=meta.interpret),
        out_shape=jax.ShapeDtypeStruct((n, B, s, F), x.dtype),
        in_specs=[_SMEM, _VMEM, _VMEM],
        scratch_shapes=[pltpu.VMEM((2, B, s, A), x.dtype)] + _sems(),
        interpret=meta.interpret,
        **_compiler_params("ag_gemm", meta.interpret),
    )(_nbr(meta), x, w)
    return out.transpose(1, 0, 2, 3).reshape(B, n * s, F)


def _gemm_rs_call(meta, y, w):
    """[B, S, F] per-device partial, [F, A] -> [B, S/n, A] reduced shard."""
    _count("gemm_rs")
    n = meta.n
    B, S, F = y.shape
    s = S // n
    A = w.shape[1]
    ys = y.reshape(B, n, s, F).transpose(1, 0, 2, 3)
    return pl.pallas_call(
        functools.partial(_gemm_rs_kernel, n=n, out_dtype=y.dtype,
                          interpret=meta.interpret),
        out_shape=jax.ShapeDtypeStruct((B, s, A), y.dtype),
        in_specs=[_SMEM, _VMEM, _VMEM],
        scratch_shapes=[pltpu.VMEM((B, s, A), jnp.float32),
                        pltpu.VMEM((B, s, A), jnp.float32),
                        pltpu.VMEM((B, s, A), jnp.float32)] + _sems1(),
        interpret=meta.interpret,
        **_compiler_params("gemm_rs", meta.interpret),
    )(_nbr(meta), ys, w)


def _ag_accum_call(meta, r, stat):
    """ring operand r [B, s, A], stationary [B, S, Bf] -> fp32 [A, Bf] =
    sum_c r_c^T @ stat_c (the weight gradient of the fused matmuls)."""
    _count("ag_accum")
    n = meta.n
    B, s, A = r.shape
    Bf = stat.shape[2]
    st = stat.reshape(B, n, s, Bf).transpose(1, 0, 2, 3)
    return pl.pallas_call(
        functools.partial(_ag_accum_kernel, n=n, interpret=meta.interpret),
        out_shape=jax.ShapeDtypeStruct((A, Bf), jnp.float32),
        in_specs=[_SMEM, _VMEM, _VMEM],
        scratch_shapes=[pltpu.VMEM((2, B, s, A), r.dtype),
                        pltpu.VMEM((A, Bf), jnp.float32)] + _sems(),
        interpret=meta.interpret,
        **_compiler_params("ag_accum", meta.interpret),
    )(_nbr(meta), r, st)


def fused_rs_bucket(meta, x, wire_dtype=None):
    """grad_comm bucket RS: (n, cols) local -> (cols,) fp32 reduced row.
    wire_dtype (None=fp32 | bf16) compresses each hop's traveling
    accumulator on the wire; accumulation stays fp32 in the epilogue."""
    _count("rs_bucket")
    n = meta.n
    cols = x.shape[1]
    wire = jnp.dtype(wire_dtype) if wire_dtype is not None else jnp.float32
    return pl.pallas_call(
        functools.partial(_rs_bucket_kernel, n=n, interpret=meta.interpret),
        out_shape=jax.ShapeDtypeStruct((cols,), jnp.float32),
        in_specs=[_SMEM, _VMEM],
        scratch_shapes=[pltpu.VMEM((cols,), jnp.float32),
                        pltpu.VMEM((cols,), wire),
                        pltpu.VMEM((cols,), wire)] + _sems1(),
        interpret=meta.interpret,
        **_compiler_params("rs_bucket", meta.interpret),
    )(_nbr(meta), x)


def fused_ag_bucket(meta, row):
    """grad_comm bucket AG: (cols,) row -> (n, cols)."""
    _count("ag_bucket")
    n = meta.n
    cols = row.shape[0]
    return pl.pallas_call(
        functools.partial(_ag_bucket_kernel, n=n, interpret=meta.interpret),
        out_shape=jax.ShapeDtypeStruct((n, cols), row.dtype),
        in_specs=[_SMEM, _VMEM],
        scratch_shapes=[pltpu.VMEM((2, cols), row.dtype)] + _sems(),
        interpret=meta.interpret,
        **_compiler_params("ag_bucket", meta.interpret),
    )(_nbr(meta), row)


def fused_gemm_ag(meta, x, w, scale=None):
    """Column-parallel GEMM + in-kernel ring all-gather of the output:
    x [..., K] replicated rows, w [K, F/n] column shard -> [..., F] with
    feature blocks in ring (= logical) order. Every block is a
    full-contraction GEMM, so the result is BITWISE identical to
    ``x @ w_full`` — the gather moves data, never changes math. The
    serving engine's out/down/lm-head projections ride this kernel under
    the ``fused`` rung.

    ``scale`` [F/n] (quantized serving): ``w`` is an int8/fp8 shard whose
    per-output-channel dequant multiply runs in the GEMM epilogue before
    the block enters the ring — the quantized mp engine's weights never
    exist at full precision anywhere."""
    n = meta.n
    lead = x.shape[:-1]
    K = x.shape[-1]
    F = w.shape[1]
    R = 1
    for s in lead:
        R *= int(s)
    if scale is None:
        _count("gemm_ag")
        out = pl.pallas_call(
            functools.partial(_gemm_ag_kernel, n=n, out_dtype=x.dtype,
                              interpret=meta.interpret),
            out_shape=jax.ShapeDtypeStruct((n, R, F), x.dtype),
            in_specs=[_SMEM, _VMEM, _VMEM],
            scratch_shapes=[pltpu.VMEM((2, R, F), x.dtype)] + _sems(),
            interpret=meta.interpret,
            **_compiler_params("gemm_ag", meta.interpret),
        )(_nbr(meta), x.reshape(R, K), w)
    else:
        _count("gemm_ag_q")
        out = pl.pallas_call(
            functools.partial(_gemm_ag_q_kernel, n=n, out_dtype=x.dtype,
                              interpret=meta.interpret),
            out_shape=jax.ShapeDtypeStruct((n, R, F), x.dtype),
            in_specs=[_SMEM, _VMEM, _VMEM, _VMEM],
            scratch_shapes=[pltpu.VMEM((2, R, F), x.dtype)] + _sems(),
            interpret=meta.interpret,
            **_compiler_params("gemm_ag_q", meta.interpret),
        )(_nbr(meta), x.reshape(R, K), w,
          scale.reshape(1, F).astype(jnp.float32))
    # [n, R, F] -> [R, n*F]: block j lands at columns j*F..(j+1)*F (chip
    # order == logical feature order for contiguous column shards)
    return out.transpose(1, 0, 2).reshape(lead + (n * F,))


# ---------------------------------------------------------------------------
# differentiable entry points (custom VJPs: the backward passes are fused
# kernels too — the transpose of AG+GEMM is GEMM+RS of the cotangent)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fused_ag_gemm(meta, x, w):
    """x [B, s, A] seq-shard, w [A, F] shard -> [B, S, F]: the fused
    all-gather + GEMM (ColumnParallel forward)."""
    return _ag_gemm_call(meta, x, w)


def _ag_gemm_fwd(meta, x, w):
    return _ag_gemm_call(meta, x, w), (x, w)


def _ag_gemm_bwd(meta, res, g):
    x, w = res
    # dx [B, s, A]: the cotangent's GEMM+reduce-scatter with w^T
    dx = _gemm_rs_call(meta, g, w.T)
    # dw [A, F] = sum_c x_c^T g_c, accumulated while x rings past
    dw = _ag_accum_call(meta, x, g).astype(w.dtype)
    return dx, dw


fused_ag_gemm.defvjp(_ag_gemm_fwd, _ag_gemm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fused_gemm_rs(meta, y, w):
    """y [B, S, F] per-device partial, w [F, A] shard -> [B, s, A] reduced
    seq-shard: the fused GEMM + reduce-scatter (RowParallel forward)."""
    return _gemm_rs_call(meta, y, w)


def _gemm_rs_fwd(meta, y, w):
    return _gemm_rs_call(meta, y, w), (y, w)


def _gemm_rs_bwd(meta, res, g):
    y, w = res
    # dy [B, S, F]: all-gather the seq-shard cotangent while GEMMing w^T
    dy = _ag_gemm_call(meta, g, w.T)
    # dw [F, A] = sum_c y_c^T g_c = (sum_c g_c^T y_c)^T
    dw = _ag_accum_call(meta, g, y).T.astype(w.dtype)
    return dy, dw


fused_gemm_rs.defvjp(_gemm_rs_fwd, _gemm_rs_bwd)


# ---------------------------------------------------------------------------
# pipeline-boundary kernels (FLAGS_comm_backend='pp=fused'): the LAST GEMM
# of a pipeline stage (the block's down-projection, r + (x @ w + b)) runs
# row-chunked, and each chunk's boundary RDMA to the down-ring neighbor is
# issued the moment its rows retire — the next chunk's GEMM runs under the
# transfer, so the stage-boundary activation send costs no serial time and
# never takes an HBM round trip between the epilogue and the wire.


def _pp_chunks(R):
    for c in (8, 4, 2):
        if R % c == 0 and R // c >= 1 and R >= c:
            return c
    return 1


def _gemm_ppsend_kernel(nbr_ref, x_ref, w_ref, b_ref, r_ref, y_ref,
                        recv_ref, send_sem, recv_sem, *, C, interpret):
    """y = r + (x @ w + b), the boundary rows RDMA'd to the RIGHT
    (down-ring) neighbor's recv_ref in C pipelined chunks straight from
    the GEMM epilogue — the first bytes are on the wire while later
    chunks are still being issued, and the boundary activation never
    takes an HBM round trip before the transfer. The GEMM itself runs as
    ONE full-matrix matmul: a row-chunked dot takes a shape-dependent
    accumulation path, and the fused rung must stay BITWISE equal to the
    unfused stage tail. Destination rows are disjoint per chunk, so two
    in-flight transfers (double-buffered semaphore slots) need no extra
    capacity backpressure: slot c%2 was last waited at iteration c-1."""
    idx, right, left = nbr_ref[0], nbr_ref[1], nbr_ref[2]
    barrier = _barrier(interpret)
    if barrier:
        barrier(left, right)
    # plain matmul + the block tail's exact op order (r + (x@w + b))
    y_ref[...] = (r_ref[...] +
                  (x_ref[...] @ w_ref[...] + b_ref[0])).astype(y_ref.dtype)
    R = y_ref.shape[0]
    rc = R // C
    dmas = []
    for c in range(C):
        lo = c * rc
        hi = lo + rc
        dma = _rdma(y_ref.at[lo:hi], recv_ref.at[lo:hi],
                    send_sem.at[c % 2], recv_sem.at[c % 2], right)
        dma.start()
        dmas.append(dma)
        if c > 0:
            dmas[c - 1].wait()
    dmas[C - 1].wait()


def _gemm_pprecv_kernel(nbr_ref, gy_ref, grecv_ref, x_ref, w_ref, dx_ref,
                        dw_ref, dr_ref, gwire_ref, send_sem,
                        recv_sem, *, C, interpret):
    """Backward tick of the fused boundary: the received-value cotangent
    ``grecv`` rides UP the ring (to the left neighbor — the transpose of
    the forward hop) chunk by chunk while dx/dr rows for the previous
    chunk compute under the transfer. dw/db run ONCE over the fully
    assembled cotangent at the end — chunked accumulation would change
    the summation order and break bitwise parity with the lax reference.
    The same goes for a row-chunked dx dot (shape-dependent accumulation),
    so the per-arrival work is the elementwise cotangent assembly
    (row-independent, bitwise-safe) and all three GEMM-class reductions
    run full-matrix after the last chunk lands."""
    idx, right, left = nbr_ref[0], nbr_ref[1], nbr_ref[2]
    barrier = _barrier(interpret)
    if barrier:
        barrier(left, right)
    R = gy_ref.shape[0]
    rc = R // C

    def consume(c):
        lo = c * rc
        hi = lo + rc
        dr_ref[lo:hi] = (gy_ref[lo:hi] +
                         gwire_ref[lo:hi].astype(gy_ref.dtype))

    dmas = []
    for c in range(C):
        lo = c * rc
        hi = lo + rc
        dma = _rdma(grecv_ref.at[lo:hi], gwire_ref.at[lo:hi],
                    send_sem.at[c % 2], recv_sem.at[c % 2], left)
        dma.start()
        dmas.append(dma)
        if c > 0:
            dmas[c - 1].wait()
            consume(c - 1)
    dmas[C - 1].wait()
    consume(C - 1)
    cot = dr_ref[...]
    # exact dimension numbers autodiff emits: d(x@w)/dx = g @ w^T
    dx_ref[...] = lax.dot_general(
        cot, w_ref[...], (((1,), (1,)), ((), ()))).astype(dx_ref.dtype)
    # d(x@w)/dw = x^T g, as the contraction autodiff emits (dims 0/0).
    # The bias cotangent is NOT reduced here: an interpret-mode in-kernel
    # reduce takes a different accumulation order than the XLA-compiled
    # reduce autodiff emits — the wrapper reduces dr at the JAX level.
    dw_ref[...] = lax.dot_general(
        x_ref[...], cot, (((0,), (0,)), ((), ()))).astype(dw_ref.dtype)


def _sems_pp():
    return [pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,))]


def _gemm_ppsend_call(meta, x, w, b, r):
    _count("gemm_ppsend")
    R, K = x.shape
    F = w.shape[1]
    C = _pp_chunks(R)
    y, recv = pl.pallas_call(
        functools.partial(_gemm_ppsend_kernel, C=C,
                          interpret=meta.interpret),
        out_shape=(jax.ShapeDtypeStruct((R, F), r.dtype),
                   jax.ShapeDtypeStruct((R, F), r.dtype)),
        in_specs=[_SMEM, _VMEM, _VMEM, _VMEM, _VMEM],
        scratch_shapes=_sems_pp(),
        interpret=meta.interpret,
        **_compiler_params("gemm_ppsend", meta.interpret),
    )(_nbr(meta), x, w, b.reshape(1, F), r)
    return y, recv


def _gemm_pprecv_call(meta, gy, grecv, x, w):
    _count("gemm_pprecv")
    R, F = gy.shape
    K = x.shape[1]
    C = _pp_chunks(R)
    dx, dw, dr = pl.pallas_call(
        functools.partial(_gemm_pprecv_kernel, C=C,
                          interpret=meta.interpret),
        out_shape=(jax.ShapeDtypeStruct((R, K), x.dtype),
                   jax.ShapeDtypeStruct((K, F), w.dtype),
                   jax.ShapeDtypeStruct((R, F), gy.dtype)),
        in_specs=[_SMEM, _VMEM, _VMEM, _VMEM, _VMEM],
        scratch_shapes=[pltpu.VMEM((R, F), gy.dtype)] + _sems_pp(),
        interpret=meta.interpret,
        **_compiler_params("gemm_pprecv", meta.interpret),
    )(_nbr(meta), gy, grecv, x, w)
    return dx, dw, dr


def _pp_perms(n):
    down = [(i, (i + 1) % n) for i in range(n)]
    up = [((i + 1) % n, i) for i in range(n)]
    return down, up


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def fused_gemm_ppsend(meta, rdma, rows, x, w, b, r):
    """Fused stage tail + boundary send: ``y = r + (x @ w + b)``;
    ``recv`` = the DOWN-ring ppermute of y (what this device receives
    from its up-neighbor). ``rdma=True`` issues the hop from the GEMM
    epilogue (real remote DMA on TPU; the jax<0.5 interpret discharge
    rule supports it on a single-axis mesh); ``rdma=False`` keeps the
    same math with the hop as an explicit lax.ppermute outside the
    kernel region (multi-axis CPU meshes). ``rows`` is the caller's
    static leading-axis split of the flattened row dimension (e.g.
    (B, S)) — the bias-cotangent reduce follows it so the backward is
    BITWISE equal to autodiff of the unflattened stage tail. Both paths
    match ``gemm_ppsend_reference`` bitwise."""
    if rows is None:
        rows = (x.shape[0],)
    if rdma:
        return _gemm_ppsend_call(meta, x, w, b, r)
    _count("gemm_ppsend_local")
    y = (r + (x @ w + b)).astype(r.dtype)
    down, _ = _pp_perms(meta.n)
    return y, lax.ppermute(y, meta.axis, down)


def _gemm_ppsend_fwd(meta, rdma, rows, x, w, b, r):
    return fused_gemm_ppsend(meta, rdma, rows, x, w, b, r), (x, w)


def _gemm_ppsend_bwd(meta, rdma, rows, res, g):
    x, w = res
    if rows is None:
        rows = (x.shape[0],)
    gy, grecv = g
    if rdma:
        dx, dw, dr = _gemm_pprecv_call(meta, gy, grecv, x, w)
    else:
        _, up = _pp_perms(meta.n)
        cot = gy + lax.ppermute(grecv, meta.axis, up)
        dx = lax.dot_general(cot, w, (((1,), (1,)), ((), ())))
        dw = lax.dot_general(x, cot, (((0,), (0,)), ((), ()))).astype(w.dtype)
        dr = cot
    # the bias cotangent reduces at the JAX level over the caller's
    # original (e.g. (B, S)) axis split — the exact reduce autodiff
    # emits for the broadcast-bias transpose of the unflattened tail
    F = dr.shape[-1]
    db = jnp.sum(dr.reshape(rows + (F,)),
                 axis=tuple(range(len(rows)))).astype(w.dtype)
    return dx.astype(x.dtype), dw, db.reshape(-1), dr


fused_gemm_ppsend.defvjp(_gemm_ppsend_fwd, _gemm_ppsend_bwd)


# ---------------------------------------------------------------------------
# unfused references — the SAME schedule (chunk order, fp32 accumulation)
# expressed with lax collectives that materialize every intermediate
# buffer. The interpret-mode parity tests assert the kernels match these
# BITWISE: fusion must remove the buffers, not change the math.


def ag_gemm_reference(axis, n, x, w):
    from ...distributed.tp_overlap import ring_ag_gemm
    return ring_ag_gemm(x, w, axis, n)


def gemm_rs_reference(axis, n, y, w):
    from ...distributed.tp_overlap import gemm_ring_rs
    return gemm_ring_rs(y, w, axis, n)


def ag_accum_reference(axis, n, r, stat):
    """sum_c r_c^T @ stat_c with r chunks arriving over ppermute hops, in
    the kernel's exact accumulation order (src = idx, idx-1, ...)."""
    idx = lax.axis_index(axis)
    B, s, A = r.shape
    perm = [(i, (i + 1) % n) for i in range(n)]
    chunk = r
    acc = None
    for t in range(n):
        src = (idx - t) % n
        st = lax.dynamic_slice_in_dim(
            stat.reshape(stat.shape[0], n, s, stat.shape[2]).transpose(
                1, 0, 2, 3), src, 1, axis=0)[0]
        part = lax.dot_general(chunk, st, (((0, 1), (0, 1)), ((), ())),
                               preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
        if t < n - 1:
            chunk = lax.ppermute(chunk, axis, perm)
    return acc


def gemm_ag_reference(axis, n, x, w):
    """Local column-block GEMM + ppermute ring all-gather of the output
    along the last axis, in the kernel's exact block placement."""
    y = x @ w
    idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    F = y.shape[-1]
    out = jnp.zeros(y.shape[:-1] + (n * F,), y.dtype)
    chunk = y
    for t in range(n):
        src = (idx - t) % n
        out = lax.dynamic_update_slice_in_dim(out, chunk, src * F,
                                              axis=y.ndim - 1)
        if t < n - 1:
            chunk = lax.ppermute(chunk, axis, perm)
    return out


def rs_bucket_reference(axis, n, x, wire_dtype=None):
    """Ring RS of (n, cols) rows with per-hop wire quantization, in the
    kernel's exact order (part + received, fp32)."""
    idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    wire = jnp.dtype(wire_dtype) if wire_dtype is not None else jnp.float32
    acc = None
    for t in range(n):
        c = (idx - t - 1) % n
        part = lax.dynamic_index_in_dim(x, c, keepdims=False).astype(
            jnp.float32)
        if acc is None:
            acc = part
        else:
            acc = lax.ppermute(acc.astype(wire), axis, perm).astype(
                jnp.float32) + part
    return acc


def gemm_ppsend_reference(axis, n, x, w, b, r):
    """The stage tail + boundary hop the fused kernel replaces, as plain
    lax: the parity tests differentiate THIS with jax autodiff and assert
    the fused custom VJP matches bitwise."""
    y = (r + (x @ w + b)).astype(r.dtype)
    perm = [(i, (i + 1) % n) for i in range(n)]
    return y, lax.ppermute(y, axis, perm)
