"""The Mamba-1 selective scan on a TPU, over a served model's WHOLE recurrent
state ``[layers, slots, N, D]`` (float32, ``N`` the state size, ``D`` the
inner channels), updated in place at ``(layer, slot)``:

    s_t = exp(dt_t A) * s_{t-1} + (dt_t x_t) B_t^T        y_t = s_t^T C_t

per channel ``d`` and state ``n``, ``A`` ``[N, D]`` (negative). A Mamba-1
``A`` is per (channel, state), so the recurrence has no matmul form: it is
walked position by position, each position a ``[N, TD]`` tile of the VPU.

Two kernels, one body:

* ``ssm_scan``, the ``[B, T]`` chunk step: a grid step a (row, block of
  channels), the row's ``T`` positions walked inside VMEM;
* ``ssm_step``, the ``[B, 1]`` decode update: a grid step a (slot, block of
  channels), one position.

Both take the state array whole and address ``(layer, slots[b])`` by scalar
prefetch (no layer or slot is sliced out of it: a Pallas call cannot fuse a
slice into its operand and XLA would copy it), and write it in place
(``input_output_aliases``), the discipline of ``serving/paged_attention.py::
_paged_decode_call`` and ``grouped_matmul.grouped_ffn``. A row whose
``start`` is 0 starts from a zero state; a row whose ``valid`` is 0 leaves
its state as it was. The caller makes ``dt`` 0 on the positions past
``valid`` (a pad then leaves the state where the last real position left
it).

``selective_scan`` picks the kernel on a TPU and ``scan_reference`` (the same
recurrence as a ``lax.scan`` over positions in ``jax.numpy``) elsewhere, by
the lowering platform (``lax.platform_dependent``)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_BLOCK_BYTES = 8 << 20      # a grid step's [T, TD] operands and output


def _channel_block(D, T):
    """The channels a grid step takes: all of them where the step's three
    ``[T, TD]`` float32 arrays, double-buffered, stay under the budget."""
    td = D
    while 6 * T * td * 4 > _BLOCK_BYTES and td % 256 == 0:
        td //= 2
    return td


def _row_block(B, T):
    """Rows of the ``[B T, D]`` operands a block holds: a row's ``T``
    positions, or at ``T`` 1 the eight rows (or all, fewer or not a multiple
    of eight) that one row's step shares with its neighbours' (a block of
    one row is no whole tile)."""
    if T > 1:
        return T
    return 8 if B % 8 == 0 else B


def _group(T):
    """Positions whose B and C columns are picked out of one lane block."""
    return 128 if T % 128 == 0 else T


def _kernel(layer, slots, start, valid, s_ref, dt_ref, dtx_ref, a_ref, b_ref,
            c_ref, y_ref, out_ref, *, T):
    del layer, slots
    i = pl.program_id(0)
    base = 0 if T > 1 else i % dt_ref.shape[0]       # the row's first line
    a = a_ref[...]                                         # [N, TD]
    s = jnp.where(start[i] == 0, 0.0, s_ref[...])

    def position(t, s, b, c):
        at = pl.ds(base + t, 1)
        s = jnp.exp(dt_ref[at, :] * a) * s + dtx_ref[at, :] * b
        y_ref[at, :] = jnp.sum(s * c, axis=0, keepdims=True)
        return s

    if T == 1:
        s = position(0, s, b_ref[...], c_ref[...])
    else:
        G = _group(T)
        lanes = jax.lax.broadcasted_iota(jnp.int32, (a.shape[0], G), 1)

        def group(g, s):
            bg = b_ref[:, pl.ds(g * G, G)]                 # [N, G]
            cg = c_ref[:, pl.ds(g * G, G)]

            def one(j, s):
                pick = lanes == j
                col = lambda m: jnp.sum(jnp.where(pick, m, 0.0), axis=1,
                                        keepdims=True)    # [N, 1]
                return position(g * G + j, s, col(bg), col(cg))

            return jax.lax.fori_loop(0, G, one, s)

        s = jax.lax.fori_loop(0, T // G, group, s)
    out_ref[...] = jnp.where(valid[i] > 0, s, s_ref[...])


def _call(state, layer, slots, start, valid, dt, dtx, A, Bt, Ct, channels,
          interpret):
    B, N, T = Bt.shape
    D = dt.shape[1]
    td = channels or _channel_block(D, T)
    R = _row_block(B, T)

    def rows(i, d, *_):
        return i * T // R, d

    def cols(i, d, *_):
        return i, 0, 0

    def at(i, d, lay, sl, *_):
        return lay[0], sl[i], 0, d

    def chans(i, d, *_):
        return 0, d

    lanes = -(-T // 128) * 128
    vmem = 2 * 4 * (3 * R * td + 3 * N * td + 2 * N * lanes) + (4 << 20)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, D // td),
        in_specs=[pl.BlockSpec((None, None, N, td), at),
                  pl.BlockSpec((R, td), rows),
                  pl.BlockSpec((R, td), rows),
                  pl.BlockSpec((N, td), chans),
                  pl.BlockSpec((None, N, T), cols),
                  pl.BlockSpec((None, N, T), cols)],
        out_specs=[pl.BlockSpec((R, td), rows),
                   pl.BlockSpec((None, None, N, td), at)])
    return pl.pallas_call(
        functools.partial(_kernel, T=T),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B * T, D), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(vmem, 100 << 20)),
        interpret=interpret,
    )(layer, slots, start, valid, state, dt, dtx, A, Bt, Ct)


def _run(name, state, layer, slots, start, valid, dt, dtx, A, Bt, Ct,
         channels=None, interpret=False):
    # Mosaic rejects x64-typed index math; the framework enables x64
    # globally, so pin 32-bit types for the scalars and the kernel's trace
    with jax.enable_x64(False):
        scalars = [jnp.asarray(v, jnp.int32).reshape(-1)
                   for v in (layer, slots, start, valid)]
        # the kernel's name on a device trace: %ssm_scan.N, %ssm_step.N
        with jax.named_scope(name):
            return _call(state, *scalars, dt, dtx, A, Bt, Ct, channels,
                         interpret)


def ssm_scan(state, layer, slots, start, valid, dt, dtx, A, Bt, Ct, **kw):
    """The chunk step: ``dt``, ``dtx`` (``dt * x``) ``[B T, D]`` float32 (row
    b's positions one after the other), ``A`` ``[N, D]``, ``Bt``, ``Ct``
    ``[B, N, T]`` (each position's B and C as a column), the state ``[L, S,
    N, D]`` float32 at ``(layer, slots[b])``, ``start`` / ``valid`` ``[B]``.
    Returns ``y`` ``[B T, D]`` float32 (without the ``D x`` skip) and the
    state, updated in place."""
    return _run("ssm_scan", state, layer, slots, start, valid, dt, dtx, A,
                Bt, Ct, **kw)


def ssm_step(state, layer, slots, start, valid, dt, dtx, A, Bt, Ct, **kw):
    """The decode update: ``ssm_scan`` at ``T`` 1."""
    assert Bt.shape[2] == 1, Bt.shape
    return _run("ssm_step", state, layer, slots, start, valid, dt, dtx, A,
                Bt, Ct, **kw)


def scan_reference(state, layer, slots, start, valid, dt, dtx, A, Bt, Ct):
    """What the kernels compute, in ``jax.numpy``: the rows' states gathered
    at ``(layer, slots)``, a ``lax.scan`` over the positions, the states put
    back."""
    B, _, T = Bt.shape
    old = state[layer, slots]                              # [B, N, D]
    s0 = jnp.where((start == 0)[:, None, None], 0.0, old)

    def position(s, xs):
        dt_t, dtx_t, b_t, c_t = xs                         # [B, D] [B, N]
        s = jnp.exp(dt_t[:, None] * A) * s + dtx_t[:, None] * b_t[..., None]
        return s, jnp.sum(s * c_t[..., None], axis=1)

    by_position = lambda v: jnp.swapaxes(v.reshape(B, T, -1), 0, 1)
    s, ys = jax.lax.scan(position, s0, (
        by_position(dt), by_position(dtx), jnp.moveaxis(Bt, 2, 0),
        jnp.moveaxis(Ct, 2, 0)))
    s = jnp.where((valid > 0)[:, None, None], s, old)
    return jnp.swapaxes(ys, 0, 1).reshape(B * T, -1), \
        state.at[layer, slots].set(s)


def selective_scan(state, layer, slots, start, valid, dt, dtx, A, Bt, Ct):
    """The recurrence over the chunk or decode window: ``ssm_step`` at ``T``
    1 and ``ssm_scan`` otherwise on a TPU, ``scan_reference`` elsewhere."""
    kernel = ssm_step if Bt.shape[2] == 1 else ssm_scan
    return jax.lax.platform_dependent(
        state, layer, slots, start, valid, dt, dtx, A, Bt, Ct,
        tpu=kernel, default=scan_reference)

