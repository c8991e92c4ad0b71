"""Iteration-level continuous-batching engine (Orca-style) for the GPT
family, built on the fixed-shape / cached-executable discipline of the
eager+jit runtime.

The KV cache is a block-paged pool ``[L, P, page_size, nh, d]`` (d padded
to whole lanes on the device, ``pool_head_dim``) plus a slot->page table
(vLLM-style PagedAttention): admission is bounded by physical PAGES, not
worst-case-length slots, so effective batch tracks ACTUAL sequence lengths;
prompts with a cached prefix map the same physical pages copy-on-write
(serving/paged_kv.py); and long prompts prefill in fixed-size CHUNKS fused
into the regular decode step (Sarathi-style), so admitting a 1024-token
prompt does not stall all B decode streams for a monolithic prefill.

The engine owns a fixed batch of B decode SLOTS and a small static
executable set — ONE fused step dispatched at its steady-state shapes
([B, 1] decode over all slots, [1, chunk] prefill chunk, one shape a rung of
the chunk ladder), plus the CoW page copy — all trace-counter gated. Every
per-request quantity that varies (chunk offset, is-prefill/emit, page table,
absolute position, do_sample mask, temperature, top_p, PRNG keys) is a TRACED
operand, so admission, eviction, slot recycling, chunk progress and
sampling-config changes are pure data changes: the executable is reused,
never re-traced (`top_k` stays static, it shapes the top_k kernel).

Requests join and leave at step boundaries (continuous batching): a finished
request's slot is recycled for the next queued request while the other
slots' decode continues undisturbed — each slot's token stream is bitwise
identical to running that request alone through
`models.generation.generate_from_params`, for any admission order, greedy
and sampled, with sharing and chunking on (tested).

The host loop fetches each step's B next-tokens (serving must stream tokens
out anyway) and keeps all scheduling state in numpy; only the KV pool stays
device-resident (donated back into the next step's executable off-CPU).
"""
from __future__ import annotations

import threading
import time
from functools import lru_cache, partial

import numpy as np
import jax
import jax.numpy as jnp

from ..flags import get_flags
from ..observability import tracing as obs_tracing
from ..utils import fault_injection as _fi
from ..models.generation import (
    _cfg_key, _cfg_view, _collect_params, _next_token, _verify_accept,
)
from . import metrics
from . import quant as _squant
from .adapters import AdapterRegistry, AdapterSpec, UnknownAdapterError
from .kv_transfer import KVTransfer, PagePayload
from .operands import StepLayout, pack_out, split_out
from .paged_attention import (
    pad_lanes, paged_draft_forward, paged_kv_rewind, paged_verify_forward,
)
from .paged_kv import PagedKVPool, pages_for, ring_pages
from .served_model import GPT, served_model
from .request import (
    CANCELLED, ERROR, EXPIRED, FINISHED, LENGTH, QUEUED, RUNNING, SHED,
    STOP, GenerationResult, Request,
)
from .scheduler import QueueFullError, Scheduler, ShedError
from .slo import ShedPolicy, resolve_tenant_adapters


class EngineStoppedError(RuntimeError):
    """submit() on a drained/stopped engine. Carries the work the drain
    handed back so a router can act instead of guessing: ``queue_depth``
    (requests the drain requeued and still unclaimed) and ``requeued``
    (their request ids — resubmit them, or this new request, to a live
    replica or to an engine restored from this one's last snapshot).

    ``reforming=True`` means the stop is TEMPORARY: the replica's mp
    group is mid-reform after a chip loss/return and will come back (on
    fewer or more chips) momentarily — back off for ``retry_after``
    seconds and retry, rather than declaring the replica dead. The
    supervisor router treats reforming replicas as temporarily
    unroutable and spills elsewhere; only an all-reforming fleet
    surfaces this error to the caller, retry_after attached."""

    def __init__(self, message, queue_depth=0, requeued=(),
                 reforming=False, retry_after=None):
        super().__init__(message)
        self.queue_depth = int(queue_depth)
        self.requeued = tuple(requeued)
        self.reforming = bool(reforming)
        self.retry_after = retry_after


# Every builder is memoized on its static configuration: every Engine with
# the same model config shares ONE jit wrapper, so a rebuilt/second engine
# reuses the already-compiled executables instead of re-tracing (fast
# restart). The trace counters are correspondingly GLOBAL — a new engine over
# warm shapes adds zero traces.
@lru_cache(maxsize=None)
def _make_paged_step(cfg, top_k, page_size, use_kernel, donate,
                     mp_key=None, anomaly=False, quant=None,
                     qkernel=False, adapters=None, model=GPT):
    """Build the FUSED chunk/decode executable over the paged pool: every
    batch row is a slot processing a T-token window (ids' second dim) at
    its own offset. The engine dispatches it at exactly two steady-state
    shapes — [B, 1] (one-token decode over all slots) and [1, chunk] (one
    prefill chunk, Sarathi-interleaved between decodes). start/valid/emit
    and the page table are traced per-slot operands, so admission, chunk
    progress, CoW remaps and sampling changes never retrace; distinct
    shapes -> exactly one trace per rung of the chunk ladder.

    A slot's PRNG key splits ONLY on steps where it emits a token
    (emit[b]), replicating generate's split-per-emitted-token stream even
    though prefill now spans several steps.

    ``mp_key`` = (mesh, ServingMPConfig) routes the forward through the
    mp-sharded schedule (serving/mp_forward.py) — same signature, same
    traced operands, bitwise-identical logits — so the host loop, trace
    gates and snapshot machinery are mp-blind.

    ``anomaly=True`` (FLAGS_serving_anomaly_policy != "off") additionally
    returns a per-slot all-finite verdict over the logits ([B] bool,
    fused into the step — no extra dispatch or host sync beyond the
    fetch the host loop already does): the serving anomaly guard. The
    healthy-path math is untouched (one extra reduction output), and
    with the flag off this builder key is byte-identical to the PR 12
    executable.

    ``quant`` = (weight_dtype, kv_dtype) (serving/quant.py) keys the
    quantized variants: quantized weights ride scale leaves inside the
    params tree (same signature), a quantized KV pool appends the
    per-page ``ksc``/``vsc`` [L, P] traced scale operands AFTER
    ``key_data`` (donate indices untouched). quant=None is byte-identical
    to the PR 13 builder.

    ``adapters`` = ``AdapterSpec.key()`` (serving/adapters.py) keys the
    per-slot LoRA-delta variants: the per-slot adapter row id [B] and the
    stacked delta slabs {target: (A, B)} arrive as traced operands AFTER
    the kv scales. The id is DATA — a mixed-adapter batch (base rows
    included) shares this one executable at its two steady-state shapes,
    and adapter load/evict/swap (content-only slab rewrites) never
    retrace. adapters=None is byte-identical to the adapter-less
    builder.

    ``model`` is the served model's seam (serving/served_model.py): its
    forward takes and returns the pools, as many arrays as its cache
    geometry names over all its groups (GPT: kc, vc), which ride as the
    operands after ``params`` and come back first; ``table`` is the one
    group's page table or a tuple of one a group; where the forward
    returns statistics they are the step's last output.

    What is jitted is ``packed_fn``: ``(params, *pools, packed[, kv
    scales][, adapter slabs], layout=) -> (*pools, out)``. The per-slot
    operands arrive as ONE int32 vector, laid out by the static ``layout``
    (serving/operands.py) and unpacked bit for bit at the top of the
    trace; the small outputs leave as one. It is jitted once a layout
    under the dispatch shape's name (``_ShapedStep``), so a device trace
    reads ``jit_pt_paged_b16_t1`` where it read ``jit_packed_fn``. ``fn``,
    the step over named operands, is what it was; ``step.named`` is ``fn``
    jitted alone, for whoever reads the step's jaxpr or lowers it by its
    operands' names."""
    config = model.view(cfg)
    n_pools = len(model.geometry(config).names)
    kvq = quant is not None and quant[1] != "bf16"

    def fn(params, *operands):
        metrics.bump("paged_traces")  # body runs only when traced
        pools = operands[:n_pools]
        (ids, start, valid, emit, table, do_sample, temperature, top_p,
         key_data, *rest) = operands[n_pools:]
        scales = None
        if kvq:
            scales = (rest[0], rest[1])
            rest = rest[2:]
        ad = (rest[0], rest[1]) if adapters is not None else None
        logits, pools, stats = model.forward(
            params, config, ids, pools, start, valid, table, page_size,
            use_kernel=use_kernel, kv_scales=scales, wq_kernel=qkernel,
            adapters=ad, mp_key=mp_key)
        tail = () if stats is None else (stats,)
        with jax.named_scope("pt_tail"):
            keys = jax.random.wrap_key_data(key_data)       # [B] keys
            pair = jax.vmap(jax.random.split)(keys)         # [B, 2] keys
            nxt = _next_token(logits, pair[:, 1], do_sample & emit,
                              temperature, top_k, top_p)
            new_keys = jnp.where(emit[:, None],
                                 jax.random.key_data(pair[:, 0]), key_data)
        if anomaly:
            ok = jnp.all(jnp.isfinite(logits), axis=-1)     # [B] per-slot
            return (*pools, nxt, new_keys, ok, *tail)
        return (*pools, nxt, new_keys, *tail)

    def packed_fn(params, *operands, layout):
        packed, *rest = operands[n_pools:]
        *slot, adapter_ids = layout.unpack(packed)
        if adapters is not None:        # the ids ride before the slabs
            rest.insert(len(rest) - 1, adapter_ids)
        out = fn(params, *operands[:n_pools], *slot, *rest)
        nxt, new_keys, *tail = out[n_pools:]
        ok = tail.pop(0) if anomaly else None
        with jax.named_scope("pt_tail"):
            out_vec = pack_out(nxt, new_keys, ok, tail[0] if tail else None)
        return (*out[:n_pools], out_vec)

    return _ShapedStep(packed_fn, fn, donate)


def _jit_as(name, fn, donate=()):
    """``fn`` jitted under ``name``: its runs read ``jit_<name>(...)`` on a
    device trace's ``XLA Modules`` line, and the program's ``pt.serve.*``
    spans carry ``exe=<name>``."""
    def run(*args):
        return fn(*args)
    run.__name__ = run.__qualname__ = name
    return jax.jit(run, donate_argnums=donate)


class _ShapedStep:
    """The paged step of one builder key, called as ``step(*operands,
    layout=)`` as the one jit with a static ``layout`` was: one jitted
    wrapper a ``StepLayout``, named by its dispatch shape
    (``StepLayout.exe``), kept here so that every engine of the key shares
    it (one trace a shape, as before). ``named`` is the step over named
    operands, jitted alone."""

    def __init__(self, packed_fn, fn, donate):
        self._packed_fn, self._donate = packed_fn, donate
        self._exes = {}
        self.named = jax.jit(fn)

    def exe(self, layout):
        """The jitted wrapper of ``layout``."""
        got = self._exes.get(layout)
        if got is None:
            got = self._exes[layout] = _jit_as(
                layout.exe, partial(self._packed_fn, layout=layout),
                self._donate)
        return got

    def __call__(self, *operands, layout):
        return self.exe(layout)(*operands)

    def lower(self, *operands, layout):
        return self.exe(layout).lower(*operands)


@lru_cache(maxsize=None)
def _make_page_copy(donate):
    """Physical page copy (the CoW split): one executable, src/dst traced
    scalars, reused for every copy-on-write divergence."""

    def fn(pools, src, dst):
        metrics.bump("copy_traces")  # body runs only when traced
        return tuple(a.at[:, dst].set(a[:, src]) for a in pools)

    return _jit_as("pt_page_copy", fn, donate)


@lru_cache(maxsize=None)
def _make_page_read():
    """Read one physical page out of the pool (the prefill worker's
    transfer-out path): src is a traced scalar, one executable for every
    page hauled to the host at the pool's storage dtype."""

    def fn(kc, vc, src):
        metrics.bump("read_traces")  # body runs only when traced
        return kc[:, src], vc[:, src]

    return jax.jit(fn)


@lru_cache(maxsize=None)
def _make_page_write(donate):
    """Write one page payload into the pool (the decode worker's
    transfer-in path): dst is a traced scalar, so installing any page of
    any transfer reuses ONE executable."""

    def fn(kc, vc, kpage, vpage, dst):
        metrics.bump("write_traces")  # body runs only when traced
        # a payload carries the model's head_dim, the pool whole lanes
        kc = kc.at[:, dst].set(pad_lanes(kpage, kc))
        vc = vc.at[:, dst].set(pad_lanes(vpage, vc))
        return kc, vc

    return jax.jit(fn, donate_argnums=donate)


@lru_cache(maxsize=None)
def _make_spec_draft(cfg, page_size, k, quant=None, name="pt_draft"):
    """Build the speculative DRAFT executable: greedily roll the draft
    params ``k`` tokens ahead of every slot, reading the shared paged
    pool (strictly below each slot's write position) and carrying the
    in-window KV in a [L, B, k, nh, d] sidecar — the pool is NEVER
    written, so a rejected proposal needs zero draft-side rewind.
    nprop gating is the verify pass's job (its accept scan stops at
    nprop[b]); the draft always rolls the full static k so one
    executable serves every per-slot proposal depth. Memoized per
    (config, page_size, k, quant) — both draft sources share this one
    wrapper; their distinct param TREES (int8 scale leaves vs sliced
    shallow blocks) key distinct traces under it, exactly like the
    quantized vs bf16 fused step. ``name`` is what the executable runs
    under (``_jit_as``): the engine gives its dispatch shape's."""
    config = _cfg_view(cfg)
    kvq = quant is not None and quant[1] != "bf16"

    def fn(draft_params, kc, vc, tok, pos, table, *kv_scales):
        metrics.bump("spec_draft_traces")  # body runs only when traced
        scales = tuple(kv_scales) if kvq else None
        return paged_draft_forward(draft_params, config, tok, kc, vc, pos,
                                   table, page_size, k, kv_scales=scales)

    # NO donation: kc/vc must survive — the verify dispatch reads them next
    return _jit_as(name, fn)


@lru_cache(maxsize=None)
def _make_spec_verify(cfg, top_k, page_size, donate, anomaly=False,
                      quant=None, qkernel=False, name="pt_verify"):
    """Build the fused speculative VERIFY executable: score ALL slots'
    [B, k+1] windows (lane 0 = the last emitted token, lanes 1..k = the
    draft's proposals) with the SERVED weights, run the accept scan
    (per-slot nprop/emit/sampling params as traced operands — the
    chunk-ladder trick, so mixed speculative/plain/greedy/sampled
    traffic shares this one executable), then rewind every KV byte
    written past each slot's accepted length back to its pre-dispatch
    value. PRNG keys split once per EMITTED token inside the scan, so
    sampled streams replay ``generate_from_params`` exactly.

    ``anomaly=True`` mirrors the fused step's guard: a slot is flagged
    only if a NON-finite logit occurs on a lane it actually emitted
    from — rejected lanes' logits are dead values. ``name``: as the
    draft's."""
    config = _cfg_view(cfg)
    kvq = quant is not None and quant[1] != "bf16"

    def fn(params, kc, vc, ids, start, valid, emit, table, nprop,
           do_sample, temperature, top_p, key_data, *kv_scales):
        metrics.bump("spec_verify_traces")  # body runs only when traced
        scales = tuple(kv_scales) if kvq else None
        logits, kc, vc, saved_k, saved_v = paged_verify_forward(
            params, config, ids, kc, vc, start, valid, table, page_size,
            False, kv_scales=scales, wq_kernel=qkernel)
        # lane i's logits score the token AFTER window position i: the
        # proposal to check against is ids[:, i+1] (last lane has none)
        ids_next = jnp.concatenate(
            [ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1)
        toks, n_emit, new_keys = _verify_accept(
            logits, ids_next, nprop, emit, do_sample, temperature, top_p,
            key_data, top_k)
        kc, vc = paged_kv_rewind(kc, vc, saved_k, saved_v, table, start,
                                 valid, n_emit, page_size)
        if anomaly:
            T = ids.shape[1]
            lane = jnp.arange(T)[None, :]
            fin = jnp.all(jnp.isfinite(logits), axis=-1)        # [B, T]
            ok = jnp.all((lane >= n_emit[:, None]) | fin, axis=-1)
            return kc, vc, toks, n_emit, new_keys, ok
        return kc, vc, toks, n_emit, new_keys

    return _jit_as(name, fn, donate)


class Engine:
    """Continuous-batching serving engine.

    Accepts a ``GPTForCausalLM`` Layer or the functional param tree
    (``init_gpt_params`` layout, the thing ``HybridTrainStep`` trains), so
    trained params serve directly::

        eng = serving.Engine(model, num_slots=8)              # from a Layer
        eng = serving.Engine(params=step.params, config=cfg)  # from params

        eng.submit(serving.Request([1, 2, 3], max_new_tokens=32,
                                   eos_token_id=50256, on_token=stream_cb))
        results = eng.run()        # drain queue + slots

    Defaults come from FLAGS_serving_* (flags.py); kwargs override.
    """

    def __init__(self, model=None, *, params=None, config=None,
                 num_slots=None, max_seq_len=None, max_queue=None,
                 top_k=None, page_size=None, num_pages=None,
                 prefill_chunk=None, prefix_cache=None,
                 tag=None, trace=None, priority=None, tenant_weights=None,
                 shed=None, params_version=0, mesh=None, mp=None,
                 comm_backend=None, anomaly=None, quant=None, role=None,
                 speculate_k=None, draft_source=None, draft_layers=None,
                 adapter_slots=None, adapter_rank=None,
                 tenant_adapters=None):
        if model is not None:
            params = _collect_params(model)
            config = model.config
        if params is None or config is None:
            raise ValueError("Engine needs a GPTForCausalLM model, or "
                             "params= (init_gpt_params layout) + config=")
        self.config = config
        # the served model's seam (serving/served_model.py): its cache
        # geometry and its paged forward are all the engine asks of it
        self._model = served_model(config)
        flags = get_flags()

        # -- quantized serving (serving/quant.py): resolve the dtype
        # config FIRST — it decides the stored weight leaves, the KV
        # pool's storage dtype and the per-page scale tables. quant=None
        # + bf16 flags resolves to None and every quantized code path
        # below is skipped: the engine is byte-identical to the
        # unquantized one (the flags-off parity contract).
        self._quant = _squant.resolve(quant, flags)
        self._refuse("quant", self._quant is not None)
        if self._quant is not None:
            _squant.validate(self._quant, params, config)
            # fill missing KV clip ranges by the automatic one-forward
            # calibration over the deterministic token sample — the
            # flags-only path where no PTQ artifact exists
            self._quant = _squant.ensure_kv_clips(self._quant, params,
                                                  config)

        # -- tensor-parallel serving (serving/mp_forward.py): resolve the
        # mp mesh FIRST — it decides the param layout (head-major sharded
        # vs logical replicated). mp > 1 shards the GPT weights column-
        # parallel and the paged KV pool's head axis over a 1-D 'mp' mesh;
        # the schedule is gather-only, so engine output stays BITWISE
        # identical to the single-chip engine on every collective rung.
        if mesh is None and mp is None:
            mp = int(flags.get("FLAGS_serving_mp", 0) or 0)
        self._refuse("mp", mesh is not None or int(mp or 0) > 1)
        if mesh is None and mp is not None and int(mp) > 1:
            from .mp_forward import replica_mesh
            mesh = replica_mesh(int(mp))
        self._mesh = None
        self._mp_cfg = None
        self._kv_sharding = None
        if mesh is not None:
            from ..distributed import tp_overlap as _tpov
            self._mp_cfg = _tpov.resolve_serving(config, mesh,
                                                 backend=comm_backend)
            if self._mp_cfg is not None:
                self._mesh = mesh
        self.mp = 1 if self._mp_cfg is None else self._mp_cfg.n
        self._mp_records = {}        # dispatch shape -> static comm record
        if self.mp > 1:
            # head-major + column-sharded placement; an already-mp-sharded
            # HybridTrainStep tree (config.qkv_head_major) is device_put
            # straight to the serving shardings — no host round trip.
            # A quant spec quantizes BEFORE placement (per-channel
            # quantization is column-independent, so the shards are
            # bitwise the single-chip engine's column slices).
            from .mp_forward import shard_serving_params
            self.params = shard_serving_params(params, config, self._mesh,
                                               self._mp_cfg,
                                               quant_spec=self._quant)
            metrics.set_mp_info(self.mp, self._mp_cfg.backend)
        else:
            params = self._model.prepare(params, config)
            if self._quant is not None and self._quant.quantizes_weights:
                params = _squant.quantize_params(params, config,
                                                 self._quant)
            self.params = jax.tree_util.tree_map(jnp.asarray, params)
        # per-request span tracing (observability/tracing.py): host-side
        # only — recording sites are gated on `req.trace is not None`, so
        # disabled tracing costs one attribute check and the executables /
        # trace counters are identical either way
        self.trace_enabled = (bool(flags.get("FLAGS_serving_trace", False))
                              if trace is None else bool(trace))
        # FLAGS_metrics_port: bring the Prometheus endpoint up with the
        # serving runtime (no-op at the default 0; idempotent otherwise)
        from ..observability import prometheus as _prom
        _prom.start_from_flags()
        self.num_slots = int(num_slots or flags.get("FLAGS_serving_slots", 8))
        self.max_seq_len = int(max_seq_len or
                               flags.get("FLAGS_serving_max_seq_len", 0) or
                               config.max_seq_len)
        if self.max_seq_len > config.max_seq_len:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} exceeds the model's wpe "
                f"table ({config.max_seq_len})")
        # SLO traffic management (serving/slo.py) — ALL policy, no traced
        # operand or executable changes: with both knobs off, admission is
        # the strict FCFS the parity suites gate, byte-identical to the
        # pre-SLO engine.
        self.priority_mode = (
            bool(flags.get("FLAGS_serving_priority_classes", False))
            if priority is None else bool(priority))
        self._class_deadlines = {
            "interactive": float(
                flags.get("FLAGS_serving_class_deadline_interactive", 0.0)),
            "batch": float(
                flags.get("FLAGS_serving_class_deadline_batch", 0.0)),
            "best_effort": float(
                flags.get("FLAGS_serving_class_deadline_best_effort", 0.0)),
        }
        self._preempt_margin_s = float(
            flags.get("FLAGS_serving_preempt_margin_s", 0.0))
        # -- per-slot LoRA-class adapters (serving/adapters.py): resolve
        # the CAPACITY spec before the scheduler — WFQ lanes rotate across
        # ADAPTERS when adapters are on (the many-model fairness axis),
        # across tenants otherwise. Off (the default
        # FLAGS_serving_adapter_slots=0) resolves to None and every
        # adapter code path below is skipped: executables, dispatch
        # signatures and trace counters are byte-identical to the
        # adapter-less engine (the flags-off parity contract).
        self._adapter_spec = AdapterSpec.resolve(
            flags.get("FLAGS_serving_adapter_slots", 0)
            if adapter_slots is None else adapter_slots,
            flags.get("FLAGS_serving_adapter_rank", 8)
            if adapter_rank is None else adapter_rank)
        self._refuse("adapters", self._adapter_spec is not None)
        self.adapters = None            # AdapterRegistry once constructed
        self._tenant_adapters = {}
        lane_key = (None if self._adapter_spec is None
                    else (lambda r: r.adapter or 0))
        self.scheduler = Scheduler(
            max_queue=int(max_queue or
                          flags.get("FLAGS_serving_max_queue", 256)),
            priority=self.priority_mode, tenant_weights=tenant_weights,
            lane_key=lane_key)
        shed_on = (bool(flags.get("FLAGS_serving_shed", False))
                   if shed is None else bool(shed))
        self._shed = None
        if shed_on:
            self._shed = ShedPolicy(
                self.scheduler.max_queue,
                high=float(flags.get("FLAGS_serving_shed_high", 0.75)),
                low=float(flags.get("FLAGS_serving_shed_low", 0.5)),
                window=int(flags.get("FLAGS_serving_shed_window", 4)))
        # weight-swap audit trail: every admitted request is stamped with
        # the version its tokens are produced under
        self.params_version = int(params_version)
        self._resolved_total = 0          # feeds the shed drain-rate EWMA
        # serving anomaly guard (FLAGS_serving_anomaly_policy): "off"
        # (default — the fused step and its trajectory are byte-identical
        # to the unguarded engine) or "quarantine" (a per-slot all-finite
        # check on the logits rides the fused step; a poisoned slot is
        # resolved finish_reason="error" at the boundary — freed WITHOUT
        # publishing its prompt pages to the prefix cache — while its
        # neighbors stay bitwise-stable, so a NaN from bad weights or a
        # flaky chip never poisons the shared batch or a snapshot)
        policy = (flags.get("FLAGS_serving_anomaly_policy", "off")
                  if anomaly is None else anomaly)
        if policy not in ("off", "quarantine"):
            raise ValueError(
                f"FLAGS_serving_anomaly_policy must be 'off' or "
                f"'quarantine', got {policy!r}")
        self.anomaly_policy = policy
        self._anomaly = policy != "off"
        self.top_k = (None if top_k in (None, 0)
                      else min(int(top_k), config.vocab_size))

        # speculative decoding (FLAGS_serving_speculate_k): resolves to
        # None at the default 0 and every speculative code path below is
        # skipped — the engine's executables, dispatch sequence and trace
        # counters are byte-identical to the plain engine (the flags-off
        # parity contract every serving PR carries).
        self._spec = _squant.resolve_draft(speculate_k, draft_source,
                                           draft_layers, flags)
        self._refuse("spec", self._spec is not None)
        self.speculate_k = 0 if self._spec is None else self._spec.k
        self._draft_params = None
        self._spec_draft = None
        self._spec_verify = None
        self._draft_params_version = None
        if self._spec is not None and self.mp > 1:
            raise ValueError(
                "speculative decoding is single-chip for now (the draft/"
                "verify pair would double the mp collective schedule); "
                "use mp=1 with FLAGS_serving_speculate_k > 0")
        if self._adapter_spec is not None:
            if self._spec is not None:
                raise ValueError(
                    "adapter serving is mutually exclusive with "
                    "speculative decoding for now (the draft would need "
                    "its own per-slot delta routing to keep accept rates "
                    "honest); use FLAGS_serving_speculate_k=0 with "
                    "FLAGS_serving_adapter_slots > 0")
            self.adapters = AdapterRegistry(config, self._adapter_spec,
                                            mesh=self._mesh)
            self._tenant_adapters = (
                resolve_tenant_adapters(flags) if tenant_adapters is None
                else {str(k): int(v)
                      for k, v in dict(tenant_adapters).items()})
            for t, a in self._tenant_adapters.items():
                if not 0 <= int(a) <= self._adapter_spec.slots:
                    raise UnknownAdapterError(
                        a, f"tenant {t!r} maps to adapter id {a} outside "
                           f"capacity 0..{self._adapter_spec.slots}")
            metrics.set_adapter_info(self._adapter_spec.slots,
                                     self._adapter_spec.rank,
                                     self.adapters.row_bytes())
            metrics.set_adapter_residency(0, 0)

        from ..framework.compilation_cache import ensure_persistent_cache
        ensure_persistent_cache()
        cfg = self._model.key(config)
        donate_ok = jax.default_backend() != "cpu"  # cpu: donation unimplemented
        B = self.num_slots
        self._geo = geo = self._model.geometry(config)
        n_pools = len(geo.names)
        compute = jnp.dtype(geo.dtype)

        self.page_size = int(page_size or
                             flags.get("FLAGS_serving_page_size", 16))
        self.prefill_chunk = int(
            prefill_chunk or flags.get("FLAGS_serving_prefill_chunk", 16))
        if self.prefill_chunk < self.page_size:
            raise ValueError(
                f"prefill_chunk ({self.prefill_chunk}) must be >= "
                f"page_size ({self.page_size})")
        # the chunk LADDER: power-of-two multiples of page_size up to
        # prefill_chunk. Bulk prefill rides the largest rung; the tail
        # drops down the ladder so the final chunk's padding is always
        # < page_size. One executable per rung, all trace-gated.
        self._chunk_ladder = [self.page_size]
        while self._chunk_ladder[-1] * 2 <= self.prefill_chunk:
            self._chunk_ladder.append(self._chunk_ladder[-1] * 2)
        if prefix_cache is None:
            # the flag's default is on; a model that cannot share prefixes
            # yet resolves to off and refuses an explicit True
            prefix_cache = bool(
                flags.get("FLAGS_serving_prefix_cache", True)) \
                and "prefix_cache" not in self._model.unsupported
        self._refuse("prefix_cache", bool(prefix_cache))
        kv_dtype = self._quant.kv_dtype if self._quant is not None else "bf16"
        pool_kw = {}
        if kv_dtype != "bf16":
            pool_kw = dict(kv_dtype=kv_dtype,
                           num_layers=config.num_layers,
                           k_clip=self._quant.kv_k_clip,
                           v_clip=self._quant.kv_v_clip,
                           qmax=_squant.QMAX[kv_dtype])
        # an allocator and a page table a PAGED group of layers. The first
        # group's is ``pool``: it takes ``num_pages``, the prefix cache and
        # copy-on-write; a group with no window maps a request's whole
        # lifetime, a window group a ring a slot (``ring_pages``),
        # whatever the context. A state group has neither: its arrays are
        # a row a slot, found by the slot's number (_table_arg)
        if not geo.groups[0].paged:
            raise ValueError(
                f"the {self._model.name} model's first cache group must be "
                f"a paged one (serving/served_model.py)")
        self._group_pools = []
        for g in geo.paged:
            first = not self._group_pools
            slot_tokens = self.max_seq_len if g.window is None else min(
                self.max_seq_len, self.page_size * ring_pages(
                    g.window, self._chunk_ladder[-1], self.page_size))
            self._group_pools.append(PagedKVPool(
                B, slot_tokens, self.page_size,
                num_pages=int(num_pages or
                              flags.get("FLAGS_serving_num_pages", 0) or 0)
                if first and g.window is None else 0,
                prefix_cache=prefix_cache and first,
                **(pool_kw if first else {})))
        self.pool = self._group_pools[0]
        self._kv_quant = kv_dtype != "bf16"
        use_kernel = bool(flags.get("FLAGS_serving_paged_kernel", True)
                          ) and self._model.kernel_ok(
                              config, self.mp, self.page_size)
        self._paged_kernel = use_kernel
        quant_key = None if self._quant is None else self._quant.key()
        qkernel = (self._quant is not None
                   and self._quant.quantizes_weights
                   and self.mp == 1
                   and bool(flags.get("FLAGS_serving_quant_kernel", True))
                   and jax.default_backend() == "tpu")
        adapter_key = (None if self._adapter_spec is None
                       else self._adapter_spec.key())
        if self.mp > 1:
            self._paged_step = _make_paged_step(
                cfg, self.top_k, self.page_size, use_kernel,
                (1, 2) if donate_ok else (),
                mp_key=(self._mesh, self._mp_cfg),
                anomaly=self._anomaly, quant=quant_key,
                qkernel=qkernel, adapters=adapter_key)
        else:
            self._paged_step = _make_paged_step(
                cfg, self.top_k, self.page_size, use_kernel,
                tuple(range(1, 1 + n_pools)) if donate_ok else (),
                anomaly=self._anomaly, quant=quant_key, qkernel=qkernel,
                adapters=adapter_key, model=self._model)
        self._page_copy = _make_page_copy((0,) if donate_ok else ())
        if self._spec is not None:
            # one draft + one verify builder, memoized per config like
            # every other serving executable: a second spec engine
            # over warm shapes adds zero traces
            k = self._spec.k
            self._verify_exe = f"pt_verify_b{B}_t{k + 1}"
            self._draft_exe = f"pt_draft_b{B}_t{k}"
            self._spec_verify = _make_spec_verify(
                cfg, self.top_k, self.page_size,
                (1, 2) if donate_ok else (), anomaly=self._anomaly,
                quant=quant_key, qkernel=qkernel, name=self._verify_exe)
            self._spec_draft = _make_spec_draft(
                cfg, self.page_size, k, quant=quant_key,
                name=self._draft_exe)
            self._build_draft_params()
        # a row's last axis padded to whole lanes on the device;
        # snapshots and page payloads keep the model's own width
        # (_logical / pad_lanes)
        if self._kv_quant:
            compute = _squant.STORE_DTYPES[kv_dtype]
        # the pool arrays a layer keeps, group after group in the
        # geometry's order (GPT: K and V, also reachable as _kc / _vc),
        # and the group each belongs to
        self._pool_group = tuple(i for i, g in enumerate(geo.groups)
                                 for _ in g.names)
        # every group beside its allocator (a state group has none)
        allocators = iter(self._group_pools)
        self._groups = [(g, next(allocators) if g.paged else None)
                        for g in geo.groups]
        shapes = [g.pool_shape(pool.num_pages, self.page_size) if g.paged
                  else g.state_shape(B) for g, pool in self._groups]
        self._pools = tuple(jnp.zeros(shapes[i],
                                      geo.groups[i].dtype or compute)
                            for i in self._pool_group)
        self._slot_ids = np.arange(B, dtype=np.int32)
        self._operand_bufs = {}           # (b, t) -> _operands' triple
        if self._quant is not None:
            metrics.set_quant_info(
                self._quant.weight_dtype, self._quant.kv_dtype,
                scale_bytes=_squant.scale_bytes(self.params)
                + (0 if not self._kv_quant
                   else int(self.pool.k_scale.nbytes
                            + self.pool.v_scale.nbytes)),
                kv_bytes_per_token=self.kv_bytes_per_token())
        if self.mp > 1:
            # the pool's GLOBAL geometry is mp-independent (the page table
            # addresses it identically at every mp); only the HEAD axis is
            # laid out across chips — per-chip KV bytes are 1/mp
            from jax.sharding import NamedSharding
            from .mp_forward import KV_SPEC
            self._kv_sharding = NamedSharding(self._mesh, KV_SPEC)
            self._pools = tuple(jax.device_put(a, self._kv_sharding)
                                for a in self._pools)

        # host-authoritative per-slot state (numpy; re-uploaded every step —
        # tiny arrays, and exactly why joins/evicts can never retrace)
        self._slots = [None] * B          # Request or None
        self._pos = np.zeros(B, np.int32)       # write position of next token
        self._tok = np.zeros(B, np.int32)       # last emitted token
        self._keys = np.zeros((B, 2), np.uint32)
        self._temp = np.ones(B, np.float32)
        self._top_p = np.ones(B, np.float32)
        self._do_sample = np.zeros(B, bool)
        self._aid = np.zeros(B, np.int32)       # per-slot adapter row id
        # next prompt index to prefill for slot b (== prompt_len once
        # prefill is done and the slot is decoding), plus the admission
        # sequence number that keeps chunked prefill FCFS across slots
        self._chunk_off = np.zeros(B, np.int32)
        self._admit_seq = np.zeros(B, np.int64)
        self._admit_count = 0
        # admission (or seating) instant of each slot's request: where its
        # prefill_span_s starts. Not snapshotted: a restored slot's span
        # restarts at the restore
        self._admit_t = [0.0] * B
        self._clock = metrics.PhaseClock(keep_spans=self.trace_enabled)
        self._results = {}                # request_id -> GenerationResult

        # disaggregated serving (serving/kv_transfer.py): role is
        # host-side SCHEDULING policy over the same executables — a
        # prefill worker never dispatches the [B,1] decode shape, a
        # decode worker seats streamed pages as if the prompt were an
        # exact prefix-cache hit — which is what keeps disaggregated
        # output bitwise identical to a single-engine run.
        self.role = "both"
        self._outbound = {}            # rid -> KVTransfer (prefill side)
        self._fresh_outbound = []      # transfers not yet taken by the sup
        self._transfers_in = []        # KVTransfers offered to this decoder
        self._install_progress = {}    # rid -> pages installed so far
        self._transfer_budget = int(
            flags.get("FLAGS_serving_transfer_pages_per_boundary", 4))
        # end-to-end KV wire integrity: stamp outbound page payloads with
        # CRC32 at creation, re-verify at install (kv_transfer.py)
        self._kv_crc = bool(flags.get("FLAGS_kv_transfer_crc", False))
        self._page_read = None
        self._page_write = None
        # per-role trace gates (host counters beside the global
        # paged_traces gate): decode dispatches and chunk rungs actually
        # used BY THIS ENGINE — the per-role acceptance criteria
        self._decode_dispatches = 0
        self._chunk_rungs = set()
        self.set_role(role if role is not None
                      else flags.get("FLAGS_serving_role", "both"))

        # self-healing state: step counter (snapshot cadence + chaos
        # hooks), attached snapshot manager, drain/stop latch
        self.tag = "engine" if tag is None else str(tag)
        self._step_count = 0
        self._stopped = False
        self._reforming = False           # stop_for_reform: temporary stop
        self._reform_retry_after = None
        self._ckpt = None
        self._snapshot_every = 0
        self._drained = []                # requests the last drain() handed back

    def _refuse(self, option, asked):
        """What the served model does not support yet raises here, at
        construction, in one sentence that names the option."""
        if asked and option in self._model.unsupported:
            raise ValueError(
                f"serving.Engine does not serve the {self._model.name} "
                f"model with {option!r} yet (unsupported: "
                f"{', '.join(sorted(self._model.unsupported))})")

    # GPT's two pool arrays by name, for the paths only GPT takes
    # (speculative verify, KV transfer, the chaos hooks) and tests
    @property
    def _kc(self):
        return self._pools[0]

    @_kc.setter
    def _kc(self, a):
        self._pools = (a,) + self._pools[1:]

    @property
    def _vc(self):
        return self._pools[1]

    @_vc.setter
    def _vc(self, a):
        self._pools = self._pools[:1] + (a,) + self._pools[2:]

    # -- submission ----------------------------------------------------------
    def _check_stopped(self):
        if self._stopped:
            pending = [r for r in self._drained
                       if r.state not in (FINISHED,)]
            if self._reforming:
                hint = self._reform_retry_after
                raise EngineStoppedError(
                    f"engine {self.tag!r} is mid-reform (its mp group is "
                    f"being re-formed after a chip loss/return); the "
                    f"replica comes back momentarily — retry"
                    f"{f' in ~{hint:.2f}s' if hint is not None else ''}",
                    queue_depth=len(pending),
                    requeued=[r.request_id for r in pending],
                    reforming=True, retry_after=hint)
            raise EngineStoppedError(
                f"engine {self.tag!r} is stopped (drained"
                f"{' after preemption' if self._ckpt is not None and self._ckpt.preempted else ''}); "
                f"resubmit to a live replica or to an engine restored from "
                f"its last snapshot ({len(pending)} drained requests are "
                f"waiting to be requeued)",
                queue_depth=len(pending),
                requeued=[r.request_id for r in pending])

    def stop_for_reform(self, retry_after=None):
        """Mark this engine TEMPORARILY stopped for a group reform: the
        supervisor is rebuilding the replica on a different chip set and
        every piece of state moves with it (live snapshot or disk
        restore), so — unlike ``drain()`` — nothing is requeued or
        mutated here. ``submit()`` raises ``EngineStoppedError`` with
        ``reforming=True`` and the ``retry_after`` hint; the router
        treats the replica as temporarily unroutable, not dead."""
        # publish the reform markers BEFORE the stop (same ordering
        # discipline as rep.state vs rep.engine in the supervisor): a
        # concurrent submit that sees stopped must never read a
        # not-yet-reforming engine and write the replica off as dead
        self._reform_retry_after = (None if retry_after is None
                                    else float(retry_after))
        self._reforming = True
        self._stopped = True

    # -- disaggregated roles -------------------------------------------------
    def set_role(self, role):
        """Assign this engine's serving role ("both" | "prefill" |
        "decode") — host-side policy only, settable while the engine is
        IDLE (no slots, no queue, no in-flight transfers): a mid-stream
        flip would strand half-prefilled slots with no decoder. The
        supervisor flips roles only through a drain (``_set_replica_role``).
        The handoff is a page copy + a table splice."""
        role = str(role)
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be 'both', 'prefill' or 'decode', got {role!r}")
        self._refuse("kv_transfer", role != "both")
        if role != "both" and getattr(self, "adapters", None) is not None:
            raise ValueError(
                "adapter serving is single-role for now (a prefill/decode "
                "handoff would have to carry the adapter-residency "
                "contract across workers); use role='both' with "
                "FLAGS_serving_adapter_slots > 0")
        if (any(r is not None for r in self._slots)
                or self.scheduler.qsize() > 0
                or self._outbound or self._transfers_in):
            raise RuntimeError(
                "set_role on a non-idle engine: drain() first")
        self.role = role
        if role != "both":
            donate_ok = jax.default_backend() != "cpu"
            self._page_read = _make_page_read()
            self._page_write = _make_page_write((0, 1) if donate_ok else ())
        return self

    def take_outbound(self):
        """Pop the transfers opened since the last call (the supervisor
        polls this on a prefill worker every boundary and routes them)."""
        out, self._fresh_outbound = self._fresh_outbound, []
        return out

    def prefill_backlog(self):
        """Prompt tokens this engine still has to prefill: the remaining
        chunk tokens of every mid-prefill slot plus every queued prompt.
        The supervisor folds this into its load probe — queue depth alone
        makes a replica mid-giant-prefill look idle."""
        backlog = 0
        for b, req in enumerate(self._slots):
            if req is not None:
                backlog += max(0, req.prompt_len - int(self._chunk_off[b]))
        backlog += sum(r.prompt_len for r in self.scheduler._q
                       if r.state != FINISHED)
        return backlog

    def prefix_page_hashes(self, prompt):
        """Stable routing key for prefix-affinity: ``(page_hashes,
        exact_key)`` where ``page_hashes[j]`` digests the cumulative
        full-page prefix ``prompt[:(j+1)*page_size]`` and ``exact_key``
        digests the whole prompt — the same keys (hashed) the prefix
        cache indexes by, so the router and tests never reach into cache
        internals."""
        import hashlib
        prompt = np.ascontiguousarray(np.asarray(prompt, np.int32))
        ps = self.page_size
        hashes = tuple(
            hashlib.blake2b(prompt[:j * ps].tobytes(),
                            digest_size=16).hexdigest()
            for j in range(1, len(prompt) // ps + 1))
        exact = hashlib.blake2b(prompt.tobytes(),
                                digest_size=16).hexdigest()
        return hashes, exact

    def prefix_coverage(self, prompt):
        """Tokens of ``prompt`` this engine's prefix cache already holds
        (longest cached prefix, LRU-neutral probe)."""
        prompt = np.ascontiguousarray(np.asarray(prompt, np.int32))
        return self.pool.peek_coverage(prompt)

    def submit(self, request):
        """Queue a request (FCFS). Raises QueueFullError past max_queue,
        EngineStoppedError after drain()/preemption, ValueError for
        requests the pool can never hold."""
        if not isinstance(request, Request):
            request = Request(request)
        self._check_stopped()
        if request.state != QUEUED:
            # single-use: the max_new_tokens==0 fast path below must not
            # re-resolve (and re-ledger) an already-finished request
            raise ValueError(f"request {request.request_id} already "
                             f"{request.state}; requests are single-use")
        if self.trace_enabled and request.trace is None:
            request.trace = obs_tracing.RequestTrace(request.request_id)
        metrics.bump("submitted")
        plen = request.prompt_len
        if plen + request.max_new_tokens > self.max_seq_len:
            metrics.bump("rejected")
            raise ValueError(
                f"prompt ({plen}) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds the KV table capacity "
                f"max_seq_len ({self.max_seq_len})")
        # worst-case demand is exactly the lifetime page count: a CoW spare
        # is reserved only when >= 1 page is prefix-shared, and every shared
        # page reduces the fresh-page need by one. A request that can NEVER
        # fit must fail fast instead of deadlocking the FCFS queue head.
        worst = pages_for(plen + request.max_new_tokens, self.page_size)
        for pool in self._group_pools:
            if min(worst, pool.slot_pages) > pool.num_pages - 1:
                metrics.bump("rejected")
                raise ValueError(
                    f"request needs up to {min(worst, pool.slot_pages)} KV "
                    f"pages but the pool only has {pool.num_pages - 1}")
        if request.top_k not in (None, self.top_k):
            metrics.bump("rejected")
            raise ValueError(
                f"request top_k={request.top_k} differs from the engine's "
                f"static top_k={self.top_k}; per-value top_k would recompile "
                f"the shared executables (construct the Engine with that "
                f"top_k instead)")
        if request.do_sample and request.top_k is None \
                and self.top_k is not None:
            # greedy is top-k-invariant (argmax survives the mask), but a
            # sampled request would silently draw from top-k-truncated
            # logits, diverging from generate_from_params(top_k=None)
            metrics.bump("rejected")
            raise ValueError(
                f"sampled request with top_k=None on an engine compiled "
                f"with static top_k={self.top_k}; pass top_k={self.top_k} "
                f"to accept the engine's truncation, or serve it from an "
                f"Engine built with top_k=None")
        if request.adapter is None:
            # tenant default mapping (FLAGS_serving_tenant_adapters):
            # unmapped tenants serve the base model
            request.adapter = int(
                self._tenant_adapters.get(request.tenant, 0))
        if request.adapter != 0:
            # typed refusal UP FRONT for ids the engine can never serve
            # (disabled adapters / outside capacity). A merely
            # NON-RESIDENT id is NOT an error: the request queues and
            # admission blocks until load_adapter makes it resident.
            if self.adapters is None:
                metrics.bump("rejected")
                raise UnknownAdapterError(
                    request.adapter,
                    f"request names adapter {request.adapter} but this "
                    f"engine serves no adapters "
                    f"(FLAGS_serving_adapter_slots=0)")
            try:
                self.adapters._check_id(request.adapter)
            except UnknownAdapterError:
                metrics.bump("rejected")
                raise
        if request.max_new_tokens == 0:
            # parity with generate(max_new_tokens=0): prompt unchanged
            request.submit_t = time.perf_counter()
            self._resolve(request, LENGTH)
            return request
        if self.priority_mode and request.deadline_s is None:
            # per-class default deadline (0 = none): the SLO contract a
            # class carries when the caller didn't set one explicitly
            dflt = self._class_deadlines.get(request.priority, 0.0)
            if dflt > 0:
                request.deadline_s = dflt
        if self._shed is not None and self._shed.shedding \
                and request.class_rank >= 2:
            # sustained overload, shedding latched: refuse new best-effort
            # work UP FRONT with the drain-rate hint instead of queueing it
            # only to shed it a boundary later
            qsize = self.scheduler.qsize()
            hint = self._shed.retry_after(qsize)
            metrics.bump("shed")
            raise ShedError(
                f"shedding {request.priority} traffic under sustained "
                f"overload ({qsize} waiting); retry in ~{hint:.2f}s",
                qsize=qsize, max_queue=self.scheduler.max_queue,
                retry_after=hint)
        try:
            self.scheduler.submit(request)
        except QueueFullError:
            metrics.bump("rejected")
            raise
        return request

    def requeue(self, request):
        """Re-admit a drained/preempted request (the replay path): unlike
        ``submit`` it bypasses the ``max_queue`` bound (the request was
        already accepted once — dropping it now would break the zero-drop
        drain guarantee), inserts at the request's ORIGINAL arrival
        position (global FCFS survives a drain) and keeps its original
        ``submit_t``/deadline. Returns True unless the request was
        cancelled while in flight between drain and requeue.

        (Not counted in the ``requeued`` ledger — that counter means
        "in-flight requests reset to queue state by a drain", bumped
        exactly once in ``drain()``; cross-replica re-insertion is the
        supervisor's ``replayed``.)"""
        self._check_stopped()
        return self.scheduler.requeue(request)

    def cancel(self, request, *, count="cancelled"):
        """Abort a queued or running request; its slot (if any) is recycled
        at the next step boundary. Race-safe against a concurrent drain: a
        request cancelled while it sits BETWEEN drain() and a requeue (in
        neither the wait queue nor a slot) resolves as cancelled here, and
        ``Scheduler.requeue``/``admit`` skip already-resolved requests.

        ``count=None`` skips the ledger bump — for internal hygiene
        cancels (a supervisor pruning a stale snapshot's duplicates) that
        are not user cancellations and must not skew the SLO counters."""
        if request.state == QUEUED:
            in_queue = self.scheduler.cancel(request)
            if in_queue or request in self._drained:
                self._resolve(request, CANCELLED, count=count)
        elif request.state == RUNNING:
            b = request.slot
            if b is not None and 0 <= b < self.num_slots \
                    and self._slots[b] is request:
                self._free_slot(b)
                self._resolve(request, CANCELLED, count=count)
            elif request.request_id in self._install_progress:
                # cancelled MID-TRANSFER on the decode side (RUNNING, no
                # slot anywhere): abort the stream, return staged pages
                rid = request.request_id
                for tr in self._transfers_in:
                    if tr.request_id == rid:
                        tr.aborted = True
                self.pool.release_staged(rid)
                self._install_progress.pop(rid, None)
                self._transfers_in = [t for t in self._transfers_in
                                      if t.request_id != rid]
                self._resolve(request, CANCELLED, count=count)
            # else: a RUNNING handle this engine does not host (e.g. a
            # stale snapshot copy whose live twin moved to another
            # replica) — freeing request.slot here would evict whatever
            # unrelated request occupies that slot. Not ours: no-op.

    # -- one engine iteration ------------------------------------------------
    def step(self):
        """One scheduling boundary + one decode iteration: evict expired,
        admit (prefill) into free slots, decode one token for every active
        slot. Returns True while any work remains."""
        if self._stopped:
            return False
        # the phase clock (metrics.PhaseClock): the step opens in its admit
        # phase, every dispatch site below switches it to feed / wait /
        # emit, and the boundary's sums reach the ledger under one lock
        self._clock.start()
        try:
            return self._step()
        finally:
            spans = self._clock.finish()
            if spans is not None:
                obs_tracing.collect_boundary(self.tag, spans)

    def _step(self):
        # chaos hook: simulated ABRUPT engine death (no flush) — recovery
        # must come from the last periodic snapshot or request replay
        _fi.maybe_kill_serving(self.tag, self._step_count)
        # chaos hook: FINITE silent corruption of the live KV pool — the
        # all-finite anomaly guard cannot see it; only the shadow audit can
        if _fi._plan is not None and _fi._plan.kv_bitflip_at:
            self._maybe_kv_bitflip()
        now = time.perf_counter()

        # 1) evict running requests whose deadline passed (same boundary
        #    predicate — Request.expired — as every queue-expiry site)
        for b, req in enumerate(self._slots):
            if req is not None and req.expired(now):
                self._free_slot(b)
                self._resolve(req, EXPIRED, count="expired")

        # 2) reap deadline-expired queued requests (even with zero free
        #    slots — they must not count toward backpressure); their queue
        #    wait goes to the ledger so refused traffic stays visible
        expired = self.scheduler.expire(now)

        # 2b) graceful load shedding: after `window` consecutive over-high
        #     boundaries, shed lowest-class queued work down to the low
        #     watermark with a retry-after hint from the live drain rate
        if self._shed is not None:
            qsize = self.scheduler.qsize()
            target = self._shed.observe(qsize, self._resolved_total, now)
            if target is not None:
                hint = self._shed.retry_after(qsize)
                for req in self.scheduler.shed(target):
                    req.retry_after = hint
                    metrics.observe_queue_wait(
                        now - req.submit_t if req.submit_t else 0.0, "shed")
                    self._resolve(req, SHED, count="shed")

        # 2c) preemptive admission (priority mode): when an interactive
        #     request would miss its deadline waiting for capacity, evict
        #     the youngest lowest-class running slot — requeued through
        #     the PR 7 drain machinery (ORIGINAL submit_t/deadline kept,
        #     replay bitwise), so preemption costs the victim latency,
        #     never correctness
        if self.priority_mode:
            self._preempt_for_deadline(now)

        # 2d) inbound KV transfers (disaggregated serving): install up to
        #     the per-boundary page budget and seat fully-landed requests
        #     BEFORE admission, so a handed-off request (older by FCFS —
        #     it was admitted on the prefill worker already) takes a free
        #     slot ahead of fresh queue arrivals
        if self._transfers_in:
            self._pump_transfers(now)

        #    then admission into free slots at the boundary, FCFS or
        #    class-aware WFQ, page-aware: a candidate is admitted when
        #    PAGES suffice for its whole lifetime, not when a whole-Smax
        #    slot does
        free = [b for b, r in enumerate(self._slots) if r is None]
        admitted, admit_expired = self.scheduler.admit(
            len(free), now, fits=self._try_reserve)
        for req in expired + admit_expired:
            # already _finish(EXPIRED)ed by the scheduler; _resolve stores
            # the result, bumps the ledger and closes the trace
            metrics.observe_queue_wait(
                now - req.submit_t if req.submit_t else 0.0, "expired")
            self._resolve(req, EXPIRED, count="expired")
        for req, b in zip(admitted, free):
            self._admit(req, b)

        # 3) one iteration over all slots
        active = np.array([r is not None for r in self._slots])
        metrics.observe_boundary(self.scheduler.qsize(), int(active.sum()),
                                 self.num_slots)
        metrics.observe_pages(
            sum(p.pages_in_use for p in self._group_pools),
            sum(p.num_pages - 1 for p in self._group_pools))
        if active.any():
            self._iterate_paged()

        self._step_count += 1
        if self._ckpt is not None and self._snapshot_every > 0 \
                and self._step_count % self._snapshot_every == 0 \
                and any(r is not None for r in self._slots):
            self.save_snapshot()

        return self.scheduler.qsize() > 0 or \
            any(r is not None for r in self._slots) or \
            bool(self._transfers_in) or bool(self._outbound)

    def _record_mp_comm(self, B, T, t0, t1, reqs=()):
        """mp-rung observability per fused-step dispatch: the STATIC
        collective schedule of this dispatch shape is recorded into the
        training-shared ``profiler.mp_comm_counters()`` ledger (PR 3
        plumbing) and the serving ledger (wire bytes / collectives /
        fused dispatches), and every traced request on board gets a
        per-boundary ``mp_comm`` span carrying wire bytes + backend
        label (PR 9 tracing). Zero-cost at mp == 1."""
        if self.mp <= 1:
            return
        from ..distributed import tp_overlap as _tpov
        rec = self._mp_records.get((B, T))
        if rec is None:
            rec = _tpov.serving_step_record(self.config, self._mp_cfg, B, T)
            self._mp_records[(B, T)] = rec
        _tpov.record_step(rec)
        wire = rec.rs_bytes + rec.ag_bytes
        metrics.bump("mp_steps")
        metrics.bump("mp_collectives", rec.collectives)
        metrics.bump("mp_wire_bytes", wire)
        metrics.bump("mp_fused_dispatches", rec.fused_dispatches)
        for req in reqs:
            if req is not None and req.trace is not None:
                req.trace.span("mp_comm", t0, t1, bytes=wire,
                               backend=self._mp_cfg.backend, mp=self.mp)

    def _logical(self, pool, group=0):
        """A host copy of a pool array of ``group`` (or of pages of it) at
        the model's own row width, contiguous: what snapshots, page
        payloads and the chaos hooks see. The device holds whole lanes."""
        return self._geo.groups[group].logical(pool)

    def _table_arg(self, sl=slice(None)):
        """The step's table fields for the slots ``sl``, by the layout's
        names: a group's page table (a state group's is the slots'
        numbers). Host arrays: they ride the dispatch's one buffer."""
        return {f"table{i}": (pool.table if g.paged else self._slot_ids)[sl]
                for i, (g, pool) in enumerate(self._groups)}

    def _slot_fields(self, sl=slice(None)):
        """The fields of a dispatch over the slots ``sl`` that are the
        host's per-slot state as it stands: the tables, the sampling
        parameters, the keys, and the adapter ids where adapters are on."""
        fields = dict(self._table_arg(sl), do_sample=self._do_sample[sl],
                      temperature=self._temp[sl], top_p=self._top_p[sl],
                      key_data=self._keys[sl])
        if self.adapters is not None:
            fields["adapter_ids"] = self._aid[sl]
        return fields

    def _operands(self, b, t):
        """The layout of a ``[b, t]`` dispatch, the host buffer the engine
        keeps for it and the buffer's views, one a field. A buffer is
        filled again only after the dispatch that read it has its outputs
        on the host (every dispatch ends with that fetch; warm_up sends
        each buffer once), so an upload that aliases it is safe."""
        got = self._operand_bufs.get((b, t))
        if got is None:
            layout = StepLayout(
                b, t, tuple(pool.table.shape[1] if g.paged else 0
                            for g, pool in self._groups),
                self.adapters is not None)
            buf = np.zeros(layout.size, np.int32)
            got = self._operand_bufs[b, t] = (layout, buf, layout.views(buf))
        return got

    def _exe_name(self, b, t):
        """What the executable of a ``[b, t]`` dispatch runs under: the
        ``exe=`` of its feed, launch and wait spans."""
        return self._operands(b, t)[0].exe

    def _step_args(self, b, t, named=False, **fields):
        """What the fused step takes for a ``[b, t]`` dispatch over the
        first ``b`` slots, as ``(args, kwargs)``. ``fields`` are the slot
        operands by name (serving/operands.py); left out, the dispatch is
        idle, as warm_up sends it: no live lane (valid=0 sends every write
        to the trash page, emit=False parks the keys). ``named=True``
        gives the operands of ``step.named`` instead, one an array, for a
        reader of the step's jaxpr."""
        layout, buf, views = self._operands(b, t)
        if not fields:
            fields = dict.fromkeys(layout.fields, 0)
            fields.update(temperature=1.0, top_p=1.0)
        layout.pack(views, **fields)
        rest = self._kv_scale_args()
        if self.adapters is not None:
            rest += (self.adapters.device_slabs(),)
        if not named:
            return ((self.params, *self._pools, self._upload(buf), *rest),
                    {"layout": layout})
        *slot, aid = layout.unpack(jnp.asarray(buf))
        if aid is not None:
            rest = (*rest[:-1], aid, rest[-1])
        return (self.params, *self._pools, *slot, *rest), {}

    def _dispatch(self, b, t, **fields):
        """One dispatch of the fused step: the slot operands packed into
        the shape's buffer and sent as one array. The pools go back into
        the engine; returns the step's small outputs, one array still on
        the device (``_take`` fetches and splits it)."""
        args, kw = self._step_args(b, t, **fields)
        with self._clock.launch():
            out = self._paged_step(*args, **kw)
        self._pools = tuple(out[:-1])
        return out[-1]

    @staticmethod
    def _upload(a):
        """A host array sent with a paged dispatch, counted where it is
        sent (``paged_uploads``)."""
        metrics.bump("paged_uploads")
        return jnp.asarray(a)

    @staticmethod
    def _fetch(a):
        """An output of a paged dispatch brought to the host, counted
        where it is fetched (``paged_fetches``): the host waits here."""
        metrics.bump("paged_fetches")
        return np.asarray(a)

    def _copy_page(self, src, dst):
        """One physical page of the first group copied onto another (the
        CoW split): the other groups share no page."""
        n = len(self._geo.groups[0].names)
        self._pools = self._page_copy(self._pools[:n], jnp.int32(src),
                                      jnp.int32(dst)) + self._pools[n:]

    def _take(self, out, b):
        """The small outputs of a ``[b, t]`` dispatch, fetched as the one
        array they are and split on the host: (next tokens, keys, per-slot
        verdict or None, the model's statistics or None)."""
        return split_out(self._fetch(out), b, self._anomaly)

    def _record_stats(self, stats, kind):
        """Hands a dispatch's statistics (``kind`` chunk | decode) to the
        model, once the dispatch's outputs are on the host."""
        if stats is not None:
            self._model.record(stats, kind, self.config)

    def _kv_scale_args(self):
        """Per-page dequant scale operands of a quantized pool: host-
        authoritative like the page table, uploaded with every dispatch
        ([L, P] fp32 each, beside the slot operands' one buffer). Empty for
        a full-precision pool, so the unquantized dispatch signature is
        untouched."""
        if not self._kv_quant:
            return ()
        return (self._upload(self.pool.k_scale),
                self._upload(self.pool.v_scale))

    def _cow(self, b, start, end):
        """Copy-on-write guard: a slot may only WRITE pages it exclusively
        owns — split any shared page in [start, end) to a fresh physical
        page before the dispatch that writes the range."""
        copied = 0
        for src, dst in self.pool.make_writable(b, start, end):
            self._copy_page(src, dst)
            metrics.bump("cow_copies")
            copied += 1
        if copied:
            req = self._slots[b]
            if req is not None and req.trace is not None:
                req.trace.instant("cow_copy", pages=copied)

    def _iterate_paged(self):
        """One paged iteration (Sarathi-style interleave): the FCFS-oldest
        slot still consuming its prompt advances by ONE prefill chunk
        ([1, chunk] dispatch of the fused step), and every decode-ready
        slot emits one token ([B, 1] dispatch of the SAME fused step).
        Decode streams therefore advance at every boundary — a 1024-token
        admission costs each inter-token gap one chunk, never a monolithic
        prefill — and decode slots never pay for the chunk window. The two
        dispatch shapes ARE the steady-state executable set (the chunk
        ladder), trace-counter gated."""
        B = self.num_slots
        clk = self._clock
        t_boundary = clk.pause()            # chunks + CoW + decode: the
        prefilling = sorted(                # whole inter-token gap
            (b for b in range(B) if self._slots[b] is not None
             and self._chunk_off[b] < self._slots[b].prompt_len),
            key=lambda x: self._admit_seq[x])
        n_dec = sum(1 for b in range(B) if self._slots[b] is not None
                    and self._chunk_off[b] >= self._slots[b].prompt_len)

        if prefilling:
            # prefill budget scales with IDLE decode capacity (Sarathi's
            # principle): while the batch ramps up, several prompts chunk
            # per boundary; once half the slots decode, only one chunk
            # rides along, so the inter-token gap stays one-chunk-bounded.
            # A dedicated PREFILL worker has no decode streams to protect:
            # every prefilling slot advances each boundary.
            budget = (len(prefilling) if self.role == "prefill"
                      else max(1, B // 2 - n_dec))
            for b in prefilling[:budget]:
                self._prefill_chunk(b)

        decoding = [b for b in range(B) if self._slots[b] is not None
                    and self._chunk_off[b] >= self._slots[b].prompt_len]
        if not decoding:
            return
        if self._spec is not None:
            self._iterate_spec(decoding, t_boundary)
            return
        t0 = clk.feed("decode", "decode_time_s", self._exe_name(B, 1))
        # mid-prefill slots ride along inert: valid=0 routes their writes
        # to the trash page, emit=False parks their PRNG keys
        valid = np.zeros(B, np.int32)
        emit = np.zeros(B, bool)
        valid[decoding] = 1
        emit[decoding] = True
        for b in decoding:
            self._cow(b, int(self._pos[b]), int(self._pos[b]) + 1)
        self._decode_dispatches += 1     # per-role gate: prefill workers
        out = self._dispatch(            # must never reach this dispatch
            B, 1, ids=self._tok[:, None], start=self._pos, valid=valid,
            emit=emit, **self._slot_fields())
        clk.wait()
        nxt, keys, ok, stats = self._take(out, B)
        self._record_stats(stats, "decode")
        now = clk.emit()
        self._keys = np.array(keys)
        self._record_mp_comm(B, 1, t0, now,
                             [self._slots[b] for b in decoding])
        self._count_paged_step(self._do_sample & emit)
        # what the attention read of this dispatch visits of its table, by
        # the host's copy of the operands (a speculative verify makes the
        # same read k+1 times a window and is not counted)
        table_pages = self.pool.table.size
        metrics.bump("decode_pages_table", table_pages)
        metrics.bump("decode_pages_swept",
                     int((self._pos // self.page_size + 1).sum())
                     if self._paged_kernel else table_pages)
        self._model.observe("decode", valid)
        for b in decoding:
            req = self._slots[b]
            if ok is not None and not ok[b]:
                self._quarantine(req, b)
                continue
            if req.trace is not None:
                # the span covers the whole boundary (chunks + CoW + the
                # fused dispatch): that IS this stream's inter-token gap
                req.trace.span("decode_step", t_boundary, now,
                               pos=int(self._pos[b]))
            self._pos[b] += 1
            self._emit_token(req, b, int(nxt[b]), first=False)

    @staticmethod
    def _count_paged_step(sample_mask):
        """Ledger of one paged dispatch (decode, chunk or verify), and of
        whether its sampling tail ran: the executable draws only when a
        row that emits also samples (generation._next_token's
        sample_mask), and the host holds the operands it uploaded, so it
        counts without a sync."""
        metrics.bump("paged_steps")
        if np.any(sample_mask):
            metrics.bump("sampled_steps")

    def warm_up(self):
        """Compile the steady-state executables now: every rung of the
        chunk ladder, the [B, 1] decode step and the page copy, each
        dispatched once with no live lane (valid=0 sends every write to
        the trash page, emit=False parks the keys), so that no request
        meets a compile. Changes no state; returns the engine."""
        if self._spec is not None:
            raise ValueError("warm_up covers the paged fused step (no "
                             "speculative dispatch)")
        for b, t in [(1, c) for c in self._chunk_ladder] \
                + [(self.num_slots, 1)]:
            self._dispatch(b, t)
        self._copy_page(0, 0)
        jax.block_until_ready(self._pools)
        return self

    def _build_draft_params(self):
        """(Re)derive the draft params from the SERVED weights — at
        construction and after every ``swap_params`` — so the draft always
        proposes against the live version (``_draft_params_version``, the
        snapshot's audit stamp, records which). Source "quant": the PR 14
        int8 self-draft — on an engine already serving quantized weights
        the served tree IS the draft (degenerate self-draft, 100% greedy
        agreement); on a bf16 engine the served tree is quantized fresh.
        Source "shallow": the first ``draft_layers`` transformer blocks
        of the served tree (embeddings/LN/head shared, zero copies)."""
        if self._spec.source == "quant":
            if self._quant is not None and self._quant.quantizes_weights:
                self._draft_params = self.params
            else:
                self._draft_params = _squant.quantize_params(
                    self.params, self.config,
                    _squant.QuantSpec(weight_dtype="int8"))
        else:
            self._draft_params = _squant.shallow_draft_params(
                self.params,
                self._spec.num_layers(self.config.num_layers))
        self._draft_params_version = self.params_version

    def _iterate_spec(self, decoding, t_boundary):
        """Speculative decode boundary (FLAGS_serving_speculate_k > 0):
        the draft rolls every decode-ready slot up to k tokens ahead of
        its last emitted token (sidecar KV — the shared pool is never
        written), then ONE fused verify dispatch scores all slots at
        [B, k+1] under the SERVED weights, accepts per slot, and rewinds
        every KV byte written past an accepted length. Per-slot
        nprop/emit/sampling params are traced operands — the chunk-ladder
        trick — so mixed speculative/plain/greedy/sampled traffic shares
        this one executable: a slot with nprop=0 (``speculate="off"``, or
        one token remaining) IS plain decode inside the same dispatch,
        and a spec engine never dispatches the [B, 1] plain-decode shape.
        Emitted token streams are bitwise the plain engine's (greedy) and
        replay ``generate_from_params`` exactly (sampled): the verify key
        splits once per EMITTED token only."""
        B = self.num_slots
        k = self._spec.k
        nprop = np.zeros(B, np.int32)
        valid = np.zeros(B, np.int32)
        emit = np.zeros(B, bool)
        for b in decoding:
            req = self._slots[b]
            remaining = req.max_new_tokens - len(req.tokens)
            if req.speculate != "off":
                # the window's last lane must stay a real (non-proposed)
                # emission so LENGTH fires exactly at max_new_tokens
                nprop[b] = min(k, max(0, remaining - 1))
            valid[b] = nprop[b] + 1
            emit[b] = True
        ids = np.zeros((B, k + 1), np.int32)
        ids[:, 0] = self._tok                 # lane 0: last emitted token
        clk = self._clock
        if int(nprop.max()) > 0:
            clk.feed("draft", "decode_time_s", self._draft_exe)
            args = (self._draft_params, self._kc, self._vc,
                    self._upload(self._tok), self._upload(self._pos),
                    self._upload(self.pool.table), *self._kv_scale_args())
            with clk.launch():
                props = self._spec_draft(*args)
            clk.wait()
            ids[:, 1:] = self._fetch(props)
            metrics.bump("draft_dispatches")
        clk.feed("verify", "decode_time_s", self._verify_exe)
        for b in decoding:
            self._cow(b, int(self._pos[b]),
                      int(self._pos[b]) + int(valid[b]))
        # per-role gate: prefill workers must never reach this dispatch
        self._decode_dispatches += 1
        args = (
            self.params, self._kc, self._vc, self._upload(ids),
            self._upload(self._pos), self._upload(valid), self._upload(emit),
            self._upload(self.pool.table), self._upload(nprop),
            self._upload(self._do_sample), self._upload(self._temp),
            self._upload(self._top_p), self._upload(self._keys),
            *self._kv_scale_args())
        with clk.launch():
            out = self._spec_verify(*args)
        clk.wait()
        if self._anomaly:
            self._kc, self._vc, toks, n_emit, keys, ok = out
            ok = self._fetch(ok)
        else:
            self._kc, self._vc, toks, n_emit, keys = out
            ok = None
        toks = self._fetch(toks)
        n_emit = self._fetch(n_emit)
        now = clk.emit()
        self._keys = np.array(self._fetch(keys))
        self._count_paged_step(self._do_sample & emit)
        metrics.bump("verify_dispatches")
        for b in decoding:
            req = self._slots[b]
            if ok is not None and not ok[b]:
                self._quarantine(req, b)
                continue
            # a stop token cuts the window mid-run: the tail of the
            # accepted run is dropped (freed pages only), so the emission
            # count is known BEFORE emitting — which is what lets the
            # span land before the final token's emission delivers the
            # request and archives its trace
            n = int(n_emit[b])
            stops = req.stop_token_ids or ()
            plan = next((j + 1 for j in range(n)
                         if int(toks[b, j]) in stops), n)
            accepted = max(0, plan - 1)      # lane 0 is never speculative
            metrics.bump("spec_proposed", int(nprop[b]))
            metrics.bump("spec_accepted", accepted)
            metrics.bump("spec_tokens_out", plan)
            if req.trace is not None:
                # reconciles with the emitted-token ledger: sum(emitted)
                # over a request's speculate spans == len(result.tokens)-1
                # (the first token comes from the prefill chunk)
                req.trace.span("speculate", t_boundary, now,
                               proposed=int(nprop[b]), accepted=accepted,
                               emitted=plan)
            for j in range(plan):
                if self._slots[b] is not req:
                    break                    # safety net; plan already
                self._pos[b] += 1            # accounts for the stop cut
                self._emit_token(req, b, int(toks[b, j]), first=False)

    def _prefill_chunk(self, b):
        """Advance slot b's prefill by one chunk ([1, rung] dispatch of
        the fused step); the final chunk emits the request's first token."""
        clk = self._clock
        req = self._slots[b]
        plen = req.prompt_len
        off = int(self._chunk_off[b])
        remaining = plen - off
        # largest ladder rung <= the page-rounded remainder: bulk prefill
        # uses the big rung, the tail steps down so the final chunk's
        # padding stays < page_size
        target = min(-(-remaining // self.page_size) * self.page_size,
                     self._chunk_ladder[-1])
        C = max(c for c in self._chunk_ladder if c <= target)
        # the feed opens once the rung names its executable
        t0 = clk.feed("chunk", "prefill_time_s", self._exe_name(1, C))
        v = min(C, remaining)
        last = off + v >= plen                # final chunk emits token #1
        # a PREFILL worker never emits: its final chunk dispatches with
        # emit=False, so the slot's PRNG key PARKS exactly as it does for
        # every non-final chunk — the decode worker re-derives the stream
        # from the request seed and makes the FIRST split itself, which is
        # what keeps the handoff bitwise-identical to a single engine
        emit = last and self.role != "prefill"
        self._chunk_rungs.add(C)              # per-role rung gate
        ids = np.zeros((1, C), np.int32)
        ids[0, :v] = req.prompt[off:off + v]
        self._cow(b, off, off + v)
        out = self._dispatch(1, C, ids=ids, start=off, valid=v, emit=emit,
                             **self._slot_fields(slice(b, b + 1)))
        clk.wait()
        # the chunk step ends when its outputs are on the host: their one
        # fetch is where the host waits for the device. The anomaly
        # verdict comes with them and is only consulted on the emitting
        # (final) chunk
        nxt, keys, ok_dev, stats = self._take(out, 1)
        self._record_stats(stats, "chunk")
        t1 = clk.emit()
        self._keys[b] = keys[0]
        self._record_mp_comm(1, C, t0, t1, [req])
        self._count_paged_step(emit and self._do_sample[b])
        metrics.bump("chunk_steps")
        metrics.bump("prefill_chunks")
        self._model.observe("chunk", np.array([v]))
        if req.trace is not None:
            req.trace.span("prefill_chunk", t0, t1, offset=off, tokens=v,
                           chunk=C)
        if last:
            self._chunk_off[b] = plen
            self._pos[b] = plen               # next decode writes here
            # only the final chunk is padded: waste < chunk per request
            metrics.observe_prefill_waste(C - v)
            ok = True if ok_dev is None else bool(ok_dev[0])
            if not ok:
                # poisoned already at first-token time (bad weights or a
                # corrupted prompt page): quarantine before anything is
                # emitted or published
                self._quarantine(req, b)
                return
            if self.role == "prefill":
                # the prompt KV is complete: stream the remaining pages,
                # close the transfer and free the slot for the next
                # prompt — the assigned decode worker emits token #1
                self._finish_handoff(b)
                return
            self._emit_token(req, b, int(nxt[0]), first=True)
        else:
            self._chunk_off[b] = off + v
            if self.role == "prefill":
                # pages the chunk boundary just passed are FINAL (KV of a
                # token depends only on its prefix) — stream them now so
                # the transfer overlaps the rest of the prefill
                tr = self._outbound.get(req.request_id)
                if tr is not None:
                    self._stream_pages(b, tr)

    # -- KV-page streaming (disaggregated prefill/decode) --------------------
    def _stream_pages(self, b, tr, final=False):
        """Haul slot b's FINAL pages to the host and append them to the
        outbound transfer: everything the chunk boundary has passed (a
        token's KV depends only on its prefix, so a fully-written page
        never changes again), or all ``total_pages`` when ``final``."""
        complete = (tr.total_pages if final
                    else int(self._chunk_off[b]) // self.page_size)
        while len(tr.pages) < complete:
            li = len(tr.pages)
            phys = int(self.pool.table[b, li])
            kpage, vpage = self._page_read(self._kc, self._vc,
                                           jnp.int32(phys))
            ks = vs = None
            if self._kv_quant:
                ks = self.pool.k_scale[:, phys].copy()
                vs = self.pool.v_scale[:, phys].copy()
            payload = PagePayload(li, self._logical(jax.device_get(kpage)),
                                  self._logical(jax.device_get(vpage)),
                                  ks, vs)
            if self._kv_crc:
                payload.stamp()
            tr.append(payload)

    def _finish_handoff(self, b):
        """Prefill complete on a PREFILL worker: stream the remaining
        pages, close the transfer and free the slot — the request stays
        RUNNING (slot None) while the supervisor routes its pages to a
        decode worker, which emits token #1."""
        req = self._slots[b]
        tr = self._outbound[req.request_id]
        self._stream_pages(b, tr, final=True)
        tr.finish()
        if req.trace is not None:
            req.trace.instant("handoff", pages=tr.total_pages,
                              bytes=tr.bytes_total)
        metrics.bump("prefill_handoffs")
        # frees pages AND publishes the prompt to this worker's prefix
        # cache (chunk_off == plen) — the next shared-prefix prompt routed
        # here streams its covered pages without recompute
        self._free_slot(b)
        req.slot = None

    def offer_transfer(self, tr):
        """Hand an inbound KV transfer to this (decode-capable) engine:
        pages install between decode boundaries and the request seats in
        a free slot once all pages landed. Re-offering a transfer already
        in flight (a supervisor retry) restarts its install cleanly."""
        self._refuse("kv_transfer", True)
        if self.role == "prefill":
            raise ValueError(f"engine {self.tag!r} is a prefill worker; "
                             f"offer transfers to a decode-capable engine")
        if tr.page_size != self.page_size \
                or tr.kv_dtype != self.pool.kv_dtype:
            raise ValueError(
                f"transfer geometry (page_size={tr.page_size}, "
                f"kv_dtype={tr.kv_dtype!r}) does not match this engine "
                f"(page_size={self.page_size}, "
                f"kv_dtype={self.pool.kv_dtype!r})")
        rid = tr.request_id
        if rid in self._install_progress:
            self.pool.release_staged(rid)
            self._transfers_in = [t for t in self._transfers_in
                                  if t.request_id != rid]
        if self._page_write is None:
            donate_ok = jax.default_backend() != "cpu"
            self._page_write = _make_page_write((0, 1) if donate_ok else ())
        self._transfers_in.append(tr)
        self._install_progress[rid] = 0
        return tr

    def has_transfer(self, rid):
        """Is a transfer for request ``rid`` currently installing here?"""
        return rid in self._install_progress

    def _maybe_kv_bitflip(self):
        """Chaos hook body (``FaultPlan.kv_bitflip_at``): flip scheduled
        bits in the live K cache via a host round-trip. A mantissa flip
        stays FINITE — exactly the corruption class the all-finite guard
        is blind to and the sampled shadow audit exists for."""
        flips = _fi.maybe_kv_bitflip(self.tag, self._step_count)
        if not flips or self._kc is None:
            return
        host = self._logical(jax.device_get(self._kc)).copy()
        for page, layer, bit in flips:
            view = host[int(layer) % host.shape[0], int(page) % host.shape[1]]
            flat = view.view(np.uint8).reshape(-1)
            byte, off = divmod(int(bit), 8)
            flat[byte] ^= np.uint8(1 << off)
        self._kc = jax.device_put(pad_lanes(host, self._kc),
                                  self._kc.sharding)

    def _install_page(self, payload, dst):
        """Write one page payload into physical page ``dst`` (ONE traced
        executable for every page of every transfer)."""
        kpage = jnp.asarray(payload.k, self._kc.dtype)
        vpage = jnp.asarray(payload.v, self._vc.dtype)
        self._kc, self._vc = self._page_write(self._kc, self._vc,
                                              kpage, vpage, jnp.int32(dst))
        if self._kv_quant:
            self.pool.k_scale[:, dst] = payload.k_scale
            self.pool.v_scale[:, dst] = payload.v_scale
        metrics.bump("transfer_installs")

    def _pump_transfers(self, now):
        """Advance inbound transfers at a decode boundary, T3-style: at
        most ``FLAGS_serving_transfer_pages_per_boundary`` page installs
        ride this boundary (the copies hide behind the batch's decode
        compute — decoding slots never stall on a transfer), then any
        fully-landed transfer seats its request in a free slot."""
        budget = self._transfer_budget
        keep = []
        for tr in self._transfers_in:
            rid = tr.request_id
            req = tr.request
            if tr.aborted or tr.failed or req.state == FINISHED:
                # handled elsewhere (cancel / supervisor abort): return
                # the staged pages, forget the stream
                self.pool.release_staged(rid)
                self._install_progress.pop(rid, None)
                continue
            if req.expired(now):
                self.pool.release_staged(rid)
                self._install_progress.pop(rid, None)
                tr.aborted = True
                self._resolve(req, EXPIRED, count="expired")
                continue
            installed = self._install_progress[rid]
            refused = False
            while budget > 0 and installed < len(tr.pages):
                dst = self.pool.stage(rid, 1)
                if dst is None:
                    break                  # page pressure: retry next boundary
                payload = _fi.maybe_corrupt_kv_payload(tr.pages[installed])
                if payload.crc is not None:
                    from ..distributed import integrity as _integrity
                    from .kv_transfer import KVIntegrityError
                    _integrity._count("crc_checks")
                    try:
                        payload.verify()
                    except KVIntegrityError:
                        # typed refusal: corrupt bytes never reach the
                        # pool. Drop the whole inbound stream — the
                        # supervisor sees has_transfer() go False and
                        # re-offers the RETAINED (still clean) payloads
                        _integrity._count("crc_refusals")
                        metrics.bump("transfer_crc_refusals")
                        self.pool.release_staged(rid)
                        self._install_progress.pop(rid, None)
                        refused = True
                        break
                self._install_page(payload, dst[0])
                installed += 1
                budget -= 1
            if refused:
                continue
            self._install_progress[rid] = installed
            if tr.done and installed == tr.total_pages \
                    and self._seat_transfer(tr, now):
                self._install_progress.pop(rid, None)
                continue
            keep.append(tr)
        self._transfers_in = keep

    def _seat_transfer(self, tr, now):
        """All pages landed: adopt them into a free slot and resume the
        request EXACTLY as a single engine resumes an exact-prefix-cache
        hit — ``chunk_off = plen - 1`` re-forwards the last prompt token
        (into exclusively-owned pages: no CoW) and the fresh per-request
        threefry key makes its FIRST split on the emitting chunk, so the
        token stream is bitwise the single-engine stream. Returns True
        when the transfer is terminal (seated or failed), False to retry
        at the next boundary (no slot / no tail pages yet)."""
        req = tr.request
        rid = tr.request_id
        b = next((i for i, r in enumerate(self._slots) if r is None), None)
        if b is None:
            return False
        if req.params_version is not None \
                and req.params_version != self.params_version:
            # the prompt KV was computed under different weights than this
            # engine serves — seating it would mix versions mid-stream.
            # Surface it to the supervisor for a single-version replay.
            tr.failed = True
            self.pool.release_staged(rid)
            return True
        plen = req.prompt_len
        extra = pages_for(plen + req.max_new_tokens,
                          self.page_size) - tr.total_pages
        tail = []
        if extra > 0:
            tail = self.pool.try_alloc(extra)
            if tail is None:
                return False               # page pressure: retry later
        pages = self.pool.adopt_staged(rid)
        # seated, not admitted (the prefill worker counted the admission):
        # the slot's prefill span runs from here to its first token
        t_seat = self._admit_t[b] = time.perf_counter()
        self._trace_queue_span(req, b, t_seat)
        self.pool.map_slot(b, pages + tail, None)
        req.slot = b
        self._slots[b] = req
        self._chunk_off[b] = plen - 1      # re-forward the last prompt token
        self._admit_count += 1
        self._admit_seq[b] = self._admit_count
        self._pos[b] = 0
        self._tok[b] = 0
        self._keys[b] = np.asarray(
            jax.random.key_data(jax.random.key(req.seed)))
        self._do_sample[b] = bool(req.do_sample)
        self._temp[b] = float(req.temperature)
        self._top_p[b] = 1.0 if req.top_p is None else float(req.top_p)
        tr.seated = True
        metrics.bump("transfers")
        metrics.bump("transfer_pages", tr.total_pages)
        metrics.bump("transfer_bytes", tr.bytes_total)
        metrics.add_time("transfer_time_s", now - tr.t_open)
        if req.trace is not None:
            # the transfer span covers open (prefill admission on the
            # source worker) to seat — TTFT = queue + transfer + the final
            # chunk's boundary, reconciling on the request's own timeline
            req.trace.span("transfer", tr.t_open, now,
                           bytes=tr.bytes_total, pages=tr.total_pages,
                           dtype=tr.kv_dtype, src=tr.src_tag)
        return True

    def _emit_token(self, req, b, tok, first):
        # a requeued/replayed request keeps its original first_token_t (the
        # user already saw a token) — only a genuinely-first emission may
        # contribute a TTFT sample, or every recovery round trip would
        # duplicate its entry in the histogram
        fresh_first = req.first_token_t is None
        req._emit(tok)
        metrics.bump("tokens_out")
        self._tok[b] = tok
        if first and fresh_first:
            metrics.observe_ttft(req.first_token_t - req.submit_t,
                                 priority=req.priority)
            self._clock.add("prefill_span_s",
                            req.first_token_t - self._admit_t[b])
            self._clock.add("first_tokens", 1)
            if req.trace is not None:
                # the exact timestamp the TTFT sample uses — the exported
                # trace reconciles with the ledger to the float
                req.trace.instant("first_token", req.first_token_t)
        if req.stop_token_ids and tok in req.stop_token_ids:
            self._free_slot(b)
            self._resolve(req, STOP)
        elif len(req.tokens) >= req.max_new_tokens:
            self._free_slot(b)
            self._resolve(req, LENGTH)

    # -- preemptive admission (priority mode) --------------------------------
    def _preempt_margin(self, now=None):
        """Slack under which a queued deadline counts as at-risk: the flag
        when set, else 2x the ledger's recent TTFT p50 (what admission
        actually costs right now), floor 50ms."""
        if self._preempt_margin_s > 0:
            return self._preempt_margin_s
        p50 = metrics.recent_ttft_p50()
        return max(0.05, 2.0 * p50) if p50 is not None else 0.05

    def _capacity_for(self, req):
        """Could ``req`` be admitted right now without preempting? Exact:
        the check runs the real reservation as a side-effect-free probe
        (pages allocated then immediately released, no ledger/plan
        writes)."""
        if not any(r is None for r in self._slots):
            return False
        return self._try_reserve(req, probe=True)

    def _preempt_slot(self, than_rank):
        """Victim slot for a class-``than_rank`` preemption: a RUNNING
        request of strictly worse class; worst class first, youngest
        admission first within it (the least sunk work is thrown away).
        None when every running slot is same-or-better class."""
        best = None
        for b, req in enumerate(self._slots):
            if req is None or req.class_rank <= than_rank:
                continue
            key = (req.class_rank, int(self._admit_seq[b]))
            if best is None or key > best[0]:
                best = (key, b)
        return None if best is None else best[1]

    def _preempt_for_deadline(self, now):
        """Evict lower-class running slots until the most at-risk queued
        request (slack within the preempt margin) has capacity, then seat
        it DIRECTLY: the regular admission order (class + WFQ tenant
        rotation) is deadline-blind, so leaving the freed slot to
        ``Scheduler.admit`` could hand it to a different request and the
        eviction would have been for nothing. Victims requeue at their
        ORIGINAL arrival — the PR 7 machinery — and their replay is
        bitwise, so this trades best-effort latency for the deadline.
        Bounded by the slot count per boundary."""
        margin = None
        for _ in range(self.num_slots):
            if margin is None:
                margin = self._preempt_margin(now)
            risk = self.scheduler.deadline_risk(now, margin)
            if risk is None:
                return
            if self.adapters is not None \
                    and not self.adapters.resident(risk.adapter or 0):
                # evicting running slots cannot make a non-resident
                # adapter appear — preemption would burn a victim for
                # nothing; the request waits for load_adapter instead
                return
            if not self._capacity_for(risk):
                b = self._preempt_slot(risk.class_rank)
                if b is None:
                    return
                victim = self._slots[b]
                self._free_slot(b)
                victim._requeue()
                self.scheduler.requeue(victim)
                metrics.bump("preempted")
                if not self._capacity_for(risk):
                    continue          # free more slots/pages for it
            if not self.scheduler.cancel(risk):
                return                # resolved concurrently: nothing owed
            if not self._try_reserve(risk):
                # pages raced away between probe and reserve: restore the
                # queue entry at its arrival position, retry next boundary
                self.scheduler.requeue(risk)
                return
            free_b = next(b for b, r in enumerate(self._slots) if r is None)
            self._admit(risk, free_b)

    def _prefix_salt(self, req, version=None):
        """Prefix-cache key salt for ``req``. Base traffic (adapter id 0,
        or an adapter-less engine) gets b"" — base-model prompt pages are
        keyed by tokens alone and stay shared across every tenant AND
        across adapter load/evict/swap. Adapted requests get their
        (adapter id, content version): the adapted out/up/down projections
        feed the residual stream the NEXT layer's K/V is computed from, so
        a prompt page prefilled under one set of delta bits is only
        bitwise-reusable under those SAME bits. Versioned keys are what
        makes ``swap_adapter`` flush-free — the old version's entries just
        become unreachable and age out of the LRU."""
        if self.adapters is None:
            return b""
        aid = int(req.adapter or 0)
        if aid == 0:
            return b""
        if version is None:
            version = self.adapters.version(aid)
        return b"a%d:%d|" % (aid, int(version))

    def _try_reserve(self, req, probe=False):
        """Page-aware admission predicate (the scheduler's ``fits``): pin
        the longest cached prompt prefix, then allocate every page the
        request can touch over its WHOLE lifetime (prompt + max_new_tokens,
        plus a copy-on-write spare when sharing overlaps the write range).
        Returns False — pool untouched — when pages don't suffice yet; the
        head then waits for running requests to release pages (strict
        FCFS, no starvation). A request bound to a NON-RESIDENT adapter
        never fits — admission blocks (strict in-order: the scheduler
        stops at the first non-fitting head) until ``load_adapter`` makes
        the id resident; pages are untouched."""
        if self.adapters is not None \
                and not self.adapters.resident(req.adapter or 0):
            if not probe:
                metrics.bump("adapter_admit_blocked")
            return False
        pool = self.pool
        ps = self.page_size
        plen = req.prompt_len
        # a PREFILL worker computes (and ships) only the PROMPT's pages —
        # the decode worker reserves the generation tail when it seats the
        # transfer, so prefill admission never holds decode capacity
        total = pages_for(
            plen + (0 if self.role == "prefill" else req.max_new_tokens),
            ps)
        m, shared, exact = pool.lookup(req.prompt,
                                       salt=self._prefix_salt(req))
        # at least the last prompt token must be (re-)forwarded so the
        # first emitted token has logits — even on an exact-prompt hit
        chunk_start = min(m, plen - 1)
        n_shared = len(shared)
        pool.incref(shared)       # pin before eviction can drop the entries
        # CoW spare: needed only when a shared page overlaps this
        # request's write range (an exact-prompt hit sharing the partial
        # last page) — prefix registration happens on slot RELEASE, so a
        # request never CoWs against its own registration
        spare_needed = n_shared > 0 and n_shared - 1 >= chunk_start // ps
        need = (total - n_shared) + (1 if spare_needed else 0)
        # every further group holds the request too, or none does: a
        # window group's lifetime is capped at its ring, whatever the
        # context (nothing is shared there, so nothing is looked up)
        others = [(g, min(total, g.slot_pages))
                  for g in self._group_pools[1:]]
        if probe:
            # capacity question only (preemption policy): answered without
            # allocating — pool.try_alloc would EVICT cache entries to
            # satisfy a transient probe, churning the very prefix pages
            # (possibly this request's own) the reservation depends on
            ok = pool.can_alloc(need) and all(g.can_alloc(n)
                                              for g, n in others)
            pool.decref(shared)
            return ok
        got = pool.try_alloc(need)
        got_others = []
        if got is not None:
            for g, n in others:
                pages = g.try_alloc(n)
                if pages is None:
                    break
                got_others.append(pages)
        if got is None or len(got_others) < len(others):
            # whichever group is short, the request waits with nothing held
            pool.decref(shared + (got or []))
            for (g, _), pages in zip(others, got_others):
                g.decref(pages)
            return False
        spare = got.pop() if spare_needed else None
        req._page_plan = (chunk_start, shared, got, spare, got_others)
        # ledger per successful ADMISSION (fits may poll a waiting head
        # many times; that must not dilute the hit rate)
        if pool.prefix_cache_enabled:
            metrics.bump("prefix_lookups")
        if n_shared:
            metrics.bump("prefix_hits")
            metrics.bump("prefix_tokens_reused", chunk_start)
            if req.trace is not None:
                req.trace.instant("prefix_hit", tokens=chunk_start,
                                  pages=n_shared)
        return True

    def _admit(self, req, b):
        """Bind slot b to the request's page plan (reserved by
        _try_reserve): cached prefix pages map logical 0..n_shared-1, fresh
        pages cover the rest of prompt + max_new_tokens. No forward pass
        happens here — the prompt prefills chunk-by-chunk inside the fused
        step, interleaved with every other slot's decode."""
        chunk_start, shared, private, spare, others = req._page_plan
        del req._page_plan
        self._observe_admission(req, b)
        self.pool.map_slot(b, list(shared) + list(private), spare)
        for g, pages in zip(self._group_pools[1:], others):
            g.map_slot(b, pages)
        self._count_mapped_pages(req, b)
        self._count_bound_cache(req, b)
        req.state = RUNNING
        req.slot = b
        req.params_version = self.params_version
        if self.adapters is not None:
            aid = int(req.adapter or 0)
            self._aid[b] = aid
            # the adapter analogue of params_version: which delta bits
            # produced this request's tokens (rides snapshots + results)
            req.adapter_version = self.adapters.version(aid)
            if req.trace is not None:
                req.trace.instant("adapter", adapter_id=aid,
                                  adapter_version=req.adapter_version)
        self._slots[b] = req
        self._chunk_off[b] = chunk_start
        self._admit_count += 1
        self._admit_seq[b] = self._admit_count
        self._pos[b] = 0
        self._tok[b] = 0
        self._keys[b] = np.asarray(
            jax.random.key_data(jax.random.key(req.seed)))
        self._do_sample[b] = bool(req.do_sample)
        self._temp[b] = float(req.temperature)
        self._top_p[b] = 1.0 if req.top_p is None else float(req.top_p)
        metrics.bump("admitted")
        if self.role == "prefill":
            # open the request's KV stream; pages a cached prefix already
            # covers (logical 0 .. chunk_start//ps - 1) are final right
            # now and stream before the first chunk even runs — the
            # prefix-affinity payoff on the prefill side
            tr = KVTransfer(req, self.page_size, self.pool.kv_dtype,
                            self.tag)
            self._outbound[req.request_id] = tr
            self._fresh_outbound.append(tr)
            self._stream_pages(b, tr)

    def _count_mapped_pages(self, req, b):
        """Where a group of layers attends to a window: the pages this
        admission mapped in the groups with no window and in those with
        one, and what the latter would have mapped with no cap, each times
        its group's layers. From the host's tables; no sync."""
        if all(g.window is None for g in self._geo.groups):
            return
        lifetime = pages_for(req.prompt_len + req.max_new_tokens,
                             self.page_size)
        for g, pool in zip(self._geo.paged, self._group_pools):
            mapped = g.layers * int(np.count_nonzero(pool.table[b]))
            if g.window is None:
                metrics.bump("kv_pages_mapped_full", mapped)
            else:
                metrics.bump("kv_pages_mapped_window", mapped)
                metrics.bump("kv_pages_unwindowed", g.layers * lifetime)

    def _count_bound_cache(self, req, b):
        """Where a group of layers keeps a state and no pages: the bytes
        this admission binds (the pages mapped, times their group's layers
        and a page's bytes at the model's own row width, and the state
        groups' rows of one slot) and what the same lifetime would have
        mapped had the state layers kept rows a token as the first group's
        do. From the host's tables; no sync."""
        states = [g for g in self._geo.groups if not g.paged]
        if not states:
            return
        size = self._pools[0].dtype.itemsize
        page_bytes = [g.row_bytes(size) * self.page_size
                      for g in self._geo.paged]
        mapped = sum(
            g.layers * int(np.count_nonzero(pool.table[b])) * nbytes
            for g, pool, nbytes in zip(self._geo.paged, self._group_pools,
                                       page_bytes))
        lifetime = pages_for(req.prompt_len + req.max_new_tokens,
                             self.page_size)
        metrics.bump("state_slots_bound")
        metrics.bump("cache_bytes_bound", mapped + sum(
            g.layers * g.row_bytes(jnp.dtype(g.dtype).itemsize if g.dtype
                                   else size) for g in states))
        # the layers that keep no pages (a layer may keep several states)
        state_layers = self.config.num_layers - sum(
            g.layers for g in self._geo.paged)
        metrics.bump("cache_bytes_all_paged", mapped
                     + state_layers * lifetime * page_bytes[0])

    def _quarantine(self, req, b):
        """Anomaly-guard resolution (``FLAGS_serving_anomaly_policy=
        quarantine``): the fused step's per-slot all-finite check flagged
        this slot's logits — a NaN/Inf from bad weights, a corrupted KV
        page or a flaky chip. The token is NOT emitted (it would be
        garbage), the slot is freed WITHOUT publishing its prompt pages
        to the prefix cache (poisoned KV must never be reused), and the
        request resolves ``finish_reason="error"`` at this boundary.
        Neighbors are bitwise-stable — batch rows never interact — and
        the freed slot/pages are re-written before any future read, so
        neither the shared batch nor the next snapshot carries the
        poison forward."""
        pos = int(self._pos[b])
        self._free_slot(b, register=False)
        if req.trace is not None:
            req.trace.instant("anomaly", pos=pos)
        self._resolve(req, ERROR, count="anomalies_quarantined")

    def _free_slot(self, b, register=True):
        req = self._slots[b]
        if self.role == "prefill" and req is not None:
            # a prefill slot freed before its transfer completed (cancel /
            # expiry / quarantine / drain) aborts the stream — the normal
            # resolution path owns the request, the supervisor must not
            # replay it off a half-dead transfer
            tr = self._outbound.pop(req.request_id, None)
            if tr is not None and not tr.done:
                tr.aborted = True
        if req is not None and register \
                and int(self._chunk_off[b]) >= req.prompt_len:
            # publish the prompt's pages for prefix reuse ON RELEASE
            # (vLLM-style cache-on-free): the slot never decodes into a
            # cache-pinned page, so registration costs zero CoW splits.
            # Generated-token KV beyond the prompt in the partial last
            # page is harmless — a consumer always CoW-copies that page
            # before its first write, and never unmasks a position it has
            # not itself written.
            # salt with the version STAMPED at admission (a bound adapter
            # cannot be mutated, but the stamped value is the truth of
            # which bits produced these pages)
            self.pool.register(
                req.prompt, b,
                salt=self._prefix_salt(req, version=req.adapter_version))
        self._slots[b] = None
        self._pos[b] = 0
        self._tok[b] = 0
        self._chunk_off[b] = 0
        # reset the sampling state too: a recycled slot must not carry its
        # predecessor's temp/top_p/do_sample/PRNG key — stale values made
        # slot-state debug dumps lie, and (worse) an admission that forgot
        # to overwrite one of these would silently couple the new
        # occupant's stream to the previous one's
        self._keys[b] = 0
        self._temp[b] = 1.0
        self._top_p[b] = 1.0
        self._do_sample[b] = False
        if self.adapters is not None and req is not None and req.tokens:
            # per-adapter token share (base id 0 included): the fairness
            # gauge the WFQ-across-adapters policy is audited against
            metrics.observe_adapter_tokens(int(self._aid[b]),
                                           len(req.tokens))
        self._aid[b] = 0
        for pool in self._group_pools:
            pool.release_slot(b)

    def _observe_admission(self, req, b):
        """Admission into slot b, one instant for all it feeds: the
        ``admit_queue_wait_s`` sample (now - ``submit_t``, flag or no flag;
        a requeued request is admitted, and counted, again, from its
        original arrival), the start of the slot's ``prefill_span_s``, and
        the end of the traced request's queue-wait span."""
        now = time.perf_counter()
        self._admit_t[b] = now
        self._clock.add("admit_queue_wait_s",
                        max(0.0, now - req.submit_t) if req.submit_t else 0.0)
        self._clock.add("admit_queue_waits", 1)
        self._trace_queue_span(req, b, now)

    def _trace_queue_span(self, req, b, now):
        """Admission closes the request's queue-wait span: from arrival
        (``submit_t`` — the exact float the TTFT/latency ledger uses) or,
        after a requeue/restore hop, from the last recorded span, to now."""
        if req.trace is None:
            return
        tail = req.trace.tail()
        t0 = req.submit_t if tail is None else max(tail, req.submit_t)
        req.trace.span("queue", t0, now, slot=b)

    def _resolve(self, req, reason, count="completed"):
        if req.state != FINISHED:
            req._finish(reason)
        req.slot = None
        if reason != SHED:
            # feeds the shed drain-rate EWMA: shedding itself must not
            # count as "drained" or a mass shed would spike the rate and
            # shrink the very retry hints it is about to hand out
            self._resolved_total += 1
        self._results[req.request_id] = req.result()
        if count is not None:
            metrics.bump(count)
        if reason in (STOP, LENGTH):
            metrics.bump(f"finished_{reason}")
        if req.trace is not None and not getattr(req, "_trace_done", False):
            # "deliver" lands at finish_t, the float the latency ledger
            # records — span timeline and SLO numbers reconcile exactly
            req._trace_done = True
            req.trace.instant("deliver", req.finish_t, reason=reason)
            obs_tracing.collect(req, engine_tag=self.tag)

    # -- hot weight swap -----------------------------------------------------
    def swap_params(self, params, version=None, count=True):
        """Replace the served weights in place with a SAME-SHAPE tree
        (``init_gpt_params`` layout, the thing ``HybridTrainStep`` trains):
        the executables are memoized per config and params are ordinary
        traced operands, so a same-shape swap re-dispatches the already
        compiled fused step — zero retraces (gated in tests). Bumps
        ``params_version`` (or sets it to ``version``); requests admitted
        AFTER the swap are stamped with the new version, requests already
        in a slot keep decoding against the swapped weights — which is why
        the supervisor's ``rolling_restart(new_params=)`` swaps only
        DRAINED replicas: in-flight work is requeued and recomputed from
        scratch on exactly one version, never a mid-stream mix.

        ``count=False`` skips the ``weight_swaps`` ledger bump — for
        RE-applications of already-live weights (a supervisor respawning a
        crashed replica after an upgrade), which are not new swaps and
        would make the upgrade audit trail useless for correlating
        regressions with actual weight changes."""
        if params is None:
            raise ValueError("swap_params needs a params tree")
        if any(r is not None for r in self._slots) \
                or self.scheduler.qsize() > 0:
            # KV already computed (and tokens already streamed) under the
            # old weights would continue under the new ones — a mid-stream
            # version mix. The supervisor always swaps freshly-spawned
            # (empty) engines; direct callers must drain first.
            raise RuntimeError(
                "swap_params on a non-idle engine: drain() first (the "
                "drained requests requeue and recompute single-version)")
        swap_spec = None
        if self._quant is not None and self._quant.quantizes_weights:
            # re-quantize ON DEVICE with FRESH per-channel scales (the
            # incoming weights' own absmax — a calibration pinned to the
            # OLD weights would clip channels that grew since); the KV
            # clip ranges stay the engine's (pool scales are untouched).
            # Same leaf dtypes/shapes as the served tree -> the shape
            # gate below passes and the swap stays zero-retrace.
            from dataclasses import replace as _dc_replace
            swap_spec = _dc_replace(self._quant, weight_scales=None)
        if self.mp > 1:
            # same prep as construction: head-major + column-sharded
            # placement (an already-sharded tree reshards on device)
            from .mp_forward import shard_serving_params
            new = shard_serving_params(params, self.config, self._mesh,
                                       self._mp_cfg, quant_spec=swap_spec)
        else:
            params = self._model.prepare(params, self.config)
            if swap_spec is not None:
                params = _squant.quantize_params(params, self.config,
                                                 swap_spec)
            new = jax.tree_util.tree_map(jnp.asarray, params)
        old_leaves, old_def = jax.tree_util.tree_flatten(self.params)
        new_leaves, new_def = jax.tree_util.tree_flatten(new)
        if old_def != new_def:
            raise ValueError(
                f"swap_params tree structure differs from the served "
                f"params ({new_def} vs {old_def}); a different "
                f"architecture needs a new Engine, not a swap")
        for o, n in zip(old_leaves, new_leaves):
            if o.shape != n.shape or o.dtype != n.dtype:
                raise ValueError(
                    f"swap_params leaf mismatch {n.shape}/{n.dtype} vs "
                    f"served {o.shape}/{o.dtype}; same-shape swaps only "
                    f"(anything else would retrace the fused step)")
        self.params = new
        self.params_version = (int(version) if version is not None
                               else self.params_version + 1)
        # the prefix cache holds KV pages COMPUTED UNDER THE OLD WEIGHTS —
        # a post-swap prompt that prefix-hit them would decode against
        # stale KV (caught by the parity gate). Version bump invalidates
        # the whole cache. This full flush is scoped to BASE-weight swaps
        # only: adapter load/evict/swap (load_adapter & co.) never touch
        # attention, so their pages stay valid and those ops deliberately
        # skip this.
        self.pool.clear_cache()
        if self._spec is not None:
            # the draft must propose against the NEW weights (a stale
            # draft would only cost accept rate, never correctness — the
            # verify pass serves the swapped tree — but the whole point
            # of the self-draft is tracking the served version for free)
            self._build_draft_params()
        if count:
            metrics.bump("weight_swaps")
        return self

    # -- adapter hot-load / evict / swap -------------------------------------
    def _require_adapters(self):
        if self.adapters is None:
            raise RuntimeError(
                "this engine serves no adapters; construct it with "
                "adapter_slots > 0 (or FLAGS_serving_adapter_slots)")
        return self.adapters

    def _check_adapter_unbound(self, adapter_id, verb):
        """Refuse to mutate an adapter some RUNNING slot is decoding
        against: its stream would silently switch delta bits mid-request
        — the adapter analogue of the mid-stream version mix swap_params
        drains against. Queued requests are fine (admission re-checks
        residency and stamps the version at seat time)."""
        busy = [b for b, r in enumerate(self._slots)
                if r is not None and int(self._aid[b]) == int(adapter_id)]
        if busy:
            raise RuntimeError(
                f"cannot {verb} adapter {adapter_id}: bound to running "
                f"slot(s) {busy}; wait for them to finish (or cancel)")

    def _adapter_gauges(self):
        metrics.set_adapter_residency(len(self.adapters.resident_ids()),
                                      self.adapters.delta_bytes())

    def load_adapter(self, adapter_id, tree, alpha=None, count=True):
        """Make ``adapter_id`` resident (hot — while serving): a
        content-only rewrite of the fixed-shape delta slabs, so like
        ``swap_params`` it re-dispatches the already-compiled fused step
        with ZERO retraces (gated in tests). Queued requests blocked on
        this id admit at the next boundary.

        Unlike ``swap_params``, loading an adapter does NOT flush the
        prefix-page cache: attention projections are never adapted
        (serving/adapters.py rejects ``qkv_w``), so every KV page is
        computed under the BASE weights only and stays valid for every
        adapter — shared-base prefix reuse across adapters is the point.

        ``count=False`` skips the ``adapter_loads`` ledger bump (the
        supervisor RE-applying a live adapter set onto a respawned
        replica — not a new load)."""
        reg = self._require_adapters()
        self._check_stopped()
        self._check_adapter_unbound(adapter_id, "load over")
        version = reg.load(adapter_id, tree, alpha=alpha)
        if count:
            metrics.bump("adapter_loads")
        self._adapter_gauges()
        return version

    def evict_adapter(self, adapter_id, count=True):
        """Drop a resident adapter (hot): its slab rows zero and its id
        becomes loadable again. Queued requests bound to it WAIT at
        admission (strict in-order) until a reload. No prefix-cache
        flush — see ``load_adapter``. Zero retraces."""
        reg = self._require_adapters()
        self._check_stopped()
        self._check_adapter_unbound(adapter_id, "evict")
        reg.evict(adapter_id)
        if count:
            metrics.bump("adapter_evicts")
        self._adapter_gauges()

    def swap_adapter(self, adapter_id, tree, alpha=None, count=True):
        """Replace a RESIDENT adapter's delta in place (hot): bumps the
        per-adapter version — requests admitted after the swap are
        stamped with it — and, like every adapter op, costs zero retraces
        and no prefix-cache flush. ``count=False`` is the supervisor
        applying one fleet-level swap across its replicas (counted
        once)."""
        reg = self._require_adapters()
        self._check_stopped()
        self._check_adapter_unbound(adapter_id, "swap")
        version = reg.load(adapter_id, tree, alpha=alpha, replace=True)
        if count:
            metrics.bump("adapter_swaps")
        self._adapter_gauges()
        return version

    # -- self-healing: snapshot / restore / drain ----------------------------
    def attach_checkpoint(self, mgr, every=None):
        """Attach a hardened ``CheckpointManager`` as this engine's
        snapshot sink: every ``every`` step boundaries (default
        ``FLAGS_serving_snapshot_every``; 0 disables the cadence) the full
        engine state is saved through the CRC/rename-aside/retry path, and
        ``run()`` installs the manager's SIGTERM hook in ``defer`` mode —
        on preemption the loop finishes the in-flight fused step, flushes
        a consistent snapshot at the boundary, requeues in-flight requests
        and unwinds with ``Preempted``. Returns self."""
        self._ckpt = mgr
        if every is None:
            every = get_flags().get("FLAGS_serving_snapshot_every", 32) or 0
        self._snapshot_every = max(0, int(every))
        # keep snapshot step ids MONOTONIC per manager: a fresh engine
        # reattached to a directory with history (e.g. a supervisor respawn
        # after a drain) must not write snapshots that sort BELOW the stale
        # ones — _prune would delete the new snapshot immediately and
        # restore(None) would keep resurrecting pre-restart state. (A
        # subsequent load_state_dict overwrites _step_count with the
        # restored snapshot's own step, which is >= every step it leaves
        # on disk.)
        latest = mgr.latest_step()
        if latest is not None:
            self._step_count = max(self._step_count, int(latest))
        return self

    def save_snapshot(self, blocking=None):
        """Checkpoint the full engine state at the current step count
        through the attached manager (satellite of the PR 4 hardened path:
        per-array CRC manifest, rename-aside publish, OSError retry /
        quarantine). Returns the snapshot's step id."""
        if self._ckpt is None:
            raise RuntimeError(
                "no CheckpointManager attached; call attach_checkpoint()")
        self._ckpt.save(self._step_count, self.state_dict(),
                        blocking=blocking)
        metrics.bump("snapshots")
        return self._step_count

    def _snapshot_meta(self):
        # params_version is part of the compatibility contract: a snapshot
        # holds KV computed under ONE weight version, and restoring it
        # onto an engine serving another version would resume mid-stream
        # on mixed weights. The mismatch raises in load_state_dict; the
        # supervisor then falls back to replay-from-scratch on the new
        # version — zero drops either way, single-version results always.
        # "kv_layout" is a constant: the key stays so that snapshots load
        # across versions of the program that wrote it.
        meta = {"kv_layout": "paged", "num_slots": self.num_slots,
                "max_seq_len": self.max_seq_len, "top_k": self.top_k,
                "params_version": int(self.params_version),
                "cfg": self._model.key(self.config),
                # dtype config: part of the restore contract — quantized
                # KV bytes do not reinterpret across dtypes, so a
                # mismatched restore is REFUSED (typed) up front
                "weight_dtype": (self._quant.weight_dtype
                                 if self._quant is not None else "bf16"),
                "kv_dtype": (self._quant.kv_dtype
                             if self._quant is not None else "bf16"),
                # adapter CAPACITY is a compatibility axis (slab shapes);
                # the resident SET is data and rides state["adapters"]
                "adapters": (None if self._adapter_spec is None
                             else self._adapter_spec.key()),
                "page_size": self.page_size,
                "prefill_chunk": self.prefill_chunk,
                "num_pages": self.pool.num_pages}
        if len(self._group_pools) > 1:
            meta["group_pages"] = [p.num_pages for p in self._group_pools]
        return meta

    @staticmethod
    def _result_state(res):
        return {"request_id": int(res.request_id),
                "prompt": np.asarray(res.prompt).copy(),
                "tokens": list(res.tokens),
                "finish_reason": res.finish_reason,
                "ttft": res.ttft, "latency": res.latency,
                "priority": res.priority, "tenant": res.tenant,
                "params_version": res.params_version,
                "adapter": res.adapter,
                "adapter_version": res.adapter_version,
                "retry_after": res.retry_after,
                # exceptions may not pickle; the repr is enough postmortem
                "callback_error": (None if res.callback_error is None
                                   else repr(res.callback_error))}

    def state_dict(self):
        """Snapshot the FULL engine as host numpy / plain python: device
        KV (including the slot->page table, refcounted allocator and
        prefix-cache entries via ``PagedKVPool.state_dict``), the host slot
        table (last token,
        write position, per-slot threefry streams, sampling params, chunk
        progress, admission sequence), every in-flight and queued request
        (``Request.to_state``; ``on_token`` callbacks are not captured),
        unpopped results, and the serving metrics ledger. Safe for
        ``CheckpointManager``/``framework.io`` round trips; pair with
        ``load_state_dict`` for bitwise mid-decode resume."""
        pools_np = [self._logical(jax.device_get(a), g)
                    for a, g in zip(self._pools, self._pool_group)]
        # fp8/bf16 pools: numpy IO paths don't all speak ml_dtypes —
        # snapshot the raw bytes; the restoring pool's dtype takes the view
        pools_np = [a if a.dtype in (np.int8, np.float32, np.float64,
                                     np.float16) else a.view(np.uint8)
                    for a in pools_np]
        state = {
            "meta": self._snapshot_meta(),
            # each pool array under its geometry's name (GPT: kc, vc)
            **dict(zip(self._geo.names, pools_np)),
            "pos": self._pos.copy(), "tok": self._tok.copy(),
            "keys": self._keys.copy(), "temp": self._temp.copy(),
            "top_p": self._top_p.copy(),
            "do_sample": self._do_sample.copy(),
            "chunk_off": self._chunk_off.copy(),
            "aid": self._aid.copy(),
            "admit_seq": self._admit_seq.copy(),
            "admit_count": int(self._admit_count),
            "step_count": int(self._step_count),
            "slots": [None if r is None else r.to_state()
                      for r in self._slots],
            "queue": self.scheduler.queue_state(),
            "results": [self._result_state(r)
                        for r in self._results.values()],
            "metrics": metrics.export_state(),
            # both clocks: perf_counter anchors the request timestamps
            # (same-boot restores compare directly), wall time measures
            # the outage when the perf origin changed (other host/boot)
            "snapshot_t": time.perf_counter(),
            "snapshot_wall": time.time(),
            "pool": self.pool.state_dict(),
        }
        if len(self._group_pools) > 1:
            # the further groups' tables and allocators (a window group's
            # ring among them) ride beside the first's
            state["group_pools"] = [p.state_dict()
                                    for p in self._group_pools[1:]]
        if self.adapters is not None:
            # the resident adapter SET rides every snapshot: a restored
            # (or supervisor-respawned) engine serves the same many-model
            # surface without re-issuing load_adapter calls
            state["adapters"] = self.adapters.state_dict()
        if self._spec is not None:
            # draft/speculation state. Drafts are BOUNDARY-ATOMIC — a
            # draft+verify pair completes inside one step boundary and
            # every rejected byte is rewound before the host regains
            # control — so there is never pending-draft progress to
            # drain: the snapshot is always the plain-equivalent state,
            # which is what lets spec <-> plain restores stay bitwise.
            # (Deliberately NOT in _snapshot_meta: spec config is an
            # ENGINE property, not a snapshot-compatibility axis.)
            state["spec"] = {
                "speculate_k": int(self._spec.k),
                "draft_source": self._spec.source,
                "draft_layers": int(self._spec.layers),
                "draft_params_version": (
                    None if self._draft_params_version is None
                    else int(self._draft_params_version)),
            }
        return state

    def load_state_dict(self, state, restore_metrics=False):
        """Restore a ``state_dict()`` snapshot into this (compatibly
        configured) engine and resume exactly: mid-decode slots continue
        token-for-token bitwise identically to an uninterrupted run,
        greedy and sampled. No retracing happens — the
        executable builders are memoized per config, so a restored engine
        over warm shapes re-dispatches the already-compiled fused step
        (trace counters do not move; gated in tests).

        ``restore_metrics=True`` additionally replaces the process-global
        serving ledger with the snapshot's (for a cold cross-process
        restart); leave it False when other engines share the process.

        Timestamps: ``submit_t``/deadlines are ``perf_counter`` values
        whose origin is per-boot-arbitrary, so they are re-anchored onto
        the local clock using the snapshot's WALL-clock companion: the
        outage is measured as wall time elapsed since the save (NTP-level
        accuracy is plenty for second-scale deadlines), and every request
        timestamp shifts so the snapshot instant maps to ``now - outage``.
        Deadlines therefore keep ticking through the outage on any host;
        a same-process restore shifts by ~0."""
        meta = dict(state["meta"])
        # pre-quant snapshots carry no dtype fields: they are bf16/bf16
        meta.setdefault("weight_dtype", "bf16")
        meta.setdefault("kv_dtype", "bf16")
        # pre-adapter snapshots carry no capacity field: adapter-less.
        # Normalize the key's tuple-of-tuples (JSON round trips lists)
        meta.setdefault("adapters", None)
        if meta["adapters"] is not None:
            s, r, t = meta["adapters"]
            meta["adapters"] = (int(s), int(r), tuple(t))
        mine = self._snapshot_meta()
        snap_q = (meta["weight_dtype"], meta["kv_dtype"])
        mine_q = (mine["weight_dtype"], mine["kv_dtype"])
        if snap_q != mine_q:
            # typed refusal BEFORE any state is touched: quantized KV
            # bytes (and the scale tables) do not reinterpret across
            # dtype configs — deserializing them would be garbage
            raise _squant.QuantDtypeMismatchError(snap_q, mine_q)
        if meta != mine:
            raise ValueError(
                f"engine snapshot meta {meta} does not match this engine "
                f"{mine}; build the restoring Engine with the same config")
        restored = []
        for name, like in zip(self._geo.names, self._pools):
            a_np = np.asarray(state[name])
            if a_np.dtype == np.uint8 and like.dtype != jnp.uint8:
                # raw-byte snapshot of an fp8 pool: restore the dtype view
                a_np = a_np.view(like.dtype)
            restored.append(pad_lanes(jnp.asarray(a_np, like.dtype), like))
        self._pools = tuple(restored)
        if self._kv_sharding is not None:
            # snapshots hold the GLOBAL pool (mp-independent geometry, and
            # the gather-only schedule makes its contents bitwise equal at
            # every mp) — lay the head axis back out across this engine's
            # chips. A snapshot therefore restores across mp degrees, incl.
            # single-chip <-> sharded.
            self._pools = tuple(jax.device_put(a, self._kv_sharding)
                                for a in self._pools)
        self._pos = np.asarray(state["pos"], np.int32).copy()
        self._tok = np.asarray(state["tok"], np.int32).copy()
        self._keys = np.asarray(state["keys"], np.uint32).copy()
        self._temp = np.asarray(state["temp"], np.float32).copy()
        self._top_p = np.asarray(state["top_p"], np.float32).copy()
        self._do_sample = np.asarray(state["do_sample"], bool).copy()
        self._chunk_off = np.asarray(state["chunk_off"], np.int32).copy()
        if "aid" in state:
            self._aid = np.asarray(state["aid"], np.int32).copy()
        else:                      # pre-adapter snapshot: all base
            self._aid = np.zeros(self.num_slots, np.int32)
        if self.adapters is not None and "adapters" in state:
            self.adapters.load_state_dict(state["adapters"])
            self._adapter_gauges()
        self._admit_seq = np.asarray(state["admit_seq"], np.int64).copy()
        self._admit_count = int(state["admit_count"])
        self._admit_t = [time.perf_counter()] * self.num_slots
        self._step_count = int(state["step_count"])
        self.pool.load_state_dict(state["pool"])
        for pool, st in zip(self._group_pools[1:],
                            state.get("group_pools", ())):
            pool.load_state_dict(st)
        # in-flight transfer state is NOT part of a snapshot (the
        # KVTransfer objects live with the supervisor, which replays or
        # re-offers them): staged pages restored by the pool have no
        # owning stream anymore — return them to the free list
        self.pool.clear_staged()
        self._transfers_in = []
        self._install_progress = {}
        self._outbound = {}
        self._fresh_outbound = []
        self._slots = [None if s is None else Request.from_state(s)
                       for s in state["slots"]]
        queue = [Request.from_state(s) for s in state["queue"]]
        self.scheduler.restore_queue(queue)
        if self.role == "prefill":
            # a restored mid-prefill slot has no outbound stream to append
            # to (transfers are not snapshotted): reset it to the queue —
            # re-admission opens a fresh transfer and the replay is
            # bitwise (same prompt, same pages, no tokens emitted yet)
            for b, req in enumerate(self._slots):
                if req is None:
                    continue
                self._free_slot(b, register=False)
                req._requeue()
                self.scheduler.requeue(req)
                metrics.bump("requeued")
        outage = max(0.0, time.time() - float(state["snapshot_wall"]))
        shift = (time.perf_counter() - outage) - float(state["snapshot_t"])
        live = [r for r in self._slots if r is not None] + queue
        for r in live:
            for attr in ("submit_t", "first_token_t", "finish_t"):
                v = getattr(r, attr)
                if v is not None:
                    setattr(r, attr, v + shift)
            if r.trace is not None:
                # spans ride the same clock re-anchoring as the request
                # timestamps, then a restore hop marks the outage on the
                # request's own timeline
                r.trace.shift(shift)
                r.trace.instant("restore", outage_s=outage)
        self._results = {
            d["request_id"]: GenerationResult(
                request_id=d["request_id"], prompt=d["prompt"],
                tokens=list(d["tokens"]), finish_reason=d["finish_reason"],
                ttft=d["ttft"], latency=d["latency"],
                callback_error=d["callback_error"],
                priority=d.get("priority", "batch"),
                tenant=d.get("tenant", "default"),
                params_version=d.get("params_version"),
                adapter=d.get("adapter", 0),
                adapter_version=d.get("adapter_version"),
                retry_after=d.get("retry_after"))
            for d in state["results"]}
        if restore_metrics:
            metrics.import_state(state["metrics"])
        elif self.pool.prefix_cache_enabled \
                and self.pool.cache_entries > 0:
            # the restored pool carries REAL cache entries whose lookups/
            # hits were counted before the snapshot: without the matching
            # counters, the post-restore hit RATE lies (hits against
            # restored entries over a lookup count that starts at zero).
            # Seed the prefix counters from the snapshot — only when this
            # process hasn't counted any prefix traffic of its own yet
            # (a shared-process sibling engine's ledger is never clobbered)
            metrics.seed_prefix_counters(
                state["metrics"].get("counters", {}))
        metrics.bump("snapshot_restores")
        if self._spec is not None:
            # the restoring engine rebuilt its draft from ITS OWN served
            # weights at construction; the meta check above already
            # guaranteed params_version agreement, so the draft tracks
            # the restored version too (state["spec"] is an audit stamp,
            # not restored state — drafts are boundary-atomic)
            self._draft_params_version = self.params_version
        self._stopped = False
        self._reforming = False
        self._reform_retry_after = None
        self._drained = []
        return self

    def drain(self):
        """Stop the engine and hand back every incomplete request, oldest
        arrival first: running slots are freed (pages released, prefix
        pages published) and their requests reset for requeue — original
        ``submit_t``/deadline kept, progress cleared so a replay re-emits
        the same tokens deterministically — and the wait queue is emptied
        untouched. The engine is left STOPPED: ``submit()`` raises
        ``EngineStoppedError`` (carrying these requests as the requeue
        hint) and ``step()`` returns False. Completed results remain
        available via ``pop_results()``."""
        drained = []
        for b, req in enumerate(self._slots):
            if req is None:
                continue
            self._free_slot(b)
            req._requeue()
            metrics.bump("requeued")
            drained.append(req)
        # transfer hygiene: outbound streams of freed slots were aborted
        # by _free_slot above; inbound streams return their staged pages —
        # their requests live on with the SUPERVISOR (payloads retained on
        # the KVTransfer), which re-offers or replays them elsewhere
        for tr in self._transfers_in:
            self.pool.release_staged(tr.request_id)
        self._transfers_in = []
        self._install_progress = {}
        for tr in self._outbound.values():
            if not tr.done:
                tr.aborted = True
        self._outbound = {}
        self._fresh_outbound = []
        drained.extend(self.scheduler.drain_queue())
        drained.sort(key=lambda r: (
            r.submit_t if r.submit_t is not None else float("inf"),
            r.request_id))
        self._stopped = True
        self._drained = drained
        return list(drained)

    def preempt_drain(self):
        """Graceful preemption at a step boundary (the serving mirror of
        ``CheckpointManager``'s ``defer=True`` flush; ``run()`` calls this
        between fused steps once the SIGTERM handler marks the manager
        preempted, so the snapshot is never torn mid-dispatch). Order
        matters: snapshot FIRST with slots intact — a cold restart resumes
        every mid-decode request bitwise — THEN requeue in-flight requests
        (the replay hint for a router when the snapshot is stale or
        unreachable), then unwind with ``Preempted``."""
        metrics.bump("preempt_drains")
        step = self._step_count
        state = self.state_dict()
        self.drain()
        if self._ckpt is not None:
            self._ckpt.flush_preempted(state, step=step)  # raises Preempted
        from ..incubate.checkpoint import Preempted
        raise Preempted("engine preempted; in-flight requests requeued")

    def live_requests(self):
        """Every incomplete request this engine owns: running slots (slot
        order) then the wait queue (FCFS)."""
        out = [r for r in self._slots if r is not None]
        out.extend(r for r in self.scheduler._q if r.state != FINISHED)
        return out

    @property
    def stopped(self):
        return self._stopped

    # -- draining ------------------------------------------------------------
    def pop_results(self):
        """Drain resolved requests: returns {request_id: GenerationResult}
        for everything resolved since the last drain and forgets them.
        Call this from a ``step()`` loop — results are held until popped,
        so an undrained long-running engine grows without bound."""
        out, self._results = self._results, {}
        return out

    def export_trace(self, path):
        """Write every collected finished-request trace and engine boundary
        (process-wide rings, this engine's included) as Perfetto-loadable
        Chrome-trace JSON: one thread per request and, per engine, the
        ``boundaries`` thread with each step's ``pt.serve.*`` phases."""
        return obs_tracing.export_perfetto(path)

    def run(self, requests=None):
        """Submit ``requests`` (optional) and step until queue and slots are
        empty. Returns {request_id: GenerationResult} for everything that
        resolved during this call (including earlier submissions).

        With a checkpoint manager attached, the manager's SIGTERM hook is
        installed in ``defer`` mode for the duration of the loop: a
        preemption notice only marks the manager, the loop finishes the
        current fused step, then ``preempt_drain()`` flushes a consistent
        boundary snapshot, requeues in-flight requests and unwinds with
        ``Preempted`` (BaseException — a preempted server must exit, not
        retry)."""
        if requests is not None:
            for r in requests:
                self.submit(r)
        installed = False
        if self._ckpt is not None and \
                threading.current_thread() is threading.main_thread():
            try:  # signals are main-thread-only; elsewhere rely on cadence
                self._ckpt.install_preemption_hook(None, defer=True)
                installed = True
            except ValueError:
                pass
        try:
            while True:
                if self._ckpt is not None and self._ckpt.preempted:
                    self.preempt_drain()         # raises Preempted
                if not self.step():
                    break
            if self._ckpt is not None and self._ckpt.preempted:
                # the notice landed DURING the final step: still flush and
                # unwind with Preempted — returning normally would let the
                # caller submit more work and the next hook install re-arm
                # (erase) the pending preemption
                self.preempt_drain()
        finally:
            if installed:
                self._ckpt.remove_preemption_hook()
        return self.pop_results()

    def generate(self, prompts, **kw):
        """Batch convenience: one Request per prompt (shared kwargs),
        results returned in submission order."""
        reqs = [Request(p, **kw) for p in prompts]
        results = self.run(reqs)
        return [results[r.request_id] for r in reqs]

    # -- introspection -------------------------------------------------------
    def kv_bytes_per_token(self):
        """Per-chip KV bytes one token position costs at this engine's
        dtype config: K + V across all layers for the chip's head shard,
        plus the amortized per-page scale bytes on a quantized pool — the
        bytes-per-token-by-dtype gauge of the capacity story (int8 ~4x
        fewer than fp32, fp8 likewise)."""
        # a token's row in every pool array, as many lanes as the device
        # holds, over the chips that share the head axis
        per_tok = sum(a.shape[0] * int(np.prod(a.shape[3:]))
                      * int(a.dtype.itemsize)
                      for a, g in zip(self._pools, self._pool_group)
                      if self._geo.groups[g].paged) // self.mp
        if self._kv_quant:
            # two fp32 scales per (layer, page), shared by page_size
            # tokens — rounded UP so the gauge never underreports to 0
            per_tok += -(-2 * self._geo.groups[0].layers * 4
                         // self.page_size)
        return per_tok

    def kv_shard_bytes(self):
        """Per-chip bytes of ONE of the two KV pool arrays at the pool's
        STORAGE dtype (int8/fp8 pools report their quantized footprint):
        the whole pool on a single-chip engine, 1/mp of it (the head
        shard) under mp — the memory gate of the sharded engine."""
        if self._kv_sharding is None:
            return int(self._kc.nbytes)
        shape = self._kv_sharding.shard_shape(self._kc.shape)
        n = 1
        for s in shape:
            n *= int(s)
        return n * self._kc.dtype.itemsize

    @property
    def active_slots(self):
        return sum(r is not None for r in self._slots)

    @property
    def queue_depth(self):
        return self.scheduler.qsize()
