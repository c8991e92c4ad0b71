"""Serving request/result types.

A `Request` is one user generation job; the engine assigns it a slot in the
fixed decode batch, streams tokens to `on_token` as they are produced, and
resolves it into a `GenerationResult`. Sampling params (temperature/top_p,
per-request seed) are TRACED per-slot operands of the shared decode
executable, so any mix of greedy and sampled requests batches together
without recompiling.
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field

import numpy as np

from ..models.generation import _normalize_stop

_req_ids = itertools.count()

# request lifecycle states
QUEUED = "queued"
RUNNING = "running"
FINISHED = "finished"

# finish reasons
STOP = "stop"          # produced a stop token
LENGTH = "length"      # hit max_new_tokens
EXPIRED = "expired"    # deadline passed before/while running
CANCELLED = "cancelled"
DROPPED = "dropped"    # supervisor had no live replica left to replay on
SHED = "shed"          # load-shed under sustained overload (retry_after set)
ERROR = "error"        # anomaly guard quarantined the slot (non-finite
                       # logits — bad weights / corrupted KV / flaky chip)


@dataclass(eq=False)  # identity equality: deque.remove/cancel compare BY
class Request:        # OBJECT, and field-wise eq would compare numpy prompts
    """One generation job. ``prompt`` is a 1-D int sequence. ``eos_token_id``
    is the scalar alias for ``stop_token_ids`` (both accepted, merged).
    ``top_k`` must match the engine's static top_k (it shapes the top_k
    kernel and would recompile per value). ``deadline_s`` is a relative
    deadline from submit time: expired requests are failed at the next step
    boundary instead of occupying a slot."""
    prompt: object
    max_new_tokens: int = 32
    do_sample: bool = False
    temperature: float = 1.0
    top_p: float | None = None
    top_k: int | None = None
    eos_token_id: int | None = None
    stop_token_ids: object = None
    seed: int = 0
    deadline_s: float | None = None
    on_token: object = None          # callback(request, token_id)
    # SLO class + tenant (serving/slo.py). Policy-only: with
    # FLAGS_serving_priority_classes off both are carried but never read,
    # so default traffic is byte-identical to the pre-SLO engine. Classes:
    # "interactive" (rank 0, may preempt), "batch" (default),
    # "best_effort" (preempted and shed first).
    priority: str = "batch"
    tenant: str = "default"
    # speculative-decode opt-out ("auto" | "off"). Policy-only, like
    # priority: on a speculative engine, "off" pins this request to plain
    # one-token decode (nprop=0 inside the SAME fused verify dispatch —
    # a latency-sensitive tenant trades throughput for the tightest
    # inter-token gap); on a plain engine it is carried but never read.
    speculate: str = "auto"
    # adapter id to serve this request with (serving/adapters.py): 0 = the
    # base model, 1..slots = a loaded low-rank delta, None = "resolve from
    # the tenant mapping at submit" (FLAGS_serving_tenant_adapters;
    # unmapped tenants get the base model). Submit raises a typed
    # UnknownAdapterError for ids outside the engine's capacity; a merely
    # non-resident id queues and blocks at admission until loaded.
    adapter: int | None = None

    # -- engine-managed state ------------------------------------------------
    request_id: int = field(default_factory=lambda: next(_req_ids))
    state: str = field(default=QUEUED)
    tokens: list = field(default_factory=list)
    slot: int | None = field(default=None)
    submit_t: float | None = field(default=None)
    first_token_t: float | None = field(default=None)
    finish_t: float | None = field(default=None)
    finish_reason: str | None = field(default=None)
    callback_error: object = field(default=None)  # first on_token exception
    requeue_count: int = field(default=0)         # drain/replay round trips
    # weight version this request's tokens were produced under (stamped at
    # admission; re-stamped when a requeue recomputes from scratch on a
    # swapped replica, so the RESULT is always single-version consistent)
    params_version: int | None = field(default=None)
    # per-adapter content version the tokens were produced under (stamped
    # at admission from AdapterRegistry.version; 0 for the base model) —
    # the adapter analogue of params_version
    adapter_version: int | None = field(default=None)
    # retry-after hint attached when load shedding resolves this request
    # (seconds until the shed backlog should have drained)
    retry_after: float | None = field(default=None)
    # span trace context (observability.RequestTrace) — attached by the
    # engine when FLAGS_serving_trace is on, None otherwise (untraced
    # requests pay one attribute check per recording site)
    trace: object = field(default=None)

    def __post_init__(self):
        self.prompt = np.asarray(
            self.prompt._data if hasattr(self.prompt, "_data") else self.prompt,
            np.int32).reshape(-1)
        if self.prompt.shape[0] == 0:
            # an empty prompt would read logits at a pad token —
            # plausible-looking output conditioned on nothing the user sent
            raise ValueError("prompt must be non-empty")
        if self.max_new_tokens < 0:
            raise ValueError(
                f"max_new_tokens must be >= 0, got {self.max_new_tokens}")
        if self.do_sample and self.temperature <= 0:
            # _mask_logits divides by the (clamped) temperature — a 0/neg
            # value would push every logit to +/-inf and sample garbage;
            # greedy requests never touch it, so they pass through
            raise ValueError(
                f"temperature must be > 0 for sampled requests, got "
                f"{self.temperature} (use do_sample=False for greedy)")
        self.stop_token_ids = _normalize_stop(
            self.eos_token_id, self.stop_token_ids) or ()
        if self.top_k == 0:            # generate's "disabled" spelling
            self.top_k = None
        from .slo import class_rank
        class_rank(self.priority)      # validate eagerly: fail at submit
        self.tenant = str(self.tenant)
        if self.speculate not in ("auto", "off"):
            raise ValueError(
                f"speculate must be 'auto' or 'off', got "
                f"{self.speculate!r}")
        if self.adapter is not None:
            self.adapter = int(self.adapter)
            if self.adapter < 0:
                from .adapters import UnknownAdapterError
                raise UnknownAdapterError(
                    self.adapter,
                    f"adapter id must be >= 0 (0 = base model), got "
                    f"{self.adapter}")

    @property
    def prompt_len(self):
        return int(self.prompt.shape[0])

    @property
    def deadline(self):
        """Absolute deadline (perf_counter clock), or None."""
        if self.deadline_s is None or self.submit_t is None:
            return None
        return self.submit_t + self.deadline_s

    def expired(self, now):
        """THE deadline-boundary predicate: a request is expired from the
        first instant ``now >= deadline`` (the deadline itself is outside
        the allowed window). Every site — queue expiry, admission,
        mid-flight eviction — routes through here, so the boundary
        semantics cannot drift between call sites again."""
        dl = self.deadline
        return dl is not None and now >= dl

    @property
    def class_rank(self):
        from .slo import class_rank
        return class_rank(self.priority)

    def _emit(self, token):
        self.tokens.append(int(token))
        if self.first_token_t is None:
            self.first_token_t = time.perf_counter()
        if self.on_token is not None:
            try:
                self.on_token(self, int(token))
            except Exception as e:    # noqa: BLE001 — user callback
                # A broken client stream must not unwind step(): the KV
                # cache and PRNG keys advanced BEFORE this emission, so an
                # escaping error would leave host _tok/_pos stale and the
                # next step() would re-feed old tokens at old positions
                # (duplicated token, diverged sampled stream). Disable the
                # callback, record the error, finish the request normally.
                self.callback_error = e
                self.on_token = None
                import warnings
                warnings.warn(
                    f"request {self.request_id}: on_token callback raised "
                    f"{type(e).__name__}: {e}; streaming disabled for this "
                    f"request (see GenerationResult.callback_error)")

    def _finish(self, reason):
        self.state = FINISHED
        self.finish_reason = reason
        self.finish_t = time.perf_counter()

    # -- drain / replay ------------------------------------------------------
    def _requeue(self):
        """Reset generation progress for a drain/preemption requeue. The
        ORIGINAL ``submit_t`` (arrival) is kept, so the deadline keeps
        ticking from first submission and TTFT never restarts; emitted
        tokens are cleared — a replay recomputes them deterministically
        (same seed / per-slot stream), so streaming is at-least-once but the
        final token list is bitwise what an uninterrupted run produces.
        ``first_token_t`` survives when a token was already streamed (the
        user saw it); otherwise TTFT spans the recovery gap too."""
        self.state = QUEUED
        self.slot = None
        self.tokens = []
        self.finish_t = None
        self.finish_reason = None
        self.requeue_count += 1
        if self.trace is not None:
            self.trace.instant("requeue", round=self.requeue_count)

    def replay_copy(self):
        """Fresh QUEUED copy for replaying on ANOTHER engine after its
        owner died: same ``request_id``, prompt, sampling params, seed,
        ``on_token`` callback and — critically — the ORIGINAL ``submit_t``
        and relative deadline (a replayed request must not be granted a
        fresh deadline, and its TTFT counts from first submission)."""
        r = Request(self.prompt.copy(), max_new_tokens=self.max_new_tokens,
                    do_sample=self.do_sample, temperature=self.temperature,
                    top_p=self.top_p, top_k=self.top_k,
                    stop_token_ids=self.stop_token_ids, seed=self.seed,
                    deadline_s=self.deadline_s, on_token=self.on_token,
                    priority=self.priority, tenant=self.tenant,
                    speculate=self.speculate, adapter=self.adapter)
        r.request_id = self.request_id
        r.submit_t = self.submit_t
        r.first_token_t = self.first_token_t
        r.requeue_count = self.requeue_count + 1
        if self.trace is not None:
            # the replay inherits the whole span history (queue wait and
            # any tokens the dead owner already produced are part of THIS
            # request's latency story) plus a failover hop marker
            r.trace = self.trace.copy()
            r.trace.instant("replay", round=r.requeue_count)
        return r

    # -- snapshot ------------------------------------------------------------
    def to_state(self):
        """Serializable snapshot of the request (engine state_dict leaf).
        ``on_token`` callbacks are NOT serialized (arbitrary closures don't
        survive a process boundary); a restored request finishes without
        streaming — its result still carries every token."""
        return {
            "prompt": self.prompt.copy(),
            "max_new_tokens": int(self.max_new_tokens),
            "do_sample": bool(self.do_sample),
            "temperature": float(self.temperature),
            "top_p": None if self.top_p is None else float(self.top_p),
            "top_k": None if self.top_k is None else int(self.top_k),
            "stop_token_ids": tuple(self.stop_token_ids or ()),
            "seed": int(self.seed),
            "deadline_s": (None if self.deadline_s is None
                           else float(self.deadline_s)),
            "priority": self.priority,
            "tenant": self.tenant,
            "speculate": self.speculate,
            "adapter": None if self.adapter is None else int(self.adapter),
            "adapter_version": (None if self.adapter_version is None
                                else int(self.adapter_version)),
            "params_version": (None if self.params_version is None
                               else int(self.params_version)),
            "request_id": int(self.request_id),
            "state": self.state,
            "tokens": list(self.tokens),
            "slot": None if self.slot is None else int(self.slot),
            "submit_t": self.submit_t,
            "first_token_t": self.first_token_t,
            "finish_t": self.finish_t,
            "finish_reason": self.finish_reason,
            "requeue_count": int(self.requeue_count),
            "trace": None if self.trace is None else self.trace.to_state(),
        }

    @classmethod
    def from_state(cls, state):
        """Rebuild a request from ``to_state()`` output. Bumps the global
        request-id counter past the restored id so requests created AFTER a
        cross-process restore can never collide with restored ones."""
        r = cls(state["prompt"], max_new_tokens=state["max_new_tokens"],
                do_sample=state["do_sample"], temperature=state["temperature"],
                top_p=state["top_p"], top_k=state["top_k"],
                stop_token_ids=state["stop_token_ids"], seed=state["seed"],
                deadline_s=state["deadline_s"],
                priority=state.get("priority", "batch"),
                tenant=state.get("tenant", "default"),
                speculate=state.get("speculate", "auto"),
                adapter=state.get("adapter"))
        r.params_version = state.get("params_version")
        r.adapter_version = state.get("adapter_version")
        r.request_id = int(state["request_id"])
        global _req_ids
        floor = next(_req_ids)
        if floor <= r.request_id:
            _req_ids = itertools.count(r.request_id + 1)
        r.state = state["state"]
        r.tokens = list(state["tokens"])
        r.slot = state["slot"]
        r.submit_t = state["submit_t"]
        r.first_token_t = state["first_token_t"]
        r.finish_t = state["finish_t"]
        r.finish_reason = state["finish_reason"]
        r.requeue_count = int(state.get("requeue_count", 0))
        if state.get("trace") is not None:
            from ..observability import RequestTrace
            r.trace = RequestTrace.from_state(r.request_id, state["trace"])
        return r

    def result(self):
        if self.state != FINISHED:
            raise RuntimeError(
                f"request {self.request_id} not finished (state={self.state})")
        return GenerationResult(
            request_id=self.request_id,
            prompt=self.prompt,
            tokens=list(self.tokens),
            finish_reason=self.finish_reason,
            ttft=(None if self.first_token_t is None or self.submit_t is None
                  else self.first_token_t - self.submit_t),
            latency=(None if self.finish_t is None or self.submit_t is None
                     else self.finish_t - self.submit_t),
            callback_error=self.callback_error,
            priority=self.priority,
            tenant=self.tenant,
            params_version=self.params_version,
            adapter=0 if self.adapter is None else self.adapter,
            adapter_version=self.adapter_version,
            retry_after=self.retry_after,
        )


@dataclass
class GenerationResult:
    """Resolved output of one Request. ``tokens`` are the NEW tokens only
    (stop token included when one fired, matching `generate`'s output);
    ``sequence`` is prompt + tokens."""
    request_id: int
    prompt: np.ndarray
    tokens: list
    finish_reason: str
    ttft: float | None = None
    latency: float | None = None
    callback_error: object = None    # first on_token exception, if any
    priority: str = "batch"
    tenant: str = "default"
    # weight version the tokens were produced under (hot-swap audit trail);
    # None when the request never reached a slot
    params_version: int | None = None
    # adapter id the request was served with (0 = base model) and the
    # per-adapter content version its tokens were produced under
    adapter: int = 0
    adapter_version: int | None = None
    # seconds-until-retry hint on finish_reason == "shed"
    retry_after: float | None = None

    @property
    def sequence(self):
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int32)])

    @property
    def tokens_per_s(self):
        if not self.tokens or not self.latency:
            return 0.0
        return len(self.tokens) / self.latency
