"""Request scheduler for the continuous-batching engine.

FCFS admission at STEP boundaries (Orca-style iteration-level scheduling):
between decode iterations the engine asks the scheduler for requests to
prefill into free slots. The scheduler owns the wait queue (bounded —
`submit` raises `QueueFullError` past `max_queue`, the backpressure signal a
frontend turns into HTTP 429) and per-request deadlines (expired requests
are failed at the boundary instead of wasting a prefill).
"""
from __future__ import annotations

import time
from collections import deque

from .request import EXPIRED, FINISHED, QUEUED, SHED


class QueueFullError(RuntimeError):
    """Raised by submit() when the wait queue is at max_queue. Carries
    ``qsize`` (waiting requests at rejection time) and ``max_queue`` so a
    router can back off proportionally (retry-after ~ qsize/max_queue)
    instead of blind-retrying. At the supervisor both fields are
    FLEET-WIDE totals (every replica's waiting requests / capacity), so
    the hint reflects the traffic the client actually competes with."""

    def __init__(self, message, qsize=None, max_queue=None):
        super().__init__(message)
        self.qsize = qsize
        self.max_queue = max_queue


class ShedError(QueueFullError):
    """Load shedding refused this request: the fleet is in sustained
    overload and the request's class is being shed. Shares the
    ``qsize``/``max_queue`` backpressure fields with ``QueueFullError``
    (so existing 429 handlers catch both) and adds ``retry_after`` —
    seconds until the shed backlog should have drained, derived from the
    LIVE queue-drain rate rather than a blind exponential backoff."""

    def __init__(self, message, qsize=None, max_queue=None,
                 retry_after=None):
        super().__init__(message, qsize=qsize, max_queue=max_queue)
        self.retry_after = retry_after


class Scheduler:
    """``priority=False`` (default) is strict FCFS — byte-identical to the
    pre-SLO scheduler the parity suites gate. ``priority=True`` makes
    admission class-aware (serving/slo.py): best class first, and within a
    class weighted fair queueing across tenants (deficit round-robin over
    per-tenant FCFS lanes, ``tenant_weights`` credits per rotation) so one
    tenant's burst cannot starve another's trickle. The wait queue itself
    stays ONE arrival-ordered deque either way: snapshots, drains,
    requeue-at-original-arrival and cancel races are order-agnostic and
    shared between both modes — priority is a pure admission-order policy
    computed at the boundary.

    ``lane_key`` generalizes the WFQ lane axis: the default (None) lanes
    by ``r.tenant``; an adapter-serving engine passes ``lambda r:
    r.adapter or 0`` so fairness rotates across ADAPTERS — one hot
    fine-tune's burst cannot starve the other models sharing the engine.
    ``tenant_weights`` keys by whatever the lane key returns."""

    def __init__(self, max_queue=256, priority=False, tenant_weights=None,
                 lane_key=None):
        self.max_queue = int(max_queue)
        self.priority = bool(priority)
        # weights clamp to >= 1: a zero credit would starve the tenant's
        # lane AND stall the WFQ rotation that expects every pass to drain
        self.tenant_weights = {str(t): max(1, int(w))
                               for t, w in (tenant_weights or {}).items()}
        self.lane_key = (lambda r: r.tenant) if lane_key is None else lane_key
        self._wfq_last = {}            # class rank -> last-served lane
        self._q = deque()

    def set_tenant_weight(self, tenant, weight):
        """WFQ credit per rotation for ``tenant`` (default 1): a weight-2
        tenant is served two requests per round-robin pass."""
        self.tenant_weights[str(tenant)] = max(1, int(weight))

    # -- queue ---------------------------------------------------------------
    def submit(self, req):
        if len(self._q) >= self.max_queue:
            raise QueueFullError(
                f"serving queue full ({self.max_queue} waiting); retry later",
                qsize=len(self._q), max_queue=self.max_queue)
        if req.state != QUEUED:
            raise ValueError(f"request {req.request_id} already "
                             f"{req.state}; requests are single-use")
        if req.submit_t is None:
            # first submission stamps the arrival clock; a drained/replayed
            # request keeps its ORIGINAL submit_t (and therefore deadline —
            # preemption must not grant a fresh one, and TTFT counts from
            # first submission)
            req.submit_t = time.perf_counter()
        self._q.append(req)

    def requeue(self, req):
        """Return a previously-admitted (drained/preempted) request to the
        wait queue at its ARRIVAL position: inserted before any request
        that was submitted later, so global FCFS order is preserved across
        a drain. ``max_queue`` is intentionally bypassed — the request was
        already accepted once and dropping it now would break the
        zero-requests-dropped drain guarantee. Race-safe against cancel: a
        request resolved while it was in flight between ``drain`` and this
        call is skipped (returns False)."""
        if req.state == FINISHED:
            return False              # cancelled mid-requeue: nothing to do
        req.state = QUEUED
        req.slot = None
        t = req.submit_t if req.submit_t is not None else float("-inf")
        idx = len(self._q)
        for i, other in enumerate(self._q):
            if other.submit_t is not None and other.submit_t > t:
                idx = i
                break
        self._q.insert(idx, req)
        return True

    def cancel(self, req):
        """Remove a still-queued request; returns True if it was waiting."""
        try:
            self._q.remove(req)
            return True
        except ValueError:
            return False

    def qsize(self):
        return len(self._q)

    # -- expiry --------------------------------------------------------------
    def expire(self, now=None):
        """Remove and return every queued request whose deadline passed —
        called at EVERY step boundary (not just when a slot frees), so dead
        entries never inflate qsize()/backpressure while all slots are busy.
        Returned requests are already marked EXPIRED. Boundary semantics
        are ``Request.expired`` (``now >= deadline``) — the single
        predicate every expiry site shares."""
        now = time.perf_counter() if now is None else now
        expired = [r for r in self._q if r.state != FINISHED
                   and r.expired(now)]
        for req in expired:
            self._q.remove(req)
            req._finish(EXPIRED)
        return expired

    # -- admission -----------------------------------------------------------
    def _admission_order(self):
        """Live queued requests in admission order. FCFS mode returns the
        arrival order verbatim; priority mode orders best class first and,
        within a class, deficit-round-robins across tenants (arrival order
        within each tenant's lane). The rotation resumes after the
        class's last-served tenant, so fairness holds across boundaries,
        not just within one."""
        live = [r for r in self._q if r.state != FINISHED]
        if not self.priority or len(live) <= 1:
            return live
        by_class = {}
        for r in live:
            by_class.setdefault(r.class_rank, []).append(r)
        out = []
        for rank in sorted(by_class):
            out.extend(self._wfq_order(rank, by_class[rank]))
        return out

    def _wfq_order(self, rank, reqs):
        """Weighted fair order across lanes (tenants, or adapters under
        ``lane_key``) within one class."""
        lanes, keys = {}, []
        for r in reqs:                     # arrival order within each lane
            k = self.lane_key(r)
            if k not in lanes:
                keys.append(k)
                lanes[k] = deque()
            lanes[k].append(r)
        if len(keys) <= 1:
            return reqs
        last = self._wfq_last.get(rank)
        if last in keys:                   # resume AFTER the last-served
            i = keys.index(last) + 1
            keys = keys[i:] + keys[:i]
        out = []
        while lanes:
            for t in keys:
                lane = lanes.get(t)
                if lane is None:
                    continue
                # weights keyed by the lane key; non-string keys (adapter
                # ids) fall back to their string spelling so flag-file
                # weights ({"1": 2}) apply to integer lanes too
                w = self.tenant_weights.get(
                    t, self.tenant_weights.get(str(t), 1))
                for _ in range(w):
                    if not lane:
                        break
                    out.append(lane.popleft())
                if not lane:
                    del lanes[t]
        return out

    def admit(self, free_slots, now=None, fits=None):
        """Pop up to free_slots admissible requests in admission order
        (FCFS, or class-aware WFQ under ``priority``). Requests whose
        deadline already passed are popped, marked EXPIRED and returned
        separately (they never occupy a slot).

        ``fits`` is the paged engine's page-aware admission predicate: a
        candidate is admitted only when the page pool can hold its whole
        lifetime (prompt + max_new_tokens, minus prefix-shared pages) —
        admission is bounded by PAGES, not whole-Smax slots. A candidate
        that doesn't fit STOPS admission (strict in-order — no bypass, so
        admission order stays deterministic and starvation-free; in
        priority mode a stuck interactive head blocks batch behind it
        rather than inverting priority)."""
        now = time.perf_counter() if now is None else now
        admitted, expired = [], []
        if free_slots > 0:
            for req in self._admission_order():
                if len(admitted) >= free_slots:
                    break
                if req.expired(now):
                    self._q.remove(req)
                    req._finish(EXPIRED)
                    expired.append(req)
                    continue
                if fits is not None and not fits(req):
                    break
                self._q.remove(req)
                admitted.append(req)
                if self.priority:
                    self._wfq_last[req.class_rank] = self.lane_key(req)
        while self._q and self._q[0].state == FINISHED:
            # cancelled while queued (e.g. mid-requeue race where the
            # cancel lost the deque.remove): already resolved, drop
            self._q.popleft()
        return admitted, expired

    # -- SLO policy hooks (priority / shedding) ------------------------------
    def deadline_risk(self, now, margin):
        """The queued request most entitled to preempt: unexpired, has a
        deadline, and its slack (deadline - now) is within ``margin`` —
        i.e. it will miss its deadline unless it is admitted about now.
        Best class wins; earliest arrival breaks ties. None when nothing
        is at risk."""
        best = None
        for r in self._q:
            if r.state == FINISHED or r.deadline is None or r.expired(now):
                continue
            if r.deadline - now <= margin:
                key = (r.class_rank, r.submit_t if r.submit_t is not None
                       else float("inf"))
                if best is None or key < best[0]:
                    best = (key, r)
        return None if best is None else best[1]

    def shed(self, target_len, spare_rank=0):
        """Shed queued work down to ``target_len`` live entries, lowest
        class first and youngest arrival first within a class (the request
        that would have been served LAST goes first — the oldest, best
        work keeps its place). Requests of class rank <= ``spare_rank``
        are never shed (interactive degrades via deadlines, not drops).
        Shed requests are marked ``SHED`` and returned; the caller
        attaches the retry-after hint and resolves them."""
        live = [r for r in self._q if r.state != FINISHED]
        excess = len(live) - max(0, int(target_len))
        if excess <= 0:
            return []
        victims = sorted(
            (r for r in live if r.class_rank > spare_rank),
            key=lambda r: (-r.class_rank,
                           -(r.submit_t if r.submit_t is not None else 0.0)))
        shed = victims[:excess]
        for req in shed:
            self._q.remove(req)
            req._finish(SHED)
        return shed

    # -- snapshot ------------------------------------------------------------
    def drain_queue(self):
        """Pop and return every waiting request (engine drain/shutdown
        path); their ``submit_t`` is untouched so a resubmission elsewhere
        keeps the original arrival clock."""
        out = [r for r in self._q if r.state != FINISHED]
        self._q.clear()
        return out

    def queue_state(self):
        """Serializable snapshot of the wait queue (FCFS order)."""
        return [r.to_state() for r in self._q if r.state != FINISHED]

    def restore_queue(self, reqs):
        """Replace the wait queue with ``reqs`` (engine restore path)."""
        self._q = deque(reqs)
