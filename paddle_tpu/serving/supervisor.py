"""Elastic serving supervisor: N engine replicas behind a least-loaded
router, with heartbeat failure detection, snapshot respawn and request
replay (the serving mirror of ``distributed.elastic.ElasticAgent``).

The supervisor owns the self-healing contract the engine alone cannot
provide: ZERO requests dropped across replica death. Every submitted
request is tracked until its result is delivered; when a replica dies —
engine exception, simulated kill (``FaultPlan.kill_at_decode_step``), or a
stale heartbeat (frozen process) — the supervisor first tries to respawn
the replica from its last engine snapshot (``Engine.load_state_dict``;
mid-decode requests resume bitwise), cancels whatever the restored engine
would recompute that was already delivered, and REPLAYS on a surviving
replica anything the snapshot predates or — when the snapshot is stale,
corrupt or missing — everything the dead replica still owed. Replays are
*exactly* equivalent: the engine's bitwise-parity guarantee (any admission
order, greedy and sampled, both KV layouts) means a replayed request's
token stream is identical to the one the dead replica would have produced.

Replicas here are in-process ``Engine`` objects driven round-robin — the
deterministic CPU harness the chaos ladder needs. A multi-host deployment
runs one engine per TPU VM with the same CheckpointManager/Heartbeat
wiring (``Engine.run()`` installs the SIGTERM drain per process); the
supervisor logic is identical because every primitive it consumes
(snapshot dirs, heartbeat files) already lives on shared storage.

Rolling restart (``rolling_restart()``) drains one replica at a time —
in-flight requests requeued with their ORIGINAL arrival time and deadline
onto the surviving replicas — so the fleet upgrades with zero drops and
bounded queue-depth spill.
"""
from __future__ import annotations

import os
import threading
import time

from ..flags import get_flags
from ..observability import register_supervisor
from ..incubate.checkpoint import CheckpointManager, Preempted
from ..distributed.elastic import Heartbeat, HeartbeatMonitor
from ..utils.fault_injection import Preemption
from . import metrics
from .elastic import (  # noqa: F401  (mp_replica_meshes re-exported here)
    FleetTopology, degraded_count, mp_replica_meshes, record_reform,
    set_group_gauges,
)
from .engine import EngineStoppedError
from .request import CANCELLED, DROPPED, FINISHED, RUNNING, Request
from .scheduler import QueueFullError, ShedError
from .slo import Autoscaler, TokenBucket


class ChipLossError(RuntimeError):
    """A chip of this replica's mp group was lost (injected schedule or
    stale chip heartbeat): the whole group is down and must be re-formed
    over the survivors."""


class AuditFailure(RuntimeError):
    """A replica's shadow-audit suspicion score reached
    ``FLAGS_serving_audit_threshold``: its outputs diverged from the
    ``generate_from_params`` oracle repeatedly — silent state corruption
    (e.g. a finite KV bit flip the all-finite guard cannot see). The
    replica is failed over through the ordinary reform/respawn machinery
    before the corruption spreads through its prefix cache."""


class _Replica:
    """One supervised engine slot: the engine itself is replaceable (it
    dies and respawns), the snapshot manager and heartbeat are not."""

    def __init__(self, idx, mgr, hb):
        self.idx = idx
        self.mgr = mgr              # persistent CheckpointManager or None
        self.hb = hb                # persistent Heartbeat or None
        self.engine = None
        # "up" | "down" | "draining" (rolling restart mid-drain: alive but
        # UNROUTABLE — submit/spill/replay must not target it) |
        # "reforming" (topology-elastic: the mp group is being re-formed
        # over surviving chips — TEMPORARILY unroutable, comes back) |
        # "retired" (scaled down: permanently out of rotation, indices
        # stay stable)
        self.state = "down"
        self.restarts = 0
        self.last_error = None
        # disaggregated serving: the role this replica CURRENTLY runs
        # ("both" | "prefill" | "decode") and the one it was configured
        # with — they diverge while a chip loss has the fleet rebalanced
        # (a prefill worker covering for dead decode capacity flips back
        # once configured decode capacity is routable again)
        self.role = "both"
        self.configured_role = "both"
        # topology-elastic state (None/0 when the supervisor is not in
        # elastic mode): the mesh the CURRENT engine runs on, its mp
        # degree and its global chip ranks
        self.mesh = None
        self.mp = 0
        self.group = ()
        self.chip_lost = False      # down specifically for lost chips
        # spaced retry of a reform whose spawn/restore keeps failing:
        # boundaries left to skip, and the (doubling) next skip length
        self.reform_wait = 0
        self.reform_backoff = 0

    @property
    def routable(self):
        """Safe as a routing/replay target: up AND its engine accepts
        work (a drained engine raises EngineStoppedError on submit even
        while the replica object still says "up")."""
        # single read of engine: a reform on the supervising thread
        # nulls it concurrently with router threads
        eng = self.engine
        return self.state == "up" and eng is not None and not eng.stopped

    @property
    def load(self):
        # single read of engine: a reform on the supervising thread nulls
        # it concurrently with router threads sorting by load — a nulled
        # replica sorts last and the router's in-loop None guard skips it
        eng = self.engine
        if eng is None:
            return float("inf")
        # fold the in-flight PREFILL BACKLOG in (normalized to chunk
        # boundaries — the unit a queued arrival actually waits behind):
        # queue depth + occupied slots alone make a replica grinding
        # through a giant mid-prefill prompt look as idle as one decoding
        # short tails, and the router would pile new prompts onto it
        backlog = eng.prefill_backlog()
        if backlog:
            chunk = getattr(eng, "prefill_chunk", 0) or 1
            return eng.queue_depth + eng.active_slots + backlog / chunk
        return eng.queue_depth + eng.active_slots


class ServingSupervisor:
    """Run ``num_replicas`` engines from ``engine_factory`` (a zero-arg
    callable returning a fresh, identically-configured ``Engine``) behind
    a least-queue-depth router::

        sup = ServingSupervisor(lambda: Engine(params=p, config=cfg),
                                num_replicas=2, snapshot_dir=tmp)
        for r in requests:
            sup.submit(r)
        results = sup.run()        # {request_id: GenerationResult}

    ``snapshot_dir`` enables per-replica engine snapshots through the
    hardened checkpoint path (cadence ``snapshot_every`` /
    ``FLAGS_serving_snapshot_every``); ``heartbeat_dir`` enables
    liveness monitoring (a replica whose file goes stale past
    ``heartbeat_timeout`` is failed over even though its process never
    raised). ``max_restarts`` bounds respawns per replica; past it the
    replica stays down and its work is replayed on the survivors.

    ``mp=N`` turns the supervisor TOPOLOGY-ELASTIC (serving/elastic.py):
    each replica is an mp GROUP of N chips (``devices`` defaults to the
    first ``num_replicas * N`` of ``jax.devices()``), watched at CHIP
    granularity — injected ``FaultPlan.serving_chip_loss_at`` schedules
    and, with ``heartbeat_dir``, per-chip heartbeat staleness. One lost
    chip marks its whole group down; the group re-forms over its
    surviving chips at the largest viable mp degree and respawns through
    the MP-PORTABLE snapshot path (mid-decode requests resume bitwise on
    the smaller group; the rest replays — zero drops). When the chips
    return the group grows back from a live snapshot
    (``FLAGS_serving_elastic_grow``) with zero drops and zero new traces
    (engine builders are memoized per (cfg, mesh, rung)). The factory
    must take ``(replica_idx, mesh)``. While a group is mid-reform the
    router treats it as temporarily unroutable; shed watermarks and the
    autoscaler read live ROUTABLE capacity, so a degraded fleet sheds
    and scales against what can actually serve.
    """

    def __init__(self, engine_factory, num_replicas=2, *, snapshot_dir=None,
                 snapshot_every=None, max_restarts=None, heartbeat_dir=None,
                 heartbeat_timeout=None, autoscale=None, tenant_rate=None,
                 tenant_burst=None, mp=None, devices=None,
                 elastic_grow=None, roles=None, audit_ref=None):
        flags = get_flags()
        self.engine_factory = engine_factory
        # -- sampled shadow audit (FLAGS_serving_audit_rate): replay that
        # fraction of finished greedy requests through the
        # generate_from_params oracle and bitwise-compare tokens. Needs
        # ``audit_ref=(raw_params, config)`` — the engine transforms its
        # own params at construction (logical-qkv / mp-shard / quantize),
        # so the supervisor keeps an untransformed reference copy.
        self._audit_ref = audit_ref
        self._audit_rate = float(
            flags.get("FLAGS_serving_audit_rate", 0.0) or 0.0)
        self._audit_threshold = max(1, int(
            flags.get("FLAGS_serving_audit_threshold", 2)))
        self._audit_warned = False
        # -- disaggregated prefill/decode serving (serving/kv_transfer.py):
        # ``roles`` assigns each replica a serving role — "prefill"
        # workers run only the big-chunk rungs over all their slots and
        # stream finished KV pages to a decode worker; "decode"/"both"
        # replicas install the pages between decode boundaries and emit
        # tokens. With roles unset the supervisor is the plain fleet,
        # byte-identical.
        self._roles = None
        self._disagg = False
        self._affinity_routing = bool(
            flags.get("FLAGS_serving_affinity_routing", True))
        self._transfers = {}         # rid -> in-flight KVTransfer
        self._assign = {}            # rid -> decode replica idx (target)
        self._transfer_src = {}      # rid -> prefill replica idx (source)
        if roles is not None:
            roles = tuple(str(r) for r in roles)
            if len(roles) != int(num_replicas):
                raise ValueError(
                    f"roles has {len(roles)} entries for "
                    f"{int(num_replicas)} replicas")
            for r in roles:
                if r not in ("both", "prefill", "decode"):
                    raise ValueError(
                        f"role must be 'both', 'prefill' or 'decode', "
                        f"got {r!r}")
            if all(r == "prefill" for r in roles):
                raise ValueError(
                    "a disaggregated fleet needs at least one decode-"
                    "capable replica ('decode' or 'both'): prefill "
                    "workers never emit tokens")
            self._roles = roles
            self._disagg = any(r == "prefill" for r in roles)
        self._factory_arity = None       # lazily inspected (_call_factory)
        self.snapshot_every = snapshot_every
        # -- topology-elastic mode (serving/elastic.py): ``mp`` makes each
        # replica an mp GROUP the supervisor watches at CHIP granularity.
        # A lost chip (injected schedule or stale per-chip heartbeat)
        # marks its whole group down; the group is re-formed over its
        # surviving chips at the largest viable mp degree and respawned
        # through the mp-portable snapshot path (bitwise resume). When
        # chips return the group grows back from a LIVE snapshot (zero
        # drops, memoized builders → zero new traces). With ``mp`` unset
        # the supervisor is the plain PR 7/10 fleet, byte-identical.
        self._topology = None
        self._topo_step = 0
        self._configured_mp = 0
        self._elastic_grow = (bool(flags.get("FLAGS_serving_elastic_grow",
                                             True))
                              if elastic_grow is None else bool(elastic_grow))
        self._reform_retries = int(
            flags.get("FLAGS_serving_reform_retries", 2))
        if mp is not None:
            self._configured_mp = int(mp)
            self._topology = FleetTopology(
                devices, self._configured_mp, num_replicas,
                heartbeat_dir=heartbeat_dir,
                heartbeat_timeout=heartbeat_timeout)
            # liveness is per-CHIP in elastic mode (the topology monitor
            # supersedes per-replica heartbeats: a stale chip takes its
            # group down through the reform path, not the failover path)
            heartbeat_dir = None
        self.max_restarts = int(
            max_restarts if max_restarts is not None
            else flags.get("FLAGS_serving_max_restarts", 3))
        # stored so autoscale-grown replicas get the same snapshot/
        # heartbeat wiring the constructor-built ones did
        self._snapshot_dir = snapshot_dir
        self._heartbeat_dir = heartbeat_dir
        self._heartbeat_timeout = heartbeat_timeout
        # hot-swap state: once rolling_restart(new_params=) upgrades the
        # fleet, EVERY later spawn (respawn-after-crash, autoscale grow)
        # serves the new weights — a crash must not resurrect old ones
        self._live_params = None          # (params_tree, version) or None
        self._upgrading = False           # inside rolling_restart(new_params)
        # many-model serving: the fleet's LIVE adapter set (adapter_id ->
        # (tree, alpha)) — the adapter mirror of _live_params. Every later
        # spawn (crash respawn, chip-loss reform, rolling restart,
        # autoscale grow) re-applies it, and a restored snapshot is
        # reconciled against it, so a crash can never resurrect a stale
        # adapter set. Maintained by the fleet-level load_adapter/
        # evict_adapter/swap_adapter below.
        self._live_adapters = {}
        # per-tenant token buckets at the router (ShedError over-rate)
        rate = (flags.get("FLAGS_serving_tenant_rate", 0.0)
                if tenant_rate is None else tenant_rate)
        burst = (flags.get("FLAGS_serving_tenant_burst", 8)
                 if tenant_burst is None else tenant_burst)
        self._tenant_rate = float(rate)
        self._tenant_burst = float(burst)
        self._buckets = {}                # tenant -> TokenBucket
        # telemetry-driven autoscaling (policy: serving/slo.py Autoscaler;
        # actions ride the existing spawn/drain machinery and are applied
        # on the supervising thread at step boundaries only)
        if autoscale is None:
            autoscale = bool(flags.get("FLAGS_serving_autoscale", False))
        if isinstance(autoscale, Autoscaler):
            self.autoscaler = autoscale
        elif autoscale:
            self.autoscaler = Autoscaler.from_flags(flags)
        else:
            self.autoscaler = None
        # One RLock guards the shared TRACKING state (requests/owner/
        # results/delivered) — the same discipline as the serving metrics
        # ledger's module lock — so monitoring threads (telemetry()
        # gauges, pending(), results(), a Prometheus scrape) read a
        # consistent view while the supervision loop runs. The engines
        # themselves are NOT thread-safe: submit()/cancel()/step() must
        # stay on the supervising thread (a router hands work to that
        # thread; it does not call into the engines concurrently).
        self._lock = threading.RLock()
        self._requests = {}          # request_id -> latest live Request
        self._owner = {}             # request_id -> replica idx
        self._results = {}           # request_id -> GenerationResult (1st wins)
        self._delivered = set()      # popped rids: dedup survives pop_results
        self._replicas = []
        for i in range(int(num_replicas)):
            self._replicas.append(self._new_replica(i))
        self.monitor = None
        self._remake_monitor()
        # live per-replica gauges in the metrics registry ("supervisor"
        # family; weakly referenced — dies with this object)
        register_supervisor(self)

    def _new_replica(self, i):
        """Build replica slot ``i`` (constructor AND autoscale-grow path):
        persistent snapshot manager + heartbeat, engine spawned up."""
        mgr = None
        if self._snapshot_dir is not None:
            mgr = CheckpointManager(
                os.path.join(os.fspath(self._snapshot_dir), f"replica_{i}"),
                async_save=False, site="serving_snapshot")
        hb = None
        if self._heartbeat_dir is not None:
            hb = Heartbeat(self._heartbeat_dir, rank=i)
        rep = _Replica(i, mgr, hb)
        if self._roles is not None and i < len(self._roles):
            rep.configured_role = rep.role = self._roles[i]
        if self._topology is not None:
            if i >= self._topology.num_replicas:
                raise ValueError(
                    f"cannot grow replica {i}: the elastic fleet topology "
                    f"was sized for {self._topology.num_replicas} mp="
                    f"{self._configured_mp} groups (autoscale growth needs "
                    f"spare chips the topology does not have)")
            rep.mp, rep.group = self._topology.plan(i, frozenset())
            rep.mesh = self._topology.mesh_for(rep.group)
        rep.engine = self._spawn_engine(rep)
        rep.state = "up"
        if hb is not None:
            hb.beat()
        return rep

    def _remake_monitor(self):
        """(Re)build the heartbeat monitor over the CURRENT replica count
        — called at construction and after an autoscale grow, so new
        replicas are liveness-checked too."""
        if self._heartbeat_dir is None:
            return
        timeout = (self._heartbeat_timeout
                   if self._heartbeat_timeout is not None
                   else get_flags().get("FLAGS_serving_heartbeat_timeout",
                                        10.0))
        self.monitor = HeartbeatMonitor(self._heartbeat_dir,
                                        world_size=len(self._replicas),
                                        timeout=float(timeout))

    def _spawn_engine(self, rep):
        eng = self._call_factory(rep)
        eng.tag = f"replica{rep.idx}"
        if rep.role != "both":
            # the replica's CURRENT role (configured, or rebalanced after
            # a chip loss): applied while the fresh engine is idle — the
            # only window set_role allows
            eng.set_role(rep.role)
        if self._live_params is not None:
            # the fleet was hot-upgraded: every spawn — crash respawn,
            # rolling restart, autoscale grow — serves the LIVE weights.
            # Only the upgrade itself counts as a weight swap; later
            # re-applications on respawn/grow are not new swaps
            params, version = self._live_params
            eng.swap_params(params, version=version,
                            count=self._upgrading)
        self._sync_adapters(eng)
        if rep.mgr is not None:
            eng.attach_checkpoint(rep.mgr, every=self.snapshot_every)
        return eng

    def _call_factory(self, rep):
        """Invoke the engine factory — one-arg factories receive the
        replica index (the tensor-parallel deployment shape: each replica
        builds its engine on its OWN mp device group, see
        ``mp_replica_meshes``); zero-arg factories keep the PR 7
        contract unchanged. In topology-elastic mode the factory MUST
        take ``(idx, mesh)`` — the mesh changes across reforms, so a
        factory that bakes its own mesh cannot follow the topology."""
        if self._factory_arity is None:
            try:
                import inspect
                sig = inspect.signature(self.engine_factory)
                self._factory_arity = sum(
                    1 for p in sig.parameters.values()
                    if p.kind in (p.POSITIONAL_ONLY,
                                  p.POSITIONAL_OR_KEYWORD))
            except (TypeError, ValueError):
                self._factory_arity = 0
            if self._topology is not None and self._factory_arity < 2:
                raise TypeError(
                    "a topology-elastic supervisor (mp=...) needs a "
                    "two-arg engine factory (replica_idx, mesh): the mesh "
                    "changes when the group re-forms over surviving chips")
        if self._factory_arity >= 2 and self._topology is not None:
            return self.engine_factory(rep.idx, rep.mesh)
        if self._factory_arity >= 1:
            return self.engine_factory(rep.idx)
        return self.engine_factory()

    def _sync_adapters(self, eng):
        """Reconcile an engine's resident adapter set with the fleet's
        LIVE one. A fresh spawn carries nothing and just loads the live
        set; a restored snapshot may PREDATE a fleet-level load/evict/
        swap, so residents the fleet has since evicted are dropped and
        live adapters are re-applied (content rewrite, zero retraces).
        All re-application, never new ops — ``count=False`` keeps the
        ledger counting each fleet-level op exactly once, at apply time.

        An adapter bound to a restored RUNNING slot is left untouched:
        the resumed stream keeps the delta bits it started under (the
        same mid-stream guarantee ``_check_adapter_unbound`` enforces on
        live engines); the next fleet-level op re-syncs it once the
        slot frees."""
        reg = getattr(eng, "adapters", None)
        if reg is None:
            return
        for aid in list(reg.resident_ids()):
            if aid not in self._live_adapters:
                try:
                    eng.evict_adapter(aid, count=False)
                except RuntimeError:
                    pass              # bound mid-stream: keep its bits
        for aid, (tree, alpha) in self._live_adapters.items():
            if reg.resident(aid) and aid != 0:
                try:
                    eng.evict_adapter(aid, count=False)
                except RuntimeError:
                    continue          # bound mid-stream: keep its bits
            eng.load_adapter(aid, tree, alpha=alpha, count=False)

    # -- routing -------------------------------------------------------------
    def _up(self):
        return [r for r in self._replicas if r.state == "up"]

    def _routable(self):
        """Replicas that may receive NEW or replayed work: up and not
        mid-drain (a rolling restart marks the replica "draining" and its
        engine refuses submissions — routing there used to slip through
        because the spill check only compared queue depth)."""
        return [r for r in self._replicas if r.routable]

    def _pick(self, exclude=None):
        ups = [r for r in self._routable() if r is not exclude]
        if not ups:
            return None
        return min(ups, key=lambda r: (r.load, r.idx))

    def _pick_decode(self, exclude=None):
        """Least-loaded routable DECODE-CAPABLE replica (transfer targets
        and in-transfer re-offers must never land on a prefill worker)."""
        ups = [r for r in self._routable()
               if r.role != "prefill" and r is not exclude]
        if not ups:
            return None
        return min(ups, key=lambda r: (r.load, r.idx))

    def _route_disagg(self, request, ups):
        """Role- and cache-aware candidate order for a disaggregated
        fleet. Returns ``(candidates, affinity_rep)``:

        1. PREFIX AFFINITY — when a decode-capable replica's prefix cache
           already covers the prompt to within one page (the engine's
           exact-hit re-forward handles the tail), route STRAIGHT to it:
           the prefill compute AND the transfer are skipped entirely.
           Best coverage wins; load breaks ties.
        2. Sub-page prompts (nothing cached anywhere) also go straight to
           a decode worker — a one-page handoff costs more than the one
           chunk it saves — but are NOT counted as affinity hits.
        3. Otherwise prefill workers by load (the handoff pipeline), then
           decode-capable replicas as spill.
        4. No routable prefill worker at all -> pure-decode fallback (the
           decode-capable replicas chunk-prefill locally, exactly like a
           plain fleet) — counted, it signals degraded disaggregation."""
        prefills = [r for r in ups if r.role == "prefill"]
        decodes = [r for r in ups if r.role != "prefill"]
        affinity = None
        short = False
        if self._affinity_routing and decodes:
            plen = request.prompt_len
            best = None
            for r in decodes:
                eng = r.engine
                if eng is None:
                    continue
                cov = eng.prefix_coverage(request.prompt)
                if cov > 0 and plen - cov <= eng.page_size:
                    key = (-cov, r.load, r.idx)
                    if best is None or key < best[0]:
                        best = (key, r)
                elif plen <= eng.page_size:
                    short = True
            if best is not None:
                affinity = best[1]
        if affinity is not None:
            order = [affinity] + [r for r in prefills + decodes
                                  if r is not affinity]
        elif short:
            order = sorted(decodes, key=lambda r: (r.load, r.idx)) + prefills
        elif prefills:
            order = prefills + decodes
        else:
            if decodes:
                metrics.bump("disagg_fallbacks")
            order = decodes
        return order, affinity

    def _rate_limit(self, request):
        """Per-tenant token bucket at the router: over-rate submissions
        are refused with ``ShedError`` carrying the exact time until the
        tenant's next token accrues — tenant isolation BEFORE the queues,
        so one tenant's flood cannot fill every replica's queue and starve
        the others into QueueFullError."""
        if self._tenant_rate <= 0:
            return
        with self._lock:
            # bucket creation AND take under the supervisor lock: router
            # threads submit concurrently (the documented concurrency
            # surface), and an unlocked read-modify-write of the token
            # count would let a tenant exceed rate*t + burst
            bucket = self._buckets.get(request.tenant)
            if bucket is None:
                bucket = self._buckets[request.tenant] = TokenBucket(
                    self._tenant_rate, self._tenant_burst)
            wait = bucket.take()
            if len(self._buckets) > 1024:
                # tenant ids are client-supplied strings: without a sweep
                # a rotating/adversarial id stream grows the map forever.
                # A refilled-to-burst bucket is indistinguishable from a
                # fresh one, so dropping it changes no admission decision.
                now = time.perf_counter()
                for t, b in list(self._buckets.items()):
                    if b is not bucket and b.idle_full(now):
                        del self._buckets[t]
        if wait > 0:
            metrics.bump("rate_limited")
            raise ShedError(
                f"tenant {request.tenant!r} over rate limit "
                f"({self._tenant_rate:.1f} req/s, burst "
                f"{self._tenant_burst:.0f}); retry in ~{wait:.2f}s",
                qsize=self.fleet_queue_depth(),
                max_queue=self.fleet_max_queue(), retry_after=wait)

    def fleet_queue_depth(self):
        # single read of rep.engine per replica: a reform on the
        # supervising thread nulls it concurrently with router threads
        engines = [r.engine for r in self._replicas]
        return sum(e.queue_depth for e in engines if e is not None)

    def fleet_max_queue(self):
        engines = [r.engine for r in self._routable()]
        return sum(e.scheduler.max_queue for e in engines if e is not None)

    def _reform_hint(self):
        """retry-after estimate while the fleet is mid-reform: the last
        observed reform latency (elastic ledger), floored/capped to a
        sane backoff window. None when nothing is reforming."""
        reforming = False
        for r in self._replicas:
            eng = r.engine            # single read (reform race, above)
            if r.state == "reforming" or (eng is not None
                                          and eng._reforming):
                reforming = True
                break
        if not reforming:
            return None
        return self._last_reform_latency()

    def submit(self, request):
        """Route a request to the least-loaded routable replica (spilling
        to the next when its queue is full; ``QueueFullError`` — with
        FLEET-WIDE ``qsize``/``max_queue`` totals as its back-off hints —
        only once EVERY replica is saturated). Draining/stopped replicas
        are never targeted; a replica MID-REFORM is temporarily
        unroutable, not dead — with every replica reforming the router
        backs off (bounded retries with a deterministic per-request
        jitter) and only then raises ``EngineStoppedError`` with
        ``reforming=True`` and a ``retry_after`` hint. Raises plain
        ``EngineStoppedError`` when the fleet is genuinely dead,
        ``ShedError`` when the tenant is over its rate limit."""
        if not isinstance(request, Request):
            request = Request(request)
        for attempt in range(self._reform_retries + 1):
            ups = sorted(self._routable(), key=lambda r: (r.load, r.idx))
            if ups:
                break
            hint = self._reform_hint()
            if hint is None:
                raise EngineStoppedError(
                    "no live serving replica", queue_depth=0, requeued=())
            if attempt >= self._reform_retries:
                raise EngineStoppedError(
                    f"every replica is mid-reform (chip loss/return); "
                    f"retry in ~{hint:.2f}s", queue_depth=0, requeued=(),
                    reforming=True, retry_after=hint)
            # bounded jittered backoff: deterministic per request (id-
            # derived jitter in [0.5, 1.0)), so a thundering herd of
            # routers desynchronizes without wall-clock randomness
            time.sleep(min(hint, 0.25)
                       * (0.5 + (request.request_id % 8) / 16.0))
        self._rate_limit(request)
        affinity = None
        if self._disagg:
            ups, affinity = self._route_disagg(request, ups)
        shedding = []
        stopped_midway = 0
        for rep in ups:
            # saturation probes, not trial submits: a failed Engine.submit
            # bumps the global submitted/rejected/shed ledger, so spilling
            # by try/except would count one logical request once per full
            # (or shed-latched) replica and skew the SLO surface. Shed
            # state is PER-ENGINE — a replica latched in overload is
            # skipped and the request spills to a healthy one.
            eng = rep.engine
            if eng is None:
                # a reform nulled the engine after the routable snapshot
                # (router threads vs the supervising thread): temporarily
                # unroutable, same as a mid-reform stop
                stopped_midway += 1
                continue
            shed = eng._shed
            if shed is not None and shed.shedding \
                    and request.class_rank >= 2:
                shedding.append(eng)    # the engine object: reform-safe
                continue
            if eng.queue_depth < eng.scheduler.max_queue:
                rid = request.request_id
                # register ownership BEFORE the engine accepts the work: a
                # group reform landing between a successful submit and a
                # later owner-map write could not see this request in
                # _unacked_of and would restore a snapshot predating it —
                # owned by nobody, hosted by nobody, pending forever
                with self._lock:
                    self._requests[rid] = request
                    self._owner[rid] = rep.idx
                try:
                    eng.submit(request)
                except EngineStoppedError:
                    # stopped between the probe and the submit (a reform/
                    # drain on another thread): temporarily unroutable,
                    # not dead — spill to the next candidate. Undo only
                    # OUR registration: a reform that already saw it has
                    # replayed a copy and re-homed the maps, and that
                    # copy IS the routed request.
                    with self._lock:
                        rerouted = not (
                            self._requests.get(rid) is request
                            and self._owner.get(rid) == rep.idx)
                        if not rerouted:
                            del self._requests[rid]
                            del self._owner[rid]
                    if rerouted:
                        break
                    stopped_midway += 1
                    continue
                except BaseException:
                    with self._lock:
                        if self._requests.get(rid) is request \
                                and self._owner.get(rid) == rep.idx:
                            del self._requests[rid]
                            del self._owner[rid]
                    raise
                break
        else:
            if ups and stopped_midway == len(ups):
                # EVERY candidate stopped between the routable() snapshot
                # and its submit (the fleet went mid-reform under us):
                # surface the typed temporary error, never a bogus
                # saturation hint computed from now-empty queues
                hint = self._reform_hint()
                if hint is not None:
                    raise EngineStoppedError(
                        f"every replica went mid-reform while routing; "
                        f"retry in ~{hint:.2f}s", queue_depth=0,
                        requeued=(), reforming=True, retry_after=hint)
                raise EngineStoppedError(
                    "no live serving replica", queue_depth=0, requeued=())
            # fleet-wide totals: the backoff a client derives from the
            # hint must reflect every queue it competes with, not whatever
            # replica happened to be probed last
            qsize, cap = self.fleet_queue_depth(), self.fleet_max_queue()
            if shedding:
                # every candidate was latched or full: refuse with the
                # largest (most honest) drain hint across latched replicas
                metrics.bump("shed")
                raise ShedError(
                    f"shedding {request.priority} traffic fleet-wide "
                    f"({qsize}/{cap} waiting); retry later",
                    qsize=qsize, max_queue=cap,
                    retry_after=max(
                        e._shed.retry_after(e.queue_depth)
                        for e in shedding))
            # the hint must not claim pure saturation when part of the
            # fleet is actually mid-reform and about to come back
            reform_note = (f"; {stopped_midway} replica(s) mid-reform"
                           if stopped_midway else "")
            raise QueueFullError(
                f"all {len(ups) - stopped_midway} routable replica queues "
                f"full ({qsize}/{cap} waiting fleet-wide{reform_note}); "
                f"retry later", qsize=qsize, max_queue=cap)
        if affinity is not None and rep is affinity:
            # the shared-prefix prompt landed on the replica that already
            # holds its pages: no prefill-worker compute, no KV transfer
            metrics.bump("affinity_hits")
            if request.trace is not None:
                request.trace.instant("affinity_route", replica=rep.idx)
        return request

    def _acked(self, rid):
        with self._lock:
            return rid in self._results or rid in self._delivered

    def cancel(self, request):
        """Cancel wherever the request currently lives (race-safe against
        drain/replay: a request caught between the two resolves as
        cancelled here — delivering its result immediately — and is
        skipped by any later requeue)."""
        rid = request.request_id
        if self._acked(rid):
            return
        with self._lock:
            live = self._requests.get(rid, request)
            owner = self._owner.get(rid)
            tr = self._transfers.get(rid) if self._disagg else None
        if tr is not None and live.state == RUNNING and live.slot is None:
            # cancelled MID-TRANSFER: the request occupies no slot on any
            # engine (the prefill worker freed or never finished its slot,
            # the decode worker has not seated it) — abort the stream and
            # route the cancel to the install side so staged pages return
            tr.aborted = True
            tgt = self._assign.get(rid)
            teng = (self._replicas[tgt].engine
                    if tgt is not None else None)
            if teng is not None and teng.has_transfer(rid):
                teng.cancel(live)
                return
            live._finish(CANCELLED)
            metrics.bump("cancelled")
            with self._lock:
                self._results[rid] = live.result()
            return
        rep = self._replicas[owner] if owner is not None else None
        # single read of .engine: a group reform (or crash failover) on
        # the supervising thread nulls it between a state check and the
        # dereference — fall through to the direct resolve instead
        eng = rep.engine if rep is not None and rep.state == "up" else None
        if eng is not None:
            eng.cancel(live)
        elif live.state != FINISHED:
            # owner down / mid-replay: resolve directly so pending() drains
            live._finish(CANCELLED)
            metrics.bump("cancelled")
            with self._lock:
                self._results[rid] = live.result()

    # -- the supervision loop ------------------------------------------------
    def step(self):
        """One supervision round: step every live replica one engine
        iteration (heartbeating it), fail over replicas that died or went
        stale, collect results. Returns True while undelivered requests
        remain."""
        if self._topology is not None:
            self._poll_topology()
        for rep in self._replicas:
            if rep.state != "up":
                continue
            try:
                rep.engine.step()
            except (Preemption, Preempted, Exception) as e:  # noqa: BLE001
                # abrupt death: results resolved DURING the dying step are
                # lost with the process (never read from a dead engine) —
                # recovery recomputes them from snapshot/replay
                self._on_failure(rep, e)
            else:
                self._collect(rep)
                if rep.hb is not None:
                    try:
                        rep.hb.beat(step=rep.engine._step_count)
                    except OSError:
                        # transient heartbeat-file IO is NOT engine death:
                        # the file just ages, and only the monitor's
                        # staleness timeout may eventually fail this
                        # replica over — don't burn its restart budget
                        pass
        if self.monitor is not None:
            for rank in self.monitor.failed_ranks():
                rep = self._replicas[rank]
                if rep.state == "up":
                    metrics.bump("stale_failovers")
                    self._on_failure(rep, RuntimeError(
                        f"stale heartbeat (replica {rank})"))
        if self._disagg:
            self._pump_transfers()
            self._rebalance_roles()
        if self.autoscaler is not None:
            self._autoscale_step()
        return self.pending() > 0

    # -- disaggregated prefill/decode: transfer routing, role balance --------
    def _pump_transfers(self):
        """One transfer-routing round: collect streams the prefill
        workers opened since last round, assign each to the least-loaded
        decode-capable replica, and reconcile every in-flight stream —
        seated streams flip ownership and drop, aborted ones drop,
        failed ones replay, orphaned ones (their target died mid-install)
        re-offer the RETAINED host payloads to a survivor."""
        for rep in self._replicas:
            eng = rep.engine
            if rep.state != "up" or eng is None or eng.role != "prefill":
                continue
            for tr in eng.take_outbound():
                rid = tr.request_id
                with self._lock:
                    self._transfers[rid] = tr
                self._transfer_src[rid] = rep.idx
                target = self._pick_decode()
                if target is not None:
                    target.engine.offer_transfer(tr)
                    self._assign[rid] = target.idx
        for rid, tr in list(self._transfers.items()):
            req = tr.request
            if tr.seated:
                # terminal success: the decode replica hosts the request
                # now — its death replays there, not at the prefill source
                tgt = self._assign.get(rid)
                if tgt is not None:
                    with self._lock:
                        self._owner[rid] = tgt
                self._drop_transfer(rid)
                continue
            if tr.aborted or req.state == FINISHED or self._acked(rid):
                # handled elsewhere (cancel/expire/shed/drain): the normal
                # resolution path owns it — no replay from here
                self._drop_transfer(rid)
                continue
            if tr.failed:
                # the stream is unusable but the request is live (e.g.
                # params_version moved mid-flight): single-version replay
                self._drop_transfer(rid)
                self._replay([rid])
                continue
            tgt = self._assign.get(rid)
            rep_t = self._replicas[tgt] if tgt is not None else None
            if rep_t is None or not rep_t.routable \
                    or rep_t.engine is None \
                    or not rep_t.engine.has_transfer(rid):
                # no target yet, or it died/drained mid-install: the page
                # payloads are retained host-side until seated — re-offer
                # the SAME stream to a survivor (no prompt recompute)
                target = self._pick_decode()
                if target is None:
                    continue          # fleet degraded: retry next round
                target.engine.offer_transfer(tr)
                self._assign[rid] = target.idx
            if tr.done:
                # every page is host-resident and the prefill worker has
                # freed its slot: from here the DECODE side owns delivery
                with self._lock:
                    self._owner[rid] = self._assign[rid]

    def _drop_transfer(self, rid):
        with self._lock:
            self._transfers.pop(rid, None)
        self._assign.pop(rid, None)
        self._transfer_src.pop(rid, None)

    def _drop_transfers_for(self, rep):
        """Reconcile in-flight transfers against replica ``rep`` going
        away (crash, stale heartbeat, chip loss, reform, role flip)."""
        if not self._disagg:
            return
        for rid, tr in list(self._transfers.items()):
            if self._transfer_src.get(rid) == rep.idx and not tr.done:
                # the SOURCE died mid-stream: the remaining pages can
                # never arrive — abort (the decode side returns its
                # staged pages) and let the caller's replay recompute
                tr.aborted = True
                self._drop_transfer(rid)
            elif self._assign.get(rid) == rep.idx:
                # the TARGET died: payloads survive on the host — unassign
                # and let the pump re-offer the stream to a survivor
                self._assign.pop(rid, None)

    def _rebalance_roles(self):
        """Role elasticity under chip loss: decoding must never stall —
        when no decode-capable replica is routable, the least-loaded live
        prefill worker flips to decode (drain + respawn, zero drops); it
        flips back to its configured role once native decode capacity is
        routable again."""
        ups = self._routable()
        decodes = [r for r in ups if r.role != "prefill"]
        prefills = [r for r in ups if r.role == "prefill"]
        if not decodes and prefills:
            rep = min(prefills, key=lambda r: (r.load, r.idx))
            self._set_replica_role(rep, "decode")
            metrics.bump("role_rebalances")
        elif decodes and not prefills:
            conv = [r for r in decodes if r.configured_role == "prefill"]
            native = [r for r in decodes if r.configured_role != "prefill"]
            if conv and native:
                rep = min(conv, key=lambda r: (r.load, r.idx))
                self._set_replica_role(rep, rep.configured_role)
                metrics.bump("role_rebalances")

    def _set_replica_role(self, rep, role):
        """Flip a replica's serving role through a drain (the only safe
        window — set_role refuses non-idle engines): in-flight work
        requeues on the survivors (original arrival kept, zero drops),
        the engine respawns in the new role (builders memoized — zero
        new traces over warm shapes)."""
        if rep.role == role:
            return
        eng = rep.engine
        if eng is None or rep.state != "up":
            rep.role = role          # applied at the next spawn
            return
        self._drop_transfers_for(rep)
        rep.state = "draining"
        drained = eng.drain()
        self._collect(rep)
        rep.role = role
        rep.engine = self._spawn_engine(rep)
        rep.state = "up"
        metrics.bump("respawns")
        if rep.hb is not None:
            rep.hb.beat(status="running")
        for req in drained:
            if req.state == FINISHED:
                continue
            target = self._requeue_target(req, exclude=rep) or rep
            target.engine.requeue(req)
            with self._lock:
                self._owner[req.request_id] = target.idx

    # -- telemetry-driven autoscaling ----------------------------------------
    def _autoscale_step(self):
        """Evaluate the autoscale policy against the live fleet gauges
        (queue depth, slot occupancy, TTFT p99 — the PR 9 surface) and
        apply at most one action. Runs on the supervising thread at a step
        boundary, so growth/shrink can never tear an engine mid-dispatch;
        hysteresis windows and the cooldown live in the policy object.

        Counts LIVE ROUTABLE capacity, not the configured replica count:
        a fleet degraded by a chip loss (groups down or mid-reform) has
        genuinely less capacity, and the policy must see the queue
        pressure against what can actually serve right now."""
        ups = self._routable()
        if not ups:
            return
        action = self.autoscaler.decide(
            alive=len(ups),
            queue_depth=sum(r.engine.queue_depth for r in ups),
            active_slots=sum(r.engine.active_slots for r in ups),
            total_slots=sum(r.engine.num_slots for r in ups),
            ttft_p99=metrics.recent_ttft_p99())
        if action == "grow":
            self._grow_replica()
        elif action == "shrink":
            self._shrink_replica()

    def _grow_replica(self):
        """Scale up: append a fresh replica (same snapshot/heartbeat
        wiring, live weights) and extend the liveness monitor over it.
        A topology-elastic fleet cannot grow past the chip groups it was
        sized for — growth there is the chips RETURNING (grow-back), not
        new replicas."""
        if self._topology is not None \
                and len(self._replicas) >= self._topology.num_replicas:
            return
        rep = self._new_replica(len(self._replicas))
        self._replicas.append(rep)
        self._remake_monitor()
        metrics.bump("scale_ups")

    def _shrink_replica(self):
        """Scale down: drain the least-loaded replica (its in-flight work
        requeued on the survivors with ORIGINAL arrival — the rolling-
        restart machinery, zero drops) and retire the slot. Indices stay
        stable, so owner bookkeeping and heartbeat ranks never shift.

        A topology-elastic fleet never retires a chip group this way:
        _grow_replica cannot re-create one past the topology (retirement
        would be IRREVERSIBLE — healthy chips pinned idle forever), so
        there capacity follows the chips (reform/grow-back), and the
        autoscaler's shrink decision is a no-op."""
        if self._topology is not None:
            return
        ups = self._up()
        if len(ups) <= 1:
            return
        rep = min(ups, key=lambda r: (r.load, -r.idx))
        rep.state = "draining"
        drained = rep.engine.drain()
        self._collect(rep)
        rep.engine = None
        rep.state = "retired"
        if rep.hb is not None:
            rep.hb.beat(status="stopped")
        for req in drained:
            if req.state == FINISHED:
                continue
            target = self._pick()
            if target is None:          # should not happen (len(ups) > 1)
                rep.engine = self._spawn_engine(rep)
                rep.state = "up"
                target = rep
            target.engine.requeue(req)
            with self._lock:
                self._owner[req.request_id] = target.idx
        metrics.bump("scale_downs")

    def _collect(self, rep):
        popped = rep.engine.pop_results()
        failed_audit = ()
        if self._audit_rate > 0.0 and popped:
            failed_audit = self._audit(rep, popped)
            for rid in failed_audit:
                # a mismatched result is NEVER delivered: the request is
                # still unacked and will be recomputed bitwise elsewhere
                popped.pop(rid, None)
        with self._lock:
            for rid, res in popped.items():
                # first result wins: a snapshot-respawned replica recomputes
                # work that was already delivered — recomputation is
                # deterministic, so dropping the duplicate loses nothing
                if not self._acked(rid):
                    self._results[rid] = res
        if failed_audit:
            from ..distributed import integrity as _integrity
            sus = _integrity.sdc_counters().get(
                f"suspicion_replica{rep.idx}", 0)
            if sus >= self._audit_threshold:
                # repeat offender: fail the whole replica over before its
                # corrupted state spreads through the prefix cache — the
                # ordinary respawn path replays everything it still owed
                _integrity.clear_suspicion(rep.idx)
                self._on_failure(rep, AuditFailure(
                    f"replica {rep.idx}: {sus} shadow-audit mismatches "
                    f"(threshold {self._audit_threshold})"))
            else:
                self._replay(failed_audit)

    def _audit_sampled(self, rid):
        """Deterministic per-request sampling decision (stable across
        replays: the same rid always lands on the same side of the
        rate)."""
        import zlib
        u = (zlib.crc32(str(rid).encode()) % 1000000) / 1000000.0
        return u < self._audit_rate

    def _audit(self, rep, popped):
        """Sampled shadow audit: re-run sampled finished GREEDY requests
        through the raw-params ``generate_from_params`` oracle and
        bitwise-compare the token streams (the engine parity contract
        makes any divergence corruption, not noise). Returns the rids
        that failed; their suspicion is charged to ``rep``."""
        if self._audit_ref is None:
            if not self._audit_warned:
                self._audit_warned = True
                import warnings
                warnings.warn(
                    "FLAGS_serving_audit_rate > 0 but no audit_ref="
                    "(params, config) was passed to ReplicatedEngines; "
                    "the shadow audit is disabled")
            return ()
        from ..distributed import integrity as _integrity
        from ..models.generation import generate_from_params
        import numpy as np
        params, config = self._audit_ref
        failed = []
        for rid, res in popped.items():
            if res.finish_reason not in ("stop", "length"):
                continue
            if not self._audit_sampled(rid):
                continue
            with self._lock:
                req = self._requests.get(rid)
            if req is None or getattr(req, "do_sample", False):
                continue            # greedy-only oracle
            prompt = np.asarray(res.prompt).reshape(-1)
            out = generate_from_params(
                params, prompt[None, :].astype(np.int32), config,
                max_new_tokens=req.max_new_tokens, do_sample=False,
                eos_token_id=req.eos_token_id,
                stop_token_ids=req.stop_token_ids)
            expect = [int(t) for t in
                      np.asarray(out._data)[0, len(prompt):].tolist()]
            got = [int(t) for t in res.tokens]
            # prefix compare: a finished row's oracle tail is eos padding;
            # any real corruption flips tokens INSIDE the emitted stream
            ok = bool(got) and got == expect[:len(got)]
            _integrity.note_audit(ok, rep.idx)
            if not ok:
                failed.append(rid)
        return tuple(failed)

    def _on_failure(self, rep, err):
        """Replica death: respawn from its last snapshot when one exists
        (mid-decode requests resume bitwise; anything newer than the
        snapshot is replayed), otherwise replay everything it still owed
        on the surviving replicas. Past ``max_restarts`` the replica stays
        down permanently."""
        rep.state = "down"
        rep.last_error = err
        rep.engine = None
        self._drop_transfers_for(rep)
        unacked = self._unacked_of(rep)
        rep.restarts += 1
        if rep.restarts > self.max_restarts:
            self._replay(unacked)
            return
        self._respawn_from_snapshot(rep, unacked)

    def _unacked_of(self, rep):
        with self._lock:
            return [rid for rid, owner in self._owner.items()
                    if owner == rep.idx and not self._acked(rid)]

    def _respawn_from_snapshot(self, rep, unacked):
        """Shared respawn core (crash failover AND chip-loss reform):
        spawn a fresh engine on the replica's CURRENT mesh, restore its
        last disk snapshot when one loads, reconcile restored work
        against delivery/ownership, replay the remainder. Returns True
        when the snapshot restored."""
        snap = None
        if rep.mgr is not None:
            try:
                snap = rep.mgr.restore(None)   # quarantines corrupt steps
            except Exception:
                snap = None
        eng = self._spawn_engine(rep)
        restored = False
        if snap is not None:
            try:
                eng.load_state_dict(snap)
                restored = True
            except Exception:      # incompatible/stale-format snapshot
                restored = False
        if restored:
            # the snapshot replaced the registry content _spawn_engine
            # just applied — and may predate a fleet-level adapter op;
            # bring the restored set back to the LIVE one
            self._sync_adapters(eng)
        rep.engine = eng
        rep.state = "up"
        metrics.bump("respawns")
        if rep.hb is not None:
            rep.hb.beat(status="running")
        if restored:
            hosted = self._reconcile_restored(rep, eng)
            self._replay([rid for rid in unacked if rid not in hosted],
                         prefer=rep)
        else:
            self._replay(unacked)
        return restored

    def _reconcile_restored(self, rep, eng):
        """Reconcile a restored engine's work against delivery/ownership
        (shared by crash/loss respawn AND grow-back). The snapshot may
        predate request movement: anything already delivered, cancelled,
        or since reassigned to ANOTHER replica (e.g. by a rolling-restart
        drain) must not be recomputed here — cancel is neighbor-stable,
        so the resumed slots stay bitwise intact. Stale results for
        moved/delivered requests are purged (the cancels just minted
        CANCELLED results; a snapshot can also carry pre-save ones):
        _collect must never deliver them ahead of — or instead of — the
        real owner's stream. Returns the rids the engine still hosts."""
        for req in list(eng.live_requests()):
            rid = req.request_id
            if self._acked(rid) or self._owner.get(rid) != rep.idx:
                # hygiene, not a user cancellation: skip the ledger
                eng.cancel(req, count=None)
            else:
                with self._lock:
                    self._requests[rid] = req  # live handle for cancel()
        for rid in list(eng._results):
            if self._acked(rid) or self._owner.get(rid) != rep.idx:
                del eng._results[rid]
        hosted = {r.request_id for r in eng.live_requests()}
        hosted.update(eng._results)
        return hosted

    # -- topology-elastic: chip loss, group reform, grow-back ----------------
    def _poll_topology(self):
        """One chip-liveness round (elastic mode): beat the per-chip
        heartbeats, read the lost-chip set (injected serving schedule +
        stale chips), and reconcile every group against its plan — a
        group that lost a chip re-forms over its survivors at the largest
        viable mp degree; a degraded group whose chips returned grows
        back. Runs BEFORE the replicas step, so a group is marked down
        deterministically at the boundary the loss fires on — the dead
        engine is never stepped past the loss point."""
        topo = self._topology
        step = self._topo_step
        self._topo_step += 1
        topo.beat(step)
        lost = topo.lost_chips(step)
        for rep in self._replicas:
            if rep.state in ("retired", "draining"):
                continue
            hit = any(c in lost for c in rep.group)
            degraded = rep.state != "up" or rep.mp < self._configured_mp
            if not hit and not degraded:
                continue            # healthy full-degree group: no plan
            plan = topo.plan(rep.idx, lost)
            try:
                if rep.state == "up" and hit:
                    self._reform_group(rep, plan, lost)
                elif rep.state in ("down", "reforming") and rep.chip_lost \
                        and plan is not None \
                        and (rep.mp > 0 or self._elastic_grow):
                    # every chip of the group had died (or a prior reform
                    # attempt failed); chips are available now — bring the
                    # group back at whatever degree they support. A failed
                    # reform attempt (mp > 0: it was mid-shrink at a viable
                    # degree) retries regardless of the grow flag; a FULLY
                    # dead group (mp == 0) coming back is a grow-back and
                    # honors FLAGS_serving_elastic_grow=False ("chip
                    # losses are sticky, groups only shrink")
                    if rep.reform_wait > 0:
                        # spaced retry: a persistently-failing spawn/
                        # restore must not cost the healthy groups a full
                        # spawn attempt at EVERY boundary
                        rep.reform_wait -= 1
                    else:
                        self._reform_group(rep, plan, lost)
                elif self._elastic_grow and rep.state == "up" \
                        and plan is not None and plan[0] > rep.mp:
                    self._grow_group(rep, plan)
            except Exception as e:  # noqa: BLE001 — a failed spawn/restore
                # mid-reform must neither kill the supervising loop nor
                # wedge the replica in "reforming": the group goes down,
                # its work replays on the survivors (zero drops), and the
                # resurrect branch above retries it — with a DOUBLING
                # boundary backoff, so a survivor set that can never host
                # the engine does not stall the fleet with per-token
                # spawn attempts
                rep.state = "down"
                rep.engine = None
                rep.chip_lost = True
                rep.last_error = e
                rep.reform_backoff = min(max(1, rep.reform_backoff * 2), 32)
                rep.reform_wait = rep.reform_backoff
                self._drop_transfers_for(rep)
                self._replay(self._unacked_of(rep))
        set_group_gauges(self._replicas, self._configured_mp)

    def _reform_group(self, rep, plan, lost):
        """Chip-loss reform: the group lost at least one chip, so the
        whole replica is down (its device state — sharded weights and KV
        — is gone with the chip). Re-form over the surviving chips at the
        largest viable mp degree and respawn through the MP-PORTABLE
        snapshot path: the pool geometry is global and the gather-only
        schedule is bitwise at every degree, so mid-decode requests
        resume bitwise on the smaller group; anything newer than the
        snapshot (or everything, with no snapshot) replays — zero drops
        either way. Does NOT burn the crash-restart budget: a topology
        event is not an engine fault."""
        t0 = time.perf_counter()
        # a dead group whose chips came back, or the retry of a reform
        # attempt that failed mid-spawn (engine already gone either way)
        returning = rep.state in ("down", "reforming")
        # state flips BEFORE the engine is nulled (same order as
        # _on_failure): a router thread reading state=="up" must never
        # then find engine None mid-dereference
        rep.state = "reforming"
        if not returning:
            dead = [c for c in rep.group if c in lost]
            rep.chip_lost = True
            rep.last_error = ChipLossError(
                f"replica {rep.idx} lost chip(s) {dead} of mp={rep.mp} "
                f"group {list(rep.group)}")
            if rep.engine is not None:
                # late submissions from router threads see a TYPED
                # temporary stop (reforming + retry_after), not a bare
                # dead engine
                rep.engine.stop_for_reform(self._last_reform_latency())
            rep.engine = None
        self._drop_transfers_for(rep)
        unacked = self._unacked_of(rep)
        if plan is None:
            # no home chip survives: the group stays down (degraded to
            # zero capacity) until chips return; its work replays on the
            # surviving groups
            rep.state = "down"
            rep.mp, rep.mesh, rep.group = 0, None, ()
            self._replay(unacked)
            # no record_reform: nothing re-formed — counting this as a
            # group_reform (and clobbering reform_latency_s_last with the
            # microseconds it took to mark the group down) would skew
            # every later retry_after hint and the ladder's latency p99;
            # the loss itself shows in degraded_groups / chips-lost
            return
        prev_mp = rep.mp
        rep.mp, rep.group = plan
        rep.mesh = self._topology.mesh_for(rep.group)
        if returning:
            rep.chip_lost = False
        self._respawn_from_snapshot(rep, unacked)
        rep.reform_wait = rep.reform_backoff = 0   # spawn worked again
        self._mark_reform_hop(rep)
        # "grow" only when the degree actually rose (a fully-dead group
        # coming back): the RETRY of a loss-reform that failed mid-spawn
        # also arrives with returning=True but lands at the same-or-lower
        # degree and must not inflate the grow_backs audit trail
        record_reform("grow" if returning and plan[0] > prev_mp else "loss",
                      time.perf_counter() - t0)

    def _grow_group(self, rep, plan):
        """Grow-back: chips returned (``serving_chip_return_at`` fired /
        heartbeats recovered) and the group can host a higher mp degree
        again. The replica is HEALTHY, so the reform is a live handoff:
        snapshot the running engine in memory (slots intact), rebuild on
        the bigger mesh, restore — zero drops, zero replays, bitwise
        (the mp-portable snapshot contract), and zero new traces: the
        engine builders are memoized per (cfg, mesh, rung), so the
        original topology's executables are still warm."""
        t0 = time.perf_counter()
        eng_old = rep.engine
        rep.state = "reforming"
        # stop FIRST, snapshot second: a router-thread submit landing in
        # eng_old after the snapshot would exist only in the engine about
        # to be discarded (owned but on no engine — a silent drop). Once
        # stopped, late submits get the typed reforming error and spill.
        eng_old.stop_for_reform(self._last_reform_latency())
        state = eng_old.state_dict()      # live, boundary-consistent
        prev = (rep.mp, rep.group, rep.mesh)
        rep.mp, rep.group = plan
        rep.mesh = self._topology.mesh_for(rep.group)
        rep.chip_lost = False
        try:
            eng = self._spawn_engine(rep)
            eng.load_state_dict(state)    # mp-portable: bitwise resume
        except BaseException:
            # a failed grow must not leave the replica claiming the
            # TARGET degree: the retry's prev_mp comparison would
            # misrecord the eventual grow-back as a loss-reform, and
            # gauges would report capacity the group does not have
            rep.mp, rep.group, rep.mesh = prev
            raise
        rep.engine = eng
        rep.state = "up"
        rep.reform_wait = rep.reform_backoff = 0   # spawn worked again
        # the rebuilt engine holds neither the old engine's outbound
        # streams nor its staged install pages: abort unfinished sourced
        # transfers (their slots were requeued by the restore) and
        # unassign inbound ones so the pump re-offers them
        self._drop_transfers_for(rep)
        # same reconciliation as the loss path: the handoff minted FRESH
        # Request objects (from_state), so live handles must be refreshed
        # for cancel() identity-routing; a request cancelled MID-grow
        # (acked directly while the engine was nulled) must not be
        # resurrected and decoded to completion on the grown engine; and
        # a router thread that passed eng_old's stopped check just before
        # stop_for_reform can land its request in eng_old AFTER the state
        # snapshot (submit registers ownership BEFORE the engine accepts,
        # so it is visible here) — anything owned but hosted by neither
        # the snapshot nor a result replays on the grown engine
        hosted = self._reconcile_restored(rep, eng)
        self._replay([rid for rid in self._unacked_of(rep)
                      if rid not in hosted], prefer=rep)
        self._mark_reform_hop(rep)
        record_reform("grow", time.perf_counter() - t0)

    def _last_reform_latency(self):
        from ..distributed.elastic import elastic_counters
        last = elastic_counters().get("reform_latency_s_last", 0.0)
        return min(1.0, max(0.02, 2.0 * last))

    def _mark_reform_hop(self, rep):
        """Traced requests crossing a reform carry a "reform" hop on
        their timeline (like the requeue/replay/restore hops)."""
        if rep.engine is None:
            return
        for req in rep.engine.live_requests():
            if req.trace is not None:
                req.trace.instant("reform", mp=rep.mp,
                                  group=list(rep.group))

    def _replay(self, rids, prefer=None):
        """Resubmit lost requests as fresh copies — same request_id, seed,
        sampling params and ORIGINAL submit_t/deadline — on the preferred
        or least-loaded live replica. Exactness rides on the engine parity
        guarantee: the replayed stream is bitwise the one the dead replica
        would have produced."""
        for rid in rids:
            with self._lock:
                src = self._requests.get(rid)
            if src is None or self._acked(rid):
                continue
            tr = self._transfers.get(rid) if self._disagg else None
            if tr is not None and tr.done \
                    and not (tr.aborted or tr.failed or tr.seated):
                # complete KV stream retained host-side: the pump re-offers
                # it to a surviving decode worker — cheaper than a full
                # prompt recompute, still zero drops
                self._assign.pop(rid, None)
                continue
            if src.state == FINISHED:
                if src.finish_reason == CANCELLED:
                    # cancelled while in flight: its CANCELLED result may
                    # have died with the engine before a collect — deliver
                    # the outcome from the handle so pending() drains
                    with self._lock:
                        self._results[rid] = src.result()
                    continue
                # else: it FINISHED on the dying replica in the very step
                # that crashed (result lost, never collected) — fall
                # through and recompute an exact copy on a survivor
            target = prefer if (prefer is not None and prefer.state == "up") \
                else self._pick()
            if target is None:
                # the whole fleet is gone: resolve terminally so callers
                # driving pending()/run() converge to a visible failure
                # instead of spinning on an undeliverable request
                metrics.bump("dropped")
                src._finish(DROPPED)
                with self._lock:
                    self._results[rid] = src.result()
                continue
            copy = src.replay_copy()
            target.engine.requeue(copy)
            with self._lock:
                self._requests[rid] = copy
                self._owner[rid] = target.idx
            metrics.bump("replayed")

    # -- lifecycle -----------------------------------------------------------
    def _requeue_target(self, req, exclude=None):
        """Requeue target for a drained request: least-loaded routable
        replica, PREFERRING one that serves the weight version the request
        already produced tokens under — during a hot upgrade, in-flight
        work finishes on the version it started on as long as any replica
        of that version survives (only the final drain of the old fleet
        recomputes on the new version, from scratch, so every result is
        single-version consistent either way)."""
        ups = [r for r in self._routable() if r is not exclude]
        if not ups:
            return None
        if req.params_version is not None:
            same = [r for r in ups
                    if r.engine.params_version == req.params_version]
            if same:
                ups = same
        return min(ups, key=lambda r: (r.load, r.idx))

    def rolling_restart(self, absorb_steps=2, new_params=None,
                        params_version=None):
        """Restart the fleet one replica at a time with zero drops: mark
        a replica DRAINING (unroutable — new submissions and replays go
        elsewhere), drain it (in-flight requeued, original arrival kept),
        hand its work to the survivors, respawn it FRESH, then run a few
        supervision rounds so the fleet absorbs before the next drain.

        ``new_params`` turns the restart into a ZERO-DOWNTIME WEIGHT
        UPGRADE: each respawned replica comes back serving the new tree
        (``Engine.swap_params`` — same-shape, builders memoized per
        config, so no retrace), stamped ``params_version`` (default: one
        past the fleet's current version). Snapshots carry the version, so
        a crash-respawn can never resume new-version requests from an
        old-version snapshot's KV (the meta mismatch falls back to replay
        — still zero drops); results carry the version their tokens were
        produced under; and drained in-flight requests prefer surviving
        OLD-version replicas, finishing on the version they started on
        whenever one exists."""
        metrics.bump("rolling_restarts")
        if new_params is not None:
            if params_version is None:
                versions = [r.engine.params_version for r in self._replicas
                            if r.engine is not None]
                params_version = max(versions, default=0) + 1
            self._live_params = (new_params, int(params_version))
            self._upgrading = True
        try:
            for rep in list(self._replicas):
                if rep.state != "up":
                    continue
                rep.state = "draining"  # unroutable while its queue moves
                drained = rep.engine.drain()
                self._collect(rep)
                rep.engine = self._spawn_engine(rep)
                rep.restarts = 0        # a planned restart is not a failure
                rep.state = "up"
                metrics.bump("respawns")
                if rep.hb is not None:
                    rep.hb.beat(status="running")
                for req in drained:
                    if req.state == FINISHED:
                        continue        # cancelled mid-requeue: done already
                    target = self._requeue_target(req, exclude=rep) or rep
                    target.engine.requeue(req)
                    with self._lock:
                        self._owner[req.request_id] = target.idx
                for _ in range(max(0, int(absorb_steps))):
                    self.step()
        finally:
            self._upgrading = False

    # -- many-model serving: fleet-level adapter ops -------------------------
    def _live_adapter_engines(self):
        engines = [r.engine for r in self._replicas
                   if r.state == "up" and r.engine is not None]
        if not engines:
            raise EngineStoppedError("no live serving replica",
                                     queue_depth=0, requeued=())
        return engines

    def load_adapter(self, adapter_id, tree, alpha=None):
        """Hot-load ``adapter_id`` onto every live replica and record it
        in the fleet's LIVE adapter set, so every later spawn — crash
        respawn, chip-loss reform, rolling restart, autoscale grow —
        comes back serving it (a crash never resurrects a stale set, the
        ``_live_params`` discipline). Counted ONCE in the ledger; zero
        retraces and no prefix-cache flush per the engine contract.
        Runs on the supervising thread (like rolling_restart)."""
        engines = self._live_adapter_engines()
        for eng in engines:           # all-or-nothing precheck first
            eng._require_adapters()._check_id(adapter_id)
            eng._check_adapter_unbound(adapter_id, "load over")
        for i, eng in enumerate(engines):
            eng.load_adapter(adapter_id, tree, alpha=alpha, count=(i == 0))
        self._live_adapters[int(adapter_id)] = (tree, alpha)

    def evict_adapter(self, adapter_id):
        """Drop ``adapter_id`` fleet-wide (and from the live set, so
        respawns stay evicted). Refused — before any replica mutates —
        while ANY replica has the adapter bound to a running slot."""
        engines = self._live_adapter_engines()
        for eng in engines:
            eng._require_adapters()
            eng._check_adapter_unbound(adapter_id, "evict")
        for i, eng in enumerate(engines):
            eng.evict_adapter(adapter_id, count=(i == 0))
        self._live_adapters.pop(int(adapter_id), None)

    def swap_adapter(self, adapter_id, tree, alpha=None):
        """Replace a resident adapter's delta fleet-wide, in place (the
        adapter analogue of ``rolling_restart(new_params=)`` — but with
        no drain needed: the unbound precheck is the consistency
        boundary, and the rewrite is content-only with zero retraces)."""
        engines = self._live_adapter_engines()
        for eng in engines:
            eng._require_adapters()
            eng._check_adapter_unbound(adapter_id, "swap")
        for i, eng in enumerate(engines):
            eng.swap_adapter(adapter_id, tree, alpha=alpha, count=(i == 0))
        self._live_adapters[int(adapter_id)] = (tree, alpha)

    def pending(self):
        """Requests submitted but not yet delivered."""
        with self._lock:
            return sum(1 for rid in self._requests if not self._acked(rid))

    def pop_results(self):
        """Drain resolved requests and forget their tracking state (the
        supervisor-level mirror of ``Engine.pop_results`` — an undrained
        long-running supervisor would retain every prompt and token list
        forever). Delivered ids stay in a lightweight seen-set, so a
        replica respawned from a stale snapshot can never re-deliver a
        duplicate after the heavy state is dropped."""
        with self._lock:
            out, self._results = self._results, {}
            for rid in out:
                self._delivered.add(rid)
                self._requests.pop(rid, None)
                self._owner.pop(rid, None)
        return out

    def run(self, requests=None, max_steps=100000):
        """Submit ``requests`` (optional) and supervise until every tracked
        request has a result, then drain: returns {request_id:
        GenerationResult} for everything resolved since the last drain
        (check ``finish_reason`` — a dead-fleet terminal failure surfaces
        as ``DROPPED`` rather than an infinite wait)."""
        if requests is not None:
            for r in requests:
                self.submit(r)
        steps = 0
        while self.pending():
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(
                    f"supervisor did not converge in {max_steps} rounds "
                    f"({self.pending()} requests still pending)")
        return self.pop_results()

    def shutdown(self):
        """Drain every live replica; returns still-incomplete requests
        (original arrival kept) for hand-off to another fleet."""
        leftovers = []
        for rep in self._replicas:
            if rep.state == "up" and rep.engine is not None:
                leftovers.extend(rep.engine.drain())
                self._collect(rep)
                if rep.hb is not None:
                    rep.hb.beat(status="stopped")
                rep.state = "down"
        if self._disagg:
            # requests caught mid-transfer live on NO engine (the prefill
            # worker freed its slot, the decode worker never seated them):
            # without this they would vanish from the hand-off set
            seen = {r.request_id for r in leftovers}
            for rid, tr in list(self._transfers.items()):
                req = tr.request
                tr.aborted = True
                self._drop_transfer(rid)
                if rid not in seen and not tr.seated \
                        and req.state != FINISHED:
                    req._requeue()
                    leftovers.append(req)
        return leftovers

    # -- introspection -------------------------------------------------------
    @property
    def alive_replicas(self):
        return len(self._up())

    def results(self):
        """Resolved-but-not-yet-popped results (non-draining peek)."""
        with self._lock:
            return dict(self._results)

    def telemetry(self):
        """Live fleet gauges (the registry's "supervisor" family — one
        scrape shows routing pressure and failover history per replica):
        per-replica up/queue-depth/active-slots/restarts plus the
        fleet-level pending count."""
        out = {"replicas": len(self._replicas),
               "alive": len(self._up()),
               "routable": len(self._routable()),
               "pending": self.pending(),
               "params_version": (self._live_params[1]
                                  if self._live_params is not None else 0)}
        if self._disagg:
            with self._lock:
                out["transfers_inflight"] = len(self._transfers)
        if any(getattr(r.engine, "adapters", None) is not None
               for r in self._replicas if r.engine is not None):
            out["adapters_live"] = len(self._live_adapters)
        if self._topology is not None:
            out["configured_mp"] = int(self._configured_mp)
            out["degraded_groups"] = degraded_count(self._replicas,
                                                    self._configured_mp)
        for rep in self._replicas:
            eng = rep.engine
            out[f"replica{rep.idx}"] = {
                "up": int(rep.state == "up"),
                "state": rep.state,
                "role": rep.role,
                "restarts": int(rep.restarts),
                "queue_depth": (0 if eng is None else eng.queue_depth),
                "active_slots": (0 if eng is None else eng.active_slots),
                "step_count": (0 if eng is None else eng._step_count),
                "params_version": (0 if eng is None
                                   else int(eng.params_version)),
            }
            if eng is not None and getattr(eng, "adapters", None) is not None:
                out[f"replica{rep.idx}"]["adapters_resident"] = len(
                    eng.adapters.resident_ids())
            if self._topology is not None:
                out[f"replica{rep.idx}"]["mp"] = int(rep.mp)
                out[f"replica{rep.idx}"]["group"] = list(rep.group)
        return out
