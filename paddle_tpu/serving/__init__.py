"""paddle_tpu.serving — continuous-batching TPU serving engine.

Iteration-level (Orca-style) scheduling over a fixed B-slot decode batch.
The KV cache is block-PAGED (vLLM-style: fixed-size pages + a slot->page
table, prefix reuse copy-on-write, chunked prefill fused into the decode
step). See engine.py for the design; `profiler.serving_counters()` /
`serving_summary()` for observability.

Self-healing (engine.py + supervisor.py): `Engine.state_dict()` /
`load_state_dict()` snapshot the FULL engine (KV, slot table, PRNG
streams, queue, results, metrics) through the hardened checkpoint path —
a cold restart resumes every in-flight request bitwise mid-decode;
`Engine.run()` installs a SIGTERM boundary drain that flushes a snapshot
and requeues in-flight requests instead of dropping them; and
`ServingSupervisor` runs N replicas behind a least-loaded router with
heartbeat failure detection, snapshot respawn and exact request replay
(zero requests dropped across replica death / rolling restarts).

Telemetry: with ``FLAGS_serving_trace`` on, every Request carries a span
trace (queue → prefill chunks → decode → deliver, plus CoW/prefix and
self-healing hops) that survives engine snapshots and exports as
Perfetto JSON / JSONL — see ``paddle_tpu.observability``.

Tensor-parallel serving (mp_forward.py): ``Engine(mp=N)`` shards the GPT
weights column-parallel and the paged KV pool's HEAD axis over a 1-D
'mp' mesh (per-chip KV ~ 1/mp; the page table stays global), with a
GATHER-ONLY collective schedule so engine output stays bitwise identical
to the single-chip engine on every rung (``FLAGS_comm_backend``:
mp=gspmd | ring | fused Pallas GEMM+collective kernels). Snapshots are
mp-portable; a supervisor replica is an mp group
(``mp_replica_meshes``).

Topology-elastic serving (elastic.py): ``ServingSupervisor(mp=N)``
watches every CHIP of every mp group (injected
``FaultPlan.serving_chip_loss_at`` schedules + per-chip heartbeats) —
one lost chip re-forms its group over the surviving chips at the
largest viable mp degree via the mp-portable snapshot path (bitwise
resume, zero drops), the fleet runs degraded (router backs off
mid-reform with typed ``retry_after``; shed/autoscale read live
routable capacity), and returning chips grow the group back with zero
drops and zero new traces. A traced per-slot anomaly guard
(``FLAGS_serving_anomaly_policy=quarantine``) resolves a slot whose
logits went non-finite as ``finish_reason="error"`` without poisoning
the shared batch, the prefix cache or a snapshot.

Quantized serving (quant.py + ops/pallas_kernels/quant_gemm.py;
default-OFF behind ``FLAGS_serving_weight_dtype`` /
``FLAGS_serving_kv_dtype`` = bf16|int8|fp8): weight-only int8/fp8 GEMMs
with per-output-channel scales dequantized in the GEMM epilogue (Pallas
quant kernel on TPU; the mp rungs feed the quantized shard straight into
``fused_gemm_ag``), and a quantized paged KV pool with per-PAGE scales
stored beside the page table — the same HBM holds ~2-4x the pages/slots.
Calibrate through the ``paddle_tpu.quantization`` package
(``quant.calibrate`` -> ``QuantSpec`` -> ``Engine(quant=...)``). The
exactness contract becomes "exact at a given dtype config": order
invariance, bitwise kill-and-resume and mp==single-chip bitwise all hold
per config; bf16/bf16 stays bitwise identical to the unquantized engine,
and a dtype-mismatched snapshot restore raises the typed
``QuantDtypeMismatchError`` naming both configs.

Disaggregated prefill/decode serving (kv_transfer.py; opt-in via
``ServingSupervisor(roles=...)`` / ``FLAGS_serving_role``): dedicated
PREFILL workers run only the big-chunk rungs of the ladder over all
their slots (never the [B,1] decode dispatch) and stream each request's
finished KV pages — at the pool's storage dtype, int8/fp8 wires carry
per-page scales — to a decode worker, which installs a bounded number of
pages per decode boundary (``FLAGS_serving_transfer_pages_per_boundary``)
and seats the request exactly like an exact-prefix-cache hit, so the
disaggregated token stream stays BITWISE identical to a single engine,
greedy and sampled, per dtype config. The router is role- and
cache-aware (``Engine.prefix_page_hashes`` is the stable routing key):
a prompt whose prefix a decode worker already caches routes straight
there — no prefill compute, no transfer — and the fleet rebalances
roles when a chip loss strands decode capacity (pure-decode fallback,
zero drops; transfers retain payloads until seated so a decode-worker
death mid-stream re-offers, not recomputes).

Many-model serving (adapters.py; default-off behind
``FLAGS_serving_adapter_slots``): one paged engine serves N low-rank
(LoRA-class) adapter variants of the base model at once. Adapter deltas
live as stacked device slabs (one row per adapter id; id 0 is the
pinned all-zeros base row), each slot's ``adapter_id`` is a TRACED
operand, and the per-slot delta GEMM fuses into the base projection
epilogue — so a mixed-adapter batch reuses the SAME two steady-state
executables (``paged_traces==2`` holds with adapters on), and hot
``load_adapter`` / ``evict_adapter`` / ``swap_adapter`` are pure
content rewrites with ZERO retraces. Attention projections are
deliberately un-adapted (no delta GEMM in the attention inner loop);
adapted requests' prefix-cache keys carry their (adapter id, content
version) while base traffic keeps shared unsalted keys — so adapter ops
never flush the prefix cache (a swap strands the old version's entries
to age out of the LRU) and base-weight swaps keep the full flush.
Per-slot outputs stay bitwise identical to solo
``generate_from_params(adapters=...)`` runs regardless of batch
composition or admission order, greedy and sampled, single-chip and mp.
Requests pick a model via ``Request(adapter=...)`` or the
``FLAGS_serving_tenant_adapters`` tenant mapping; WFQ fairness rotates
across adapters; snapshots and supervisor respawn/reform carry the
resident adapter set.

SLO traffic management (slo.py; all default-off, host-side policy over
the machinery above): priority classes with WFQ tenant fairness and
deadline-driven preemption (``FLAGS_serving_priority_classes``),
graceful load shedding with drain-rate retry-after hints
(``FLAGS_serving_shed``, ``ShedError``), per-tenant token-bucket rate
limits, telemetry-driven autoscaling (``FLAGS_serving_autoscale``), and
zero-downtime weight swaps (``rolling_restart(new_params=)`` /
``Engine.swap_params``; snapshots and results carry ``params_version``).
"""
from .request import (  # noqa: F401
    Request, GenerationResult,
    QUEUED, RUNNING, FINISHED, STOP, LENGTH, EXPIRED, CANCELLED, DROPPED,
    SHED, ERROR,
)
from .scheduler import Scheduler, QueueFullError, ShedError  # noqa: F401
from .slo import (  # noqa: F401
    CLASSES, class_rank, Autoscaler, ShedPolicy, TokenBucket,
)
from .paged_kv import PagedKVPool, PagePoolExhausted, pages_for  # noqa: F401
from .kv_transfer import KVTransfer, PagePayload  # noqa: F401
from .engine import Engine, EngineStoppedError  # noqa: F401
from .mp_forward import replica_mesh  # noqa: F401
from .elastic import FleetTopology, viable_mp  # noqa: F401
from .supervisor import (  # noqa: F401
    ChipLossError, ServingSupervisor, mp_replica_meshes,
)
from .metrics import (  # noqa: F401
    serving_counters, reset_serving_counters, serving_summary,
)
from . import quant  # noqa: F401
from .quant import (  # noqa: F401
    QuantSpec, QuantSpecError, QuantDtypeMismatchError,
)
from .adapters import (  # noqa: F401
    AdapterRegistry, AdapterSpec, UnknownAdapterError,
)
