"""Framework flags (ref: FLAGS_* in paddle/fluid/framework + paddle.set_flags).

TPU-relevant knobs only; unknown flags are stored and returned verbatim so
scripts written against the reference don't crash.
"""
from __future__ import annotations

_FLAGS = {
    "FLAGS_use_flash_attention": True,
    "FLAGS_cudnn_deterministic": False,   # accepted, no-op on TPU
    "FLAGS_embedding_deterministic": False,
    "FLAGS_use_remat": False,
    "FLAGS_matmul_precision": "default",  # default|highest (f32 on MXU)
    "FLAGS_donate_buffers": True,
    # Eager dispatch cache: route repeat op dispatches through cached
    # jax.jit executables (dispatch.py). Disable to force op-by-op eager
    # execution when debugging numerics or tracing issues.
    "FLAGS_eager_jit_cache": True,
    # Persist XLA executables across processes (JAX_COMPILATION_CACHE_DIR,
    # default <checkout>/.jax_cache — see framework/compilation_cache.py).
    "FLAGS_persistent_compilation_cache": True,
    # -- explicit gradient communication (distributed/grad_comm.py) ---------
    # Master switch: "auto" activates the explicit schedule only when one of
    # the two knobs below asks for a non-default schedule; True/"on" forces
    # it (gives the allreduce-fp32 baseline its own comm counters); False
    # disables it entirely. Default path is byte-identical to the seed.
    "FLAGS_grad_comm": "auto",
    # Weight-update sharding (ZeRO-1 per arXiv:2004.13336): reduce-scatter
    # grads, fused optimizer update on each replica's 1/n flat shard (slots
    # stored sharded), all-gather updated params — halves grad-reduce wire
    # bytes vs all-reduce and divides update FLOPs/slot HBM by the dp size.
    "FLAGS_weight_update_sharding": False,
    # Wire dtype for the gradient reduction: float32 | bfloat16 | int8.
    # Compressed dtypes move over an all_to_all exchange and accumulate in
    # fp32 on the receiver (EQuARX-style per-2048-chunk scales for int8);
    # master/update math stays fp32.
    "FLAGS_allreduce_dtype": "float32",
    # Flat-buffer bucket size for grad collectives: few, large transfers.
    "FLAGS_grad_bucket_bytes": 16 * 2 ** 20,
    # -- tensor-parallel schedule (distributed/tp_overlap.py) ---------------
    # Sequence parallelism (Megatron-SP done the shard_map way): norms/
    # residuals between TP blocks compute on seq-sharded activations; the
    # two per-block all-reduces become a reduce-scatter after RowParallel
    # and an all-gather before ColumnParallel — same wire bytes, 1/mp
    # activation memory between blocks. Default OFF: the GSPMD schedule is
    # untouched and the compiled program is byte-identical to the seed.
    "FLAGS_sequence_parallel": False,
    # -- fault-tolerant runtime (jit/train_step.py anomaly guard) -----------
    # Compiled anomaly guard policy. "off" (default): the compiled step is
    # byte-identical to the unguarded program. "skip": an all-finite check
    # of loss+grads is fused into the step executable (shard-space psum'd
    # under grad_comm) and a bad step's update is skipped via lax.cond —
    # the step_ok flag rides back with the loss in ONE host fetch, no extra
    # sync. "rollback": skip, plus after FLAGS_anomaly_max_bad_steps
    # consecutive bad steps the attached CheckpointManager's latest
    # checkpoint is restored and the RNG stream fast-forwarded past the
    # poison batches.
    "FLAGS_anomaly_policy": "off",
    # Consecutive bad steps tolerated under "rollback" before restoring.
    "FLAGS_anomaly_max_bad_steps": 3,
    # -- continuous-batching serving engine (serving/engine.py) -------------
    # Decode-batch slot count B: the fixed batch dim of the one-token decode
    # dispatch and of the slot->page table. More slots = more requests
    # decoded per iteration (throughput); KV memory is FLAGS_serving_num_pages.
    "FLAGS_serving_slots": 8,
    # KV table sequence capacity Smax per slot; 0 = the model's max_seq_len.
    # Every request needs prompt_len + max_new_tokens <= Smax.
    "FLAGS_serving_max_seq_len": 0,
    # Wait-queue bound: submit() past this raises QueueFullError — the
    # backpressure signal a frontend turns into HTTP 429 / retry-after.
    "FLAGS_serving_max_queue": 256,
    # The KV cache is a block-paged pool [L,P,page,nh,d] + slot->page table
    # (vLLM-style): admission is bounded by PAGES, not worst-case Smax
    # slots, long prompts prefill in chunks interleaved with decode, and
    # common prompt prefixes share physical pages copy-on-write.
    # Tokens per KV page. Smaller pages = less per-request fragmentation
    # (waste < page_size tokens per sequence) but a bigger page table.
    "FLAGS_serving_page_size": 16,
    # Physical pages in the paged pool. 0 = auto: num_slots * ceil(Smax /
    # page_size) + 1 (every slot can reach Smax at once, +1 trash page).
    "FLAGS_serving_num_pages": 0,
    # Chunked-prefill budget: long prompts prefill in chunks interleaved
    # between decode iterations (Sarathi-style), so admitting a 1024-token
    # prompt costs each inter-token gap one chunk instead of a monolithic
    # prefill stall. Chunks walk a power-of-two LADDER of sizes (page_size
    # .. this value): bulk prefill rides the largest rung, the tail steps
    # down so per-request padding waste stays < page_size. Executable set
    # = the fused step at [B, 1] (decode) + one [1, rung] trace per ladder
    # rung actually used. Must be >= page_size.
    "FLAGS_serving_prefill_chunk": 16,
    # Hash-match admitted prompts against previously served ones and map
    # the common page-aligned prefix (or the exact full prompt) to the SAME
    # physical pages, copy-on-write on first divergence. Sharing is bitwise
    # safe: KV for a token depends only on the token prefix.
    "FLAGS_serving_prefix_cache": True,
    # Route the paged decode attention through the Pallas TPU kernel
    # (serving/paged_attention.py) instead of the pure-jnp page gather.
    # TPU-only; the kernel's online-softmax accumulation is numerically
    # equivalent but NOT bitwise identical to the jnp path — disable when
    # auditing bitwise parity on TPU.
    "FLAGS_serving_paged_kernel": True,
    # Tensor-parallel serving degree: > 1 builds the engine over a 1-D
    # 'mp' mesh of that many chips — GPT weights column-sharded (head-
    # major qkv), the paged KV pool sharded over its HEAD axis (per-chip
    # KV bytes ~ 1/mp; the host page table stays global), logits/embedding
    # vocab- and feature-sharded. The schedule is GATHER-ONLY, so engine
    # output stays BITWISE identical to the single-chip engine. The
    # collective rung comes from FLAGS_comm_backend ("mp=gspmd|ring|
    # fused"); an explicit Engine(mesh=/mp=/comm_backend=) overrides both
    # flags. 0/1 = single chip.
    "FLAGS_serving_mp": 0,
    # -- quantized serving (serving/quant.py + ops/pallas_kernels/
    # quant_gemm.py) -------------------------------------------------------
    # Weight storage dtype of the serving engine: "bf16" (= today's
    # full-precision bitwise-exact path, untouched), "int8" or "fp8"
    # (weight-only quantization: per-output-channel scales computed at
    # engine build or imported from a PTQ calibration via
    # Engine(quant=QuantSpec), dequant fused into the GEMM epilogue — on
    # the mp rungs the int8/fp8 shard feeds fused_gemm_ag directly, no fp
    # weight copy anywhere). The exactness contract becomes "exact at a
    # given dtype config": order-invariant, kill-and-resume bitwise, and
    # mp output bitwise identical to single-chip QUANTIZED output.
    "FLAGS_serving_weight_dtype": "bf16",
    # KV-pool storage dtype: "bf16" (full precision) | "int8" | "fp8".
    # Quantized pools hold ~4x/~4x the pages in the same HBM (fp32
    # compute) with per-PAGE dequant scales stored beside the page table;
    # CoW, prefix sharing, chunked prefill and snapshots operate on
    # quantized pages unchanged. Requires calibration (QuantSpec KV clip
    # ranges) or accepts the engine's automatic one-forward calibration.
    "FLAGS_serving_kv_dtype": "bf16",
    # Route quantized weight GEMMs through the Pallas quant kernel
    # (dequant in the kernel epilogue, fp32 accumulation). TPU-only with
    # Mosaic-friendly shapes, single-chip engines only; everywhere else
    # the same algebra runs as jnp that XLA fuses into the MXU epilogue.
    # Like FLAGS_serving_paged_kernel, the kernel is numerically
    # equivalent but NOT bitwise identical to the jnp epilogue (tiled
    # fp32 accumulation, one rounding under bf16 compute) — disable it
    # when auditing cross-mp-degree bitwise parity of a quantized config
    # on TPU (e.g. restoring an mp snapshot onto a single chip).
    "FLAGS_serving_quant_kernel": True,
    # -- speculative decoding (serving/engine.py + serving/quant.py) --------
    # Speculative multi-token decoding on the paged engine: per boundary a
    # cheap DRAFT pass proposes up to k tokens per slot, then ONE fused
    # verify executable scores all slots at [B,k+1] with per-slot accept
    # masks / lengths / sampling params as traced operands (the chunk-
    # ladder trick: mixed speculative/plain/greedy/sampled traffic shares
    # one executable, admission never retraces). Greedy speculative output
    # is BITWISE identical to the non-speculative engine; sampled streams
    # replay generate_from_params exactly (threefry streams split only on
    # EMITTED tokens). 0 = OFF: the engine builds byte-identical
    # executables to a pre-speculation engine.
    "FLAGS_serving_speculate_k": 0,
    # Draft source: "quant" (default — the PR 14 int8 self-draft: the
    # SAME weights quantized per-channel, reading the engine's paged KV
    # through a draft-scale sidecar; on an already-quantized engine the
    # draft degenerates to the engine weights) or "shallow" (truncate to
    # the first FLAGS_serving_draft_layers transformer blocks — cheaper
    # on CPU where int8 dequant costs more than it saves).
    "FLAGS_serving_draft_source": "quant",
    # Number of transformer blocks the "shallow" draft keeps. 0 = auto
    # (num_layers // 2, at least 1). Ignored by source="quant".
    "FLAGS_serving_draft_layers": 0,
    # -- many-model serving: per-slot LoRA-class adapters (serving/
    # adapters.py). N low-rank deltas of ONE base checkpoint live stacked
    # in fixed-shape device slabs; each slot's adapter id is a TRACED
    # operand of the fused paged step, so a mixed-adapter batch (base
    # model included) shares the engine's two steady-state executables
    # and adapter hot-load/evict/swap are content-only slab rewrites —
    # zero retraces, the swap_params machinery. Attention is never
    # adapted; adapted requests' prefix-cache keys are salted with
    # (adapter id, version) while base traffic shares unsalted keys, so
    # adapter ops skip the prefix-cache flush base-weight swaps require.
    # Loadable adapter slots (ids 1..N; id 0 = base model). 0 = OFF: the
    # engine is byte-identical to the adapter-less one.
    "FLAGS_serving_adapter_slots": 0,
    # Max (padded) adapter rank r: every loaded delta's true rank must be
    # <= this; smaller ranks zero-pad (bitwise-exact — padding columns
    # contribute exact zeros). Static: changing it is a restart, like
    # page_size.
    "FLAGS_serving_adapter_rank": 8,
    # Tenant -> default adapter id mapping, dict ({"acme": 1}) or string
    # ("acme:1,beta:2"): requests that don't name adapter= explicitly are
    # served with their tenant's delta; unmapped tenants get the base
    # model.
    "FLAGS_serving_tenant_adapters": {},
    # -- self-healing serving (serving/engine.py + serving/supervisor.py) ---
    # Engine-snapshot cadence: with a CheckpointManager attached
    # (Engine.attach_checkpoint), every N step boundaries the FULL engine
    # state (KV pool, slot table, PRNG streams, queue, results, metrics)
    # is checkpointed through the hardened CRC/rename-aside path — a cold
    # restart resumes every in-flight request bitwise mid-decode. 0 keeps
    # only the SIGTERM boundary flush.
    "FLAGS_serving_snapshot_every": 32,
    # Per-replica respawn budget for the ServingSupervisor; past it the
    # replica stays down and its unacknowledged requests are replayed on
    # the surviving replicas.
    "FLAGS_serving_max_restarts": 3,
    # Heartbeat staleness threshold (seconds) past which the supervisor
    # declares a replica frozen and fails it over. In topology-elastic
    # mode the same threshold applies to the per-CHIP heartbeat files.
    "FLAGS_serving_heartbeat_timeout": 10.0,
    # -- topology-elastic serving (serving/elastic.py) -----------------------
    # Grow a degraded mp group back to its configured degree when its
    # lost chips return (serving_chip_return_at fires / chip heartbeats
    # recover): a LIVE snapshot handoff — zero drops, zero replays, and
    # zero new traces (builders memoized per (cfg, mesh, rung)). Off:
    # chip losses are sticky, groups only shrink.
    "FLAGS_serving_elastic_grow": True,
    # Bounded router retries while EVERY replica is mid-reform: the
    # supervisor's submit() backs off with a deterministic per-request
    # jitter this many times before raising EngineStoppedError with
    # reforming=True and a retry_after hint.
    "FLAGS_serving_reform_retries": 2,
    # Serving anomaly guard: "off" (default — the fused step and the
    # token trajectory are byte-identical to the unguarded engine) or
    # "quarantine" (a traced per-slot all-finite check on the logits
    # rides the fused paged step; a poisoned slot — NaN/Inf from bad
    # weights, a corrupted KV page or a flaky chip — resolves
    # finish_reason="error" at the boundary, its prompt pages are NOT
    # published to the prefix cache, and its neighbors stay
    # bitwise-stable: the poison never spreads to the shared batch or a
    # snapshot).
    "FLAGS_serving_anomaly_policy": "off",
    # -- disaggregated serving (serving/kv_transfer.py) ----------------------
    # Engine role: "both" (default — the classic single-engine loop that
    # prefills AND decodes), "prefill" (runs only the big-chunk rungs of
    # the chunked-prefill ladder over all slots and streams finished KV
    # pages out — never dispatches the [B,1] decode executable), or
    # "decode" (receives streamed pages between its own decode boundaries
    # and seats them as if the prompt were an exact prefix-cache hit).
    # Role is host-side scheduling policy ONLY: the executables are
    # identical per shape, which is what keeps disaggregated output
    # bitwise equal to a single-engine run. Paged layout required for
    # non-"both" roles. Usually set per-replica via
    # ServingSupervisor(roles=...), not globally.
    "FLAGS_serving_role": "both",
    # Max KV pages a decode worker installs from incoming transfers per
    # step boundary — bounds the host->device copy work that rides
    # between decode dispatches, so an arriving giant-prompt transfer
    # never stalls the decoding slots (T3-style overlap discipline).
    "FLAGS_serving_transfer_pages_per_boundary": 4,
    # Prefix-affinity routing: the supervisor probes each decode
    # replica's prefix cache with the request's cumulative page hashes
    # and routes shared-prefix traffic to the replica that already holds
    # the pages — a hit admits directly on the decode worker and SKIPS
    # the prefill worker and the page transfer entirely. Off: disagg
    # routing is least-loaded-prefill only.
    "FLAGS_serving_affinity_routing": True,
    # -- SLO-driven multi-tenant serving (serving/slo.py) --------------------
    # Class-aware admission: requests carry priority ("interactive" |
    # "batch" | "best_effort") and a tenant id; admission serves classes
    # best-first with weighted fair queueing across tenants WITHIN a class
    # (one tenant cannot starve another), and an interactive request about
    # to miss its deadline preemptively evicts the youngest lowest-class
    # running slot (requeued with its ORIGINAL arrival, the PR 7 drain
    # machinery — its replay is bitwise, so preemption costs latency, never
    # correctness). Default OFF: admission is the strict FCFS the parity
    # suites gate, byte-identical to the pre-SLO engine.
    "FLAGS_serving_priority_classes": False,
    # Per-class default relative deadline (seconds) applied at submit when
    # the request did not set one; 0 = no class deadline. Only read in
    # priority mode.
    "FLAGS_serving_class_deadline_interactive": 0.0,
    "FLAGS_serving_class_deadline_batch": 0.0,
    "FLAGS_serving_class_deadline_best_effort": 0.0,
    # Slack threshold (seconds) under which a queued interactive request
    # counts as about-to-miss-its-deadline and may preempt. 0 = derive from
    # live telemetry (2x the ledger's TTFT p50, floor 50ms).
    "FLAGS_serving_preempt_margin_s": 0.0,
    # Graceful load shedding: when the wait queue sits above
    # shed_high * max_queue for shed_window consecutive step boundaries
    # (sustained overload, not a burst), lowest-class queued work is shed
    # down to shed_low * max_queue with finish_reason="shed" and a
    # retry-after hint derived from the live queue-drain rate — instead of
    # everything timing out. While shedding, NEW lowest-class submissions
    # raise ShedError (same hint). Default OFF.
    "FLAGS_serving_shed": False,
    "FLAGS_serving_shed_high": 0.75,
    "FLAGS_serving_shed_low": 0.5,
    "FLAGS_serving_shed_window": 4,
    # Per-tenant token-bucket rate limit at the supervisor router:
    # sustained requests/second per tenant (0 = off) with a burst
    # allowance. Over-rate submissions raise ShedError with the exact
    # time-to-next-token as retry_after.
    "FLAGS_serving_tenant_rate": 0.0,
    "FLAGS_serving_tenant_burst": 8,
    # Telemetry-driven autoscaling (supervisor): watch fleet queue depth /
    # slot occupancy / TTFT p99 with hysteresis + cooldown and grow/shrink
    # the replica set through the existing spawn/drain machinery. OFF by
    # default; bounds and watermarks below.
    "FLAGS_serving_autoscale": False,
    "FLAGS_serving_min_replicas": 1,
    "FLAGS_serving_max_replicas": 4,
    # Scale up past up_queue waiting requests per live replica (or past
    # up_occupancy mean slot occupancy); scale down below down_queue AND
    # below down_occupancy. Watermarks are deliberately far apart
    # (hysteresis) so the fleet never flaps.
    "FLAGS_serving_autoscale_up_queue": 4.0,
    "FLAGS_serving_autoscale_down_queue": 0.5,
    "FLAGS_serving_autoscale_up_occupancy": 0.9,
    "FLAGS_serving_autoscale_down_occupancy": 0.3,
    # TTFT p99 SLO (seconds) that also triggers scale-up when breached;
    # 0 disables the latency trigger.
    "FLAGS_serving_autoscale_ttft_slo": 0.0,
    # Consecutive over/under-watermark evaluations required before acting,
    # and the minimum wall-clock gap between two actions.
    "FLAGS_serving_autoscale_window": 4,
    "FLAGS_serving_autoscale_cooldown_s": 2.0,
    # Ring-decomposed compute/communication overlap on the mp axis: the
    # pre-QKV/FFN all-gather splits into mp-1 ppermute hops with each
    # chunk's GEMM issued on arrival, and the RowParallel GEMM emits
    # partial products chunk-by-chunk into a pipelined reduce-scatter
    # (T3 / fused computation-collective style). Requires
    # FLAGS_sequence_parallel; default OFF.
    "FLAGS_mp_overlap": False,
    # -- unified telemetry (paddle_tpu/observability) ------------------------
    # Prometheus /metrics endpoint port (stdlib http.server daemon thread
    # over the registry snapshot — observability/prometheus.py). 0 = OFF
    # (the default): nothing binds, nothing is scraped. Set it non-zero
    # BEFORE constructing a serving.Engine or a TrainStep — both bring
    # the endpoint up on construction — or call
    # observability.start_metrics_server(port) directly.
    "FLAGS_metrics_port": 0,
    # Per-request span tracing in the serving engine: every Request
    # records queue-wait, each prefill chunk, decode steps, CoW/prefix
    # events and self-healing hops, survivable through engine snapshots,
    # exportable as Perfetto JSON / JSONL (observability/tracing.py).
    # Host-side only — executables, traced operands and trace counters are
    # untouched either way. Default OFF: untraced requests pay one
    # attribute check.
    "FLAGS_serving_trace": False,
    # Ring-buffer bound on retained finished-request traces.
    "FLAGS_trace_buffer": 4096,
    # Live training-step telemetry (observability/step_telemetry.py):
    # sampled per-step records with dispatch/host-sync wall split,
    # achieved MFU from the static FLOP estimator, wire bytes from the
    # static comm schedules, and device-memory watermarks. Default OFF
    # (one dict lookup per step).
    "FLAGS_step_telemetry": False,
    # Sample every Nth step when step telemetry is on. Sampling blocks on
    # that step's result; the recorded wall time averages over the window
    # since the previous sample, so the number stays honest while
    # unsampled steps keep their async dispatch overlap.
    "FLAGS_step_telemetry_every": 8,
    # EWMA regression sentinel: log a warning when a sampled step's wall
    # time drifts more than this percentage above the rolling baseline.
    # 0 disables the sentinel.
    "FLAGS_step_time_drift_pct": 25.0,
    # -- topology-elastic training (distributed/elastic.py, topology.py) ----
    # Reshard-on-load: a checkpoint whose packed dp-sharded slot layout was
    # produced on a DIFFERENT mesh is resharded for the restoring step
    # (streamed leaf-by-leaf on the host, bitwise round-trippable). Off:
    # a cross-topology load raises TopologyMismatchError naming the
    # differing fields instead (strict fleets that want resumes pinned to
    # the producing topology). Same-topology restores are unaffected
    # either way.
    "FLAGS_elastic_reshard": True,
    # ElasticMeshSupervisor snapshot cadence (TrainStep.attach_checkpoint
    # save_every): the newest good snapshot is what a re-formed mesh
    # resumes from, so this bounds steps re-executed after a chip loss.
    "FLAGS_elastic_snapshot_every": 4,
    # Smallest dp the supervisor will shrink to before giving up.
    "FLAGS_elastic_min_dp": 1,
    # Grow the mesh back when failed ranks return (heartbeats recover /
    # chip_return_at fires). Off: failures are sticky, the mesh only
    # shrinks.
    "FLAGS_elastic_grow": True,
    # Heartbeat staleness threshold (seconds) for the supervisor's rank
    # failure detection when a heartbeat_dir is configured.
    "FLAGS_elastic_heartbeat_timeout": 5.0,
    # -- per-axis communication-schedule backend ----------------------------
    # Pluggable collective decomposition per mesh axis, e.g. "mp=fused" or
    # "mp=fused,dp=ring" (distributed/comm_backend.py). Backends:
    #   gspmd — the partitioner emits whole collectives (seed behavior);
    #   ring  — scheduling-level overlap: mp-1 ppermute hops with chunk
    #           GEMMs on arrival (PR 3's ring_ag_gemm/gemm_ring_rs for mp;
    #           grad_comm's explicit bucketed RS/AG schedule for dp);
    #   fused — kernel-level fusion: Pallas kernels whose grid steps DMA
    #           the next remote chunk while the current chunk's tile GEMM
    #           runs, and whose reduce-scatter epilogue accumulates partial
    #           tiles directly into the scatter destination — no
    #           intermediate full-size buffer is ever materialized
    #           (ops/pallas_kernels/fused_collectives.py).
    # Naming mp=ring/fused implies the sequence-parallel activation layout;
    # naming dp=ring/fused implies the explicit grad-comm schedule. The
    # pp axis selects the PIPELINE-boundary schedule (distributed/
    # pipeline.py): pp=gspmd keeps the seed's partial-manual pipeline;
    # pp=ring rewrites the gpipe/1f1b schedule fully manually with the
    # boundary activation/cotangent ppermutes issued at the end of each
    # scan tick (the hop rides the wire while the next tick's stage GEMMs
    # run, and the partitioner never sees a replicated stage select —
    # involuntary-remat warnings die structurally); pp=fused additionally
    # runs each stage's LAST GEMM as a Pallas kernel whose epilogue issues
    # the boundary RDMA directly (fused_collectives.fused_gemm_ppsend,
    # custom VJP for the backward tick). The empty default keeps the
    # legacy flags in charge (FLAGS_mp_overlap -> mp=ring,
    # FLAGS_grad_comm/FLAGS_weight_update_sharding -> dp=ring) and the
    # flags-off program byte-identical to the seed. Ineligible selections
    # fall back one rung (fused -> ring -> gspmd) with a once-per-reason
    # warning naming the exact flag that would fix it.
    "FLAGS_comm_backend": "",
    # Boundary wire dtype of the explicit pp schedule (grad_comm's wire
    # vocabulary: "auto" | "float32" | "bfloat16"). "auto" wires the
    # compute dtype; "bfloat16" halves boundary bytes while every stage
    # still accumulates fp32 (pp=fused ignores this — its RDMA leaves the
    # GEMM epilogue at the compute dtype).
    "FLAGS_pp_wire_dtype": "auto",
    # -- silent-data-corruption sentinel (distributed/integrity.py) ---------
    # Fuse a per-replica integrity fingerprint (uint32 bit-reduction over
    # params + replicated optimizer slots) into every Nth step executable
    # and cross-check it over the dp axis: a flipped bit in ONE replica's
    # copy shows up as a fingerprint minority, is localized by majority
    # vote, and is repaired in place from a healthy peer's bytes — no disk
    # rewind, zero steps lost. The verdict rides the step's existing
    # combined host fetch (host_syncs per update step unchanged; the
    # fault_counters ledger audits it). 0 = OFF (the default): the step
    # executable is byte-identical to flags-off.
    "FLAGS_sdc_check_every": 0,
    # Peer repairs charged to one rank before the rank is declared a
    # repeat offender: integrity.quarantined_ranks() reports it and the
    # ElasticMeshSupervisor (policy "quarantine") treats it as a lost
    # chip — the PR 11 reform path, not a fleet-wide disk rewind.
    "FLAGS_sdc_quarantine_threshold": 2,
    # Serving shadow audit: this fraction of FINISHED requests (chosen
    # deterministically from the request id) is replayed through
    # generate_from_params and bitwise-compared before the result is
    # delivered. A mismatch refuses delivery, replays the request, and
    # bumps the owning replica's suspicion score. 0.0 = OFF.
    "FLAGS_serving_audit_rate": 0.0,
    # Audit failures charged to one replica before the supervisor fails
    # it over (fresh engine; the corrupted KV pool and prefix cache are
    # discarded before corruption spreads through cached prefixes).
    "FLAGS_serving_audit_threshold": 2,
    # CRC32 end-to-end checksums on disaggregated KV-transfer page
    # payloads (page bytes + quant scale columns, stamped at stream time,
    # verified before install). A mismatched page refuses the transfer;
    # the supervisor re-offers the retained clean payload. Default OFF:
    # payloads carry crc=None and verification is a no-op.
    "FLAGS_kv_transfer_crc": False,
    # Background checkpoint scrub cadence: every Nth save, re-verify the
    # retained snapshots' CRC manifests from _prune and quarantine rot
    # (*.corrupt) BEFORE restore time needs them. 0 = OFF.
    "FLAGS_ckpt_scrub_every": 0,
}


def set_flags(flags: dict):
    for k, v in flags.items():
        _FLAGS[k] = v


def get_flags(flags=None):
    if flags is None:
        return dict(_FLAGS)
    if isinstance(flags, str):
        flags = [flags]
    return {k: _FLAGS.get(k) for k in flags}
