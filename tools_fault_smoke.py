#!/usr/bin/env python
"""Fault-tolerance chaos bench: kill-and-resume + anomaly-guard smoke on
CPU (JAX_PLATFORMS=cpu), exercising the whole recovery stack end to end.

Legs (each seeded, deterministic):

  1. kill-resume     — train an MLP T steps (golden), rerun with a simulated
                       preemption at a pseudo-random step, resume from the
                       latest hardened checkpoint, assert the final params
                       are BITWISE equal to the uninterrupted run
  2. kill-resume-wus — same under FLAGS_weight_update_sharding + dp=8 mesh
                       + accumulate_steps=2 (packed dp-sharded slots)
  3. nan-skip        — poison one batch mid-run under
                       FLAGS_anomaly_policy=skip; assert the step was
                       skipped compiled-side (no host sync added) and the
                       final params are finite
  4. nan-rollback    — K consecutive poisoned batches under rollback;
                       assert the step restored the last checkpoint and
                       training finished finite
  5. io-chaos        — inject transient OSErrors into checkpoint writes and
                       corrupt the latest checkpoint on disk; assert saves
                       retried and restore quarantined + fell back

Serving chaos ladder (run_serving_ladder; the self-healing serving legs):

  6. serve-kill-resume     — abrupt engine kill mid-decode (FaultPlan.
                             kill_at_decode_step, nothing flushed); restore
                             from the last CADENCE snapshot, finish, assert
                             every request's tokens BITWISE equal the
                             uninterrupted run; reports p99 recovery latency
  7. serve-rolling-restart — ServingSupervisor drains+restarts each replica
                             mid-traffic; zero requests dropped, bitwise
  8. serve-snapshot-io     — OSError injected into the snapshot write
                             (retried through the hardened path) + rot the
                             newest snapshot on disk (quarantine + fallback
                             to the previous good one, still bitwise)
  9. serve-stale-heartbeat — one replica's heartbeats suppressed (frozen
                             process); the supervisor fails it over; zero
                             requests dropped, bitwise

Topology-elastic ladder (run_elastic_ladder; the mesh-reforming legs —
each seeded, injected chip loss, zero wall-clock dependence):

 10. elastic-kill-shrink-resume — dp=8 + weight-update sharding, a rank
                             is lost mid-run; the ElasticMeshSupervisor
                             re-forms dp=4 from the survivors and resumes
                             from the resharded snapshot with ZERO manual
                             steps; the resumed dp=4 trajectory is BITWISE
                             identical to an independent dp=4 step
                             restored from the same snapshot, and the
                             final params track the uninterrupted dp=8 run
                             within tolerance (reduce order differs)
 11. elastic-grow-back      — the lost rank returns; the supervisor grows
                             the mesh back to dp=8 (memoized executables
                             reused) and finishes within tolerance
 12. elastic-shrink-accum   — accumulate_steps=2 with the snapshot landing
                             MID accumulation window; the resharded
                             accumulator + micro counter continue the
                             window on the dp=4 mesh

Serving-elastic ladder (run_serving_elastic_ladder; chip-loss reform of
mp groups on mp-portable snapshots):

 13. serve-chip-kill-reform — 2 mp=2 groups on 4 devices; one chip dies,
                              the group re-forms over the survivor (mp=1)
                              from its last snapshot — zero drops,
                              bitwise, reform-latency p99 over trials
 14. serve-degraded-shed-grow-back — the degraded fleet sheds lowest-
                              class backlog with live retry hints, the
                              chip returns, the group grows back with
                              zero drops and ZERO new traces

Silent-data-corruption ladder (run_sdc_ladder; the integrity sentinel —
FLAGS_sdc_check_every fingerprints, peer repair, shadow audit, wire CRC):

 15. sdc-train-bitflip-repair — a mantissa flip on one replica's params
                              is caught by the fused cross-replica
                              fingerprint, localized by majority vote,
                              peer-repaired IN PLACE and the step
                              re-dispatched: final params bitwise equal
                              the fault-free run, zero disk restores,
                              and the verdict rides the guard's one
                              combined fetch (host_syncs == steps)
 16. sdc-train-quarantine   — two flips on the SAME rank cross the
                              repair-charge threshold; the elastic
                              supervisor's quarantine policy reports the
                              chip as LOST (reform, not rewind)
 17. sdc-serve-audit-catch  — FINITE KV corruption (exponent-bit flip)
                              the all-finite guard cannot see; the
                              sampled shadow audit catches the token
                              divergence and fails the replica over —
                              zero drops, bitwise
 18. sdc-kv-wire-crc        — a prefill->decode page payload corrupted
                              on the wire is refused by its CRC32 stamp;
                              the retained stream is re-offered and
                              seats bitwise
 19. sdc-ckpt-scrub         — bit rot in a retained snapshot is found by
                              the cadence scrub and quarantined to
                              *.corrupt before any restore needs it

  python tools_fault_smoke.py [--steps N] [--kill-step K] [--seed S]
                              [--skip-serving] [--skip-elastic]
                              [--skip-sdc]

Prints, machine-greppable:

  FAULT_SMOKE <leg>: <status>  <details>
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


DEFAULT_FLAGS = {
    "FLAGS_anomaly_policy": "off",
    "FLAGS_anomaly_max_bad_steps": 3,
    "FLAGS_grad_comm": "auto",
    "FLAGS_weight_update_sharding": False,
    "FLAGS_allreduce_dtype": "float32",
}


def build_step(paddle, nn, seed, flags=None, mesh=None, k=1):
    paddle.set_flags(dict(DEFAULT_FLAGS))
    if flags:
        paddle.set_flags(flags)
    paddle.seed(seed)
    m = nn.Sequential(nn.Linear(32, 64), nn.GELU(), nn.Dropout(0.1),
                      nn.Linear(64, 8))
    opt = paddle.optimizer.AdamW(0.01, parameters=m.parameters())
    return paddle.jit.TrainStep(m, nn.MSELoss(), opt, mesh=mesh,
                                accumulate_steps=k)


def make_data(steps, seed):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((steps, 16, 32)).astype(np.float32),
            rng.standard_normal((steps, 16, 8)).astype(np.float32))


def run(paddle, step, X, Y, lo=0, hi=None):
    hi = len(X) if hi is None else hi
    loss = None
    for i in range(lo, hi):
        loss = step(paddle.to_tensor(X[i]), paddle.to_tensor(Y[i]))
    return ({n: np.asarray(a) for n, a in step.params.items()},
            float(np.asarray(loss.numpy())) if loss is not None else None)


def leg_kill_resume(paddle, nn, fi, args, flags=None, mesh_fn=None, k=1,
                    name="kill-resume"):
    from paddle_tpu.incubate.checkpoint import CheckpointManager
    X, Y = make_data(args.steps, args.seed)
    mesh = mesh_fn() if mesh_fn else None
    golden, gloss = run(paddle, build_step(paddle, nn, args.seed, flags,
                                           mesh, k), X, Y)

    # pseudo-random but seeded kill point, at least one checkpoint before it
    kill = args.kill_step or (3 + int(
        np.random.default_rng(args.seed).integers(args.steps - 4)))
    ckpt_dir = tempfile.mkdtemp(prefix="fault_smoke_")
    try:
        mesh = mesh_fn() if mesh_fn else None
        step_a = build_step(paddle, nn, args.seed, flags, mesh, k)
        mgr = CheckpointManager(ckpt_dir, async_save=False)
        step_a.attach_checkpoint(mgr, save_every=2)
        try:
            with fi.inject(fi.FaultPlan(preempt_at_step=kill)):
                run(paddle, step_a, X, Y)
            raise AssertionError("preemption did not fire")
        except fi.Preemption:
            pass
        del step_a

        mesh = mesh_fn() if mesh_fn else None
        step_b = build_step(paddle, nn, args.seed + 99, flags, mesh, k)
        step_b.load_state_dict(mgr.restore())
        resumed, rloss = run(paddle, step_b, X, Y, lo=step_b._step)
        for n in golden:
            np.testing.assert_array_equal(golden[n], resumed[n])
        assert rloss == gloss, (rloss, gloss)  # final loss bitwise too
        print(f"FAULT_SMOKE {name}: OK  killed@{kill} "
              f"resumed@{mgr.latest_step()} steps={args.steps} "
              f"final-loss={rloss:.6f} (golden {gloss:.6f}) bitwise-equal")
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def leg_nan_skip(paddle, nn, fi, args):
    from paddle_tpu.jit.train_step import (anomaly_counters,
                                           reset_anomaly_counters)
    X, Y = make_data(args.steps, args.seed)
    reset_anomaly_counters()
    step = build_step(paddle, nn, args.seed,
                      {"FLAGS_anomaly_policy": "skip"})
    poison = args.steps // 2
    with fi.inject(fi.FaultPlan(nan_at_steps=[poison])):
        params, loss = run(paddle, step, X, Y)
    c = anomaly_counters()
    assert c["bad_steps"] == 1 and c["skipped_updates"] == 1, c
    assert c["host_syncs"] == c["steps"], c  # zero extra syncs
    assert all(np.isfinite(v).all() for v in params.values())
    print(f"FAULT_SMOKE nan-skip: OK  poisoned@{poison} "
          f"skipped=1 host-syncs={c['host_syncs']}/{c['steps']} "
          f"final-loss={loss:.6f}")


def leg_nan_rollback(paddle, nn, fi, args):
    from paddle_tpu.incubate.checkpoint import CheckpointManager
    from paddle_tpu.jit.train_step import (anomaly_counters,
                                           reset_anomaly_counters)
    X, Y = make_data(args.steps, args.seed)
    reset_anomaly_counters()
    step = build_step(paddle, nn, args.seed,
                      {"FLAGS_anomaly_policy": "rollback",
                       "FLAGS_anomaly_max_bad_steps": 2})
    ckpt_dir = tempfile.mkdtemp(prefix="fault_smoke_")
    try:
        mgr = CheckpointManager(ckpt_dir, async_save=False)
        step.attach_checkpoint(mgr, save_every=2)
        p = args.steps // 2
        with fi.inject(fi.FaultPlan(nan_at_steps=[p, p + 1])):
            params, loss = run(paddle, step, X, Y)
        c = anomaly_counters()
        assert c["rollbacks"] == 1, c
        assert all(np.isfinite(v).all() for v in params.values())
        print(f"FAULT_SMOKE nan-rollback: OK  poisoned@{p},{p + 1} "
              f"rollbacks=1 final-loss={loss:.6f}")
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def leg_io_chaos(paddle, fi, args):
    from paddle_tpu.incubate.checkpoint import (CheckpointManager,
                                                ckpt_counters)
    ckpt_dir = tempfile.mkdtemp(prefix="fault_smoke_")
    try:
        before = ckpt_counters()
        mgr = CheckpointManager(ckpt_dir, async_save=False, retries=3,
                                retry_backoff=0.01)
        with fi.inject(fi.FaultPlan(io_error_on_writes=[1, 3])):
            mgr.save(1, {"w": np.arange(16.0), "step": 1})
            mgr.save(2, {"w": np.full(16, 2.0), "step": 2})
        retries = ckpt_counters()["save_retries"] - before["save_retries"]
        # rot the newest step on disk
        with open(os.path.join(ckpt_dir, "step_2", "state.pdckpt"),
                  "r+b") as f:
            f.seek(-8, 2)
            f.write(b"\x00" * 8)
        got = mgr.restore()
        assert int(got["step"]) == 1, got
        quarantined = (ckpt_counters()["quarantined"]
                       - before["quarantined"])
        assert quarantined == 1
        print(f"FAULT_SMOKE io-chaos: OK  transient-errors=2 "
              f"retries={retries} corrupt-quarantined={quarantined} "
              f"fell-back-to=step_1")
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


# -- serving chaos ladder -----------------------------------------------------

_SERVING = None


def _serving_fixture():
    """Tiny GPT + helpers, built once (executables are memoized per config,
    so every leg reuses the same compiled fused step)."""
    global _SERVING
    if _SERVING is not None:
        return _SERVING
    import jax as _jax

    from paddle_tpu import serving
    from paddle_tpu.models.generation import generate_from_params
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.models.gpt_hybrid import init_gpt_params

    cfg = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
                    max_seq_len=128, dropout=0.0, use_flash=False,
                    compute_dtype="float32", remat=False)
    params = init_gpt_params(cfg, _jax.random.key(0))

    def factory():
        return serving.Engine(params=params, config=cfg, num_slots=3,
                              max_seq_len=96, page_size=8, prefill_chunk=8)

    def ref(prompt, n, **kw):
        out = np.asarray(generate_from_params(
            params, np.asarray(prompt)[None], cfg, max_new_tokens=n,
            **kw)._data)
        return out[0, len(prompt):].tolist()

    def traffic(n, seed):
        rng = np.random.default_rng(seed)
        reqs = []
        for i in range(n):
            kw = ({"do_sample": True, "temperature": 0.7 + 0.1 * i,
                   "top_p": 0.85, "seed": 11 + i} if i % 2 else {})
            reqs.append(serving.Request(rng.integers(0, 97, 5 + 2 * (i % 4)),
                                        max_new_tokens=4 + (i % 3), **kw))
        return reqs

    def golden(reqs):
        out = {}
        for r in reqs:
            kw = ({"do_sample": True, "temperature": r.temperature,
                   "top_p": r.top_p, "seed": r.seed} if r.do_sample else {})
            out[r.request_id] = ref(r.prompt, r.max_new_tokens, **kw)
        return out

    _SERVING = (serving, factory, ref, traffic, golden)
    _SERVING_PC.update(params=params, cfg=cfg)
    return _SERVING


_SERVING_PC = {}


def _mp_factory(**kw):
    """Two-arg (idx, mesh) factory over the shared fixture params — the
    topology-elastic supervisor's deployment shape (a replica = an mp
    group whose mesh changes across reforms)."""
    from paddle_tpu import serving
    _serving_fixture()
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)

    def factory(i, mesh):
        return serving.Engine(params=_SERVING_PC["params"],
                              config=_SERVING_PC["cfg"], mesh=mesh,
                              comm_backend="gspmd", **kw)

    return factory


def _check_bitwise(results, reqs, golden):
    missing = [r.request_id for r in reqs if r.request_id not in results]
    wrong = [r.request_id for r in reqs if r.request_id in results
             and results[r.request_id].tokens != golden[r.request_id]]
    return len(missing), not (missing or wrong)


def leg_serve_kill_resume(trials, n_reqs, seed):
    """Abrupt kill mid-decode; recover from the last cadence snapshot."""
    import time

    from paddle_tpu.incubate.checkpoint import CheckpointManager
    from paddle_tpu.utils import fault_injection as fi

    serving, factory, _, traffic, golden = _serving_fixture()
    dropped, bitwise, lat = 0, True, []
    for t in range(trials):
        reqs = traffic(n_reqs, seed + t)
        gold = golden(reqs)
        d = tempfile.mkdtemp(prefix="serve_chaos_")
        try:
            mgr = CheckpointManager(d, async_save=False,
                                    site="serving_snapshot")
            eng = factory().attach_checkpoint(mgr, every=2)
            results = {}
            with fi.inject(fi.FaultPlan(kill_at_decode_step=4 + t)):
                for r in reqs:
                    eng.submit(r)
                try:
                    while eng.step():
                        results.update(eng.pop_results())
                    raise AssertionError("kill did not fire")
                except fi.Preemption:
                    t_kill = time.perf_counter()
                del eng                         # the process is gone
                eng2 = factory().attach_checkpoint(mgr, every=0)
                eng2.load_state_dict(mgr.restore())
                eng2.step()                     # serving again
                lat.append(time.perf_counter() - t_kill)
                results.update(eng2.run())
            miss, ok = _check_bitwise(results, reqs, gold)
            dropped += miss
            bitwise &= ok
        finally:
            shutil.rmtree(d, ignore_errors=True)
    p99 = float(np.percentile(lat, 99)) if lat else 0.0
    return {"bitwise": bitwise, "dropped": dropped, "recovery_p99_s": p99,
            "trials": trials}


def leg_serve_rolling_restart(n_reqs, seed):
    from paddle_tpu.serving.supervisor import ServingSupervisor

    serving, factory, _, traffic, golden = _serving_fixture()
    reqs = traffic(n_reqs, seed)
    gold = golden(reqs)
    d = tempfile.mkdtemp(prefix="serve_chaos_")
    try:
        sup = ServingSupervisor(factory, num_replicas=2, snapshot_dir=d)
        for r in reqs:
            sup.submit(r)
        for _ in range(2):
            sup.step()
        sup.rolling_restart()
        results = sup.run()
        miss, ok = _check_bitwise(results, reqs, gold)
        return {"bitwise": ok, "dropped": miss,
                "alive": sup.alive_replicas}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def leg_serve_snapshot_io(seed):
    """Snapshot write chaos + on-disk rot: retry, quarantine, fall back."""
    from paddle_tpu.incubate.checkpoint import CheckpointManager, ckpt_counters
    from paddle_tpu.utils import fault_injection as fi

    serving, factory, ref, traffic, golden = _serving_fixture()
    reqs = traffic(3, seed)
    gold = golden(reqs)
    d = tempfile.mkdtemp(prefix="serve_chaos_")
    try:
        before = ckpt_counters()
        mgr = CheckpointManager(d, async_save=False, retries=2,
                                retry_backoff=0.01, site="serving_snapshot")
        eng = factory().attach_checkpoint(mgr, every=0)
        for r in reqs:
            eng.submit(r)
        with fi.inject(fi.FaultPlan(io_error_on_snapshots=[1])):
            for _ in range(3):
                eng.step()
            eng.save_snapshot()         # injected OSError -> retried
            for _ in range(2):
                eng.step()
            eng.save_snapshot()
        retries = ckpt_counters()["save_retries"] - before["save_retries"]
        newest = mgr.latest_step()
        with open(os.path.join(d, f"step_{newest}", "state.pdckpt"),
                  "r+b") as f:
            f.seek(-8, 2)
            f.write(b"\x00" * 8)        # rot the newest snapshot
        results = dict(eng.pop_results())
        eng2 = factory()
        eng2.load_state_dict(mgr.restore())   # quarantines + falls back
        quarantined = ckpt_counters()["quarantined"] - before["quarantined"]
        results.update(eng2.run())
        miss, ok = _check_bitwise(results, reqs, gold)
        return {"recovered": ok and quarantined == 1 and retries == 1,
                "dropped": miss, "retries": retries,
                "quarantined": quarantined,
                "fell_back_to": mgr.last_restored_step}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def leg_serve_stale_heartbeat(seed):
    import time

    from paddle_tpu.serving.supervisor import ServingSupervisor
    from paddle_tpu.utils import fault_injection as fi

    serving, factory, _, traffic, golden = _serving_fixture()
    reqs = traffic(4, seed)
    gold = golden(reqs)
    d = tempfile.mkdtemp(prefix="serve_chaos_")
    try:
        sup = ServingSupervisor(
            factory, num_replicas=2, snapshot_dir=os.path.join(d, "snap"),
            snapshot_every=2, heartbeat_dir=os.path.join(d, "hb"),
            heartbeat_timeout=0.05)
        with fi.inject(fi.FaultPlan(stale_heartbeat_ranks=[1])):
            for r in reqs:
                sup.submit(r)
            for _ in range(3):
                sup.step()
            time.sleep(0.1)             # replica1's heartbeat file rots
            results = sup.run()
        miss, ok = _check_bitwise(results, reqs, gold)
        return {"bitwise": ok, "dropped": miss,
                "heartbeats_dropped": fi.stats()["heartbeats_dropped"]}
    finally:
        shutil.rmtree(d, ignore_errors=True)


# ---------------------------------------------------------------------------
# topology-elastic ladder (mesh-reforming supervisor + reshard-on-load)
# ---------------------------------------------------------------------------

ELASTIC_FLAGS = {"FLAGS_grad_comm": "on",
                 "FLAGS_weight_update_sharding": True}


def _elastic_fixture(seed, k=1, width=16, rows=16, steps=12):
    """(factory, batch_fn, golden_params) for one elastic leg: a dp-mesh
    TrainStep factory under weight-update sharding, a deterministic
    global-batch schedule, and the uninterrupted dp=8 golden params."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed import env as dist_env

    def factory(mesh):
        paddle.set_flags(dict(DEFAULT_FLAGS))
        paddle.set_flags(ELASTIC_FLAGS)
        paddle.seed(seed)
        m = nn.Sequential(nn.Linear(width, width), nn.GELU(),
                          nn.Linear(width, 8))
        opt = paddle.optimizer.AdamW(0.01, parameters=m.parameters())
        return paddle.jit.TrainStep(m, nn.MSELoss(), opt, mesh=mesh,
                                    accumulate_steps=k)

    rng = np.random.default_rng(seed)
    X = rng.standard_normal((steps, rows, width)).astype(np.float32)
    Y = rng.standard_normal((steps, rows, 8)).astype(np.float32)
    batch_fn = lambda t: (X[t], Y[t])  # noqa: E731

    mesh = dist_env.create_hybrid_mesh(dp=8)
    g = factory(mesh)
    for i in range(steps):
        g(paddle.to_tensor(X[i]), paddle.to_tensor(Y[i]))
    golden = {n: np.asarray(a) for n, a in g.params.items()}
    dist_env.set_mesh(None)
    return factory, batch_fn, golden


def _max_dev(a, b):
    import numpy as np
    return max(float(np.abs(a[n] - np.asarray(b[n])).max()) for n in a)


def leg_elastic_kill_shrink(seed, steps=12, kill_step=5, save_every=2,
                            k=1, name="elastic-kill-shrink-resume"):
    """Kill one rank mid-run on dp=8; the supervisor re-forms dp=4 and
    resumes from the resharded snapshot. Gates: the shrink happened with
    zero manual steps, the post-shrink trajectory is BITWISE identical to
    an independent dp=4 restore of the same snapshot, and the final
    params track the uninterrupted dp=8 run within tolerance."""
    import tempfile

    import numpy as np
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.distributed import elastic, env as dist_env
    from paddle_tpu.incubate.checkpoint import CheckpointManager
    from paddle_tpu.utils import fault_injection as fi

    factory, batch_fn, golden = _elastic_fixture(seed, k=k, steps=steps)
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, async_save=False, keep_last_n=50)
        sup = elastic.ElasticMeshSupervisor(
            factory, mgr, global_batch=16, save_every=save_every, grow=False)
        with fi.inject(fi.FaultPlan(chip_loss_at={kill_step: [2]})):
            step = sup.run(batch_fn, steps)
        final = {n: np.asarray(a) for n, a in step.params.items()}
        shrinks = [e for e in sup.events if e["kind"] == "shrink"]
        restored = shrinks[0]["restored_step"] if shrinks else None
        # independent dp=4 resume from the SAME snapshot: bitwise gate
        bitwise = False
        if restored is not None:
            dist_env.set_mesh(None)
            mesh4 = dist_env.create_hybrid_mesh(
                dp=4, devices=[jax.devices()[r] for r in (0, 1, 3, 4)])
            ref = factory(mesh4)
            ref.load_state_dict(mgr.restore(restored))
            for t in range(restored, steps):
                x, y = batch_fn(t)
                ref(paddle.to_tensor(x), paddle.to_tensor(y))
            bitwise = all(
                np.array_equal(final[n], np.asarray(a))
                for n, a in ref.params.items())
        dev = _max_dev(golden, final)
        out = {"name": name,
               "shrank": bool(shrinks) and shrinks[0]["dp"] == 4,
               "restored_step": restored, "bitwise_vs_dp4": bitwise,
               "max_dev_vs_dp8": dev, "tol": 2e-3,
               "events": [(e["kind"], e["dp"]) for e in sup.events],
               "counters": profiler.elastic_counters()}
        out["ok"] = out["shrank"] and bitwise and dev < out["tol"]
    dist_env.set_mesh(None)
    paddle.set_flags(dict(DEFAULT_FLAGS))
    return out


def leg_elastic_grow_back(seed, steps=12, kill_step=4, return_step=8,
                          save_every=2):
    """The lost rank returns mid-run: the supervisor grows the mesh back
    (dp=8 again, kill of rank 0 makes the shrunk mesh NON-contiguous) and
    finishes within tolerance of the uninterrupted run."""
    import tempfile

    import numpy as np
    from paddle_tpu import profiler
    import paddle_tpu as paddle
    from paddle_tpu.distributed import elastic, env as dist_env
    from paddle_tpu.incubate.checkpoint import CheckpointManager
    from paddle_tpu.utils import fault_injection as fi

    factory, batch_fn, golden = _elastic_fixture(seed, steps=steps)
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, async_save=False, keep_last_n=50)
        sup = elastic.ElasticMeshSupervisor(
            factory, mgr, global_batch=16, save_every=save_every)
        with fi.inject(fi.FaultPlan(chip_loss_at={kill_step: [0]},
                                    chip_return_at={return_step: [0]})):
            step = sup.run(batch_fn, steps)
        final = {n: np.asarray(a) for n, a in step.params.items()}
        kinds = [e["kind"] for e in sup.events]
        dev = _max_dev(golden, final)
        out = {"name": "elastic-grow-back",
               "shrank": "shrink" in kinds, "grew": "grow" in kinds,
               "final_dp": sup.dp, "max_dev_vs_dp8": dev, "tol": 2e-3,
               "events": [(e["kind"], e["dp"]) for e in sup.events],
               "counters": profiler.elastic_counters()}
        out["ok"] = (out["shrank"] and out["grew"] and sup.dp == 8
                     and dev < out["tol"])
    dist_env.set_mesh(None)
    paddle.set_flags(dict(DEFAULT_FLAGS))
    return out


def leg_elastic_shrink_accum(seed, steps=12, kill_step=5, save_every=3):
    """accumulate_steps=2 with the snapshot cadence landing MID
    accumulation window: the resharded accumulator + micro counter must
    continue the window consistently on the shrunk mesh."""
    out = leg_elastic_kill_shrink(seed, steps=steps, kill_step=kill_step,
                                  save_every=save_every, k=2,
                                  name="elastic-shrink-accum")
    out["name"] = "elastic-shrink-accum"
    # save_every=3 with k=2: snapshots at micro 3 and 9 are mid-window
    out["mid_window_restore"] = out["restored_step"] is not None and \
        out["restored_step"] % 2 == 1
    out["ok"] = out["ok"] and out["mid_window_restore"]
    return out


def run_elastic_ladder(deterministic=False, seed=7):
    """The topology-elastic chaos ladder. ``deterministic=True`` is the
    fast tier-1 sub-rung (kill-shrink-resume + grow-back at small step
    counts); the full ladder adds the mid-accumulation-window shrink and
    prints machine-greppable lines. Every leg is injected chip loss —
    zero wall-clock dependence."""
    from paddle_tpu import profiler

    profiler.reset_elastic_counters()
    if deterministic:
        ks = leg_elastic_kill_shrink(seed, steps=8, kill_step=4)
        gb = leg_elastic_grow_back(seed + 1, steps=8, kill_step=3,
                                   return_step=6)
        return {"kill_shrink": ks, "grow_back": gb,
                "ok": ks["ok"] and gb["ok"],
                "elastic": profiler.elastic_counters()}
    ks = leg_elastic_kill_shrink(seed)
    print(f"FAULT_SMOKE elastic-kill-shrink-resume: "
          f"{'OK' if ks['ok'] else 'FAIL'}  dp8->dp4 "
          f"restored=step_{ks['restored_step']} "
          f"bitwise-vs-independent-dp4={ks['bitwise_vs_dp4']} "
          f"max-dev-vs-dp8={ks['max_dev_vs_dp8']:.2e}")
    gb = leg_elastic_grow_back(seed + 1)
    print(f"FAULT_SMOKE elastic-grow-back: "
          f"{'OK' if gb['ok'] else 'FAIL'}  events={gb['events']} "
          f"final-dp={gb['final_dp']} "
          f"max-dev-vs-dp8={gb['max_dev_vs_dp8']:.2e}")
    sa = leg_elastic_shrink_accum(seed + 2)
    print(f"FAULT_SMOKE elastic-shrink-accum: "
          f"{'OK' if sa['ok'] else 'FAIL'}  "
          f"mid-window-restore={sa['mid_window_restore']} "
          f"bitwise-vs-independent-dp4={sa['bitwise_vs_dp4']} "
          f"max-dev-vs-dp8={sa['max_dev_vs_dp8']:.2e}")
    out = {"kill_shrink": ks, "grow_back": gb, "shrink_accum": sa,
           "ok": ks["ok"] and gb["ok"] and sa["ok"],
           "elastic": profiler.elastic_counters()}
    print(f"FAULT_SMOKE elastic-ladder: {'OK' if out['ok'] else 'FAIL'}  "
          f"{profiler.elastic_summary()}")
    return out


def leg_serve_chip_kill_reform(trials, n_reqs, seed):
    """One chip of an mp=2 group dies mid-traffic: the supervisor marks
    the whole group down deterministically, re-forms it over the
    surviving chip through the MP-PORTABLE snapshot path and completes
    every request bitwise with zero drops. Recovery latency is the
    elastic ledger's measured reform wall time."""
    import jax as _jax

    from paddle_tpu import profiler
    from paddle_tpu.serving.supervisor import ServingSupervisor
    from paddle_tpu.utils import fault_injection as fi

    serving, _, _, traffic, golden = _serving_fixture()
    factory = _mp_factory()
    dropped, bitwise, degraded_ok, lat = 0, True, True, []
    for t in range(trials):
        reqs = traffic(n_reqs, seed + t)
        gold = golden(reqs)
        d = tempfile.mkdtemp(prefix="serve_elastic_")
        try:
            with fi.inject(fi.FaultPlan(
                    serving_chip_loss_at={3 + t: (1,)})):
                sup = ServingSupervisor(factory, num_replicas=2, mp=2,
                                        devices=_jax.devices()[:4],
                                        snapshot_dir=d, snapshot_every=2)
                results = sup.run(reqs)
                degraded_ok &= sup.telemetry()["replica0"]["mp"] == 1
                sup.shutdown()
            lat.append(
                profiler.elastic_counters()["reform_latency_s_last"])
            miss, ok = _check_bitwise(results, reqs, gold)
            dropped += miss
            bitwise &= ok
        finally:
            shutil.rmtree(d, ignore_errors=True)
    p99 = float(np.percentile(lat, 99)) if lat else 0.0
    return {"bitwise": bitwise and degraded_ok, "dropped": dropped,
            "recovery_p99_s": p99, "trials": trials}


def leg_serve_degraded_shed_grow_back(seed, n_reqs=16):
    """Degraded-capacity operation end to end: a chip loss halves group
    0, the sustained backlog sheds lowest-class work with live
    retry_after hints, the chip returns and the group grows back with
    ZERO new traces (memoized builders); every non-shed request
    completes bitwise, zero drops."""
    import jax as _jax

    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.serving.supervisor import ServingSupervisor
    from paddle_tpu.utils import fault_injection as fi

    serving, _, ref, _, _ = _serving_fixture()
    _shed_keys = ("FLAGS_serving_shed_high", "FLAGS_serving_shed_low",
                  "FLAGS_serving_shed_window")
    _saved = {k: paddle.get_flags()[k] for k in _shed_keys}
    paddle.set_flags({"FLAGS_serving_shed_high": 0.3,
                      "FLAGS_serving_shed_low": 0.1,
                      "FLAGS_serving_shed_window": 2})
    factory = _mp_factory(max_queue=12, shed=True)
    rng = np.random.default_rng(seed)
    reqs = [serving.Request(rng.integers(0, 97, 5 + (i % 3)),
                            max_new_tokens=6 + (i % 3),
                            priority="best_effort" if i % 2 else "batch")
            for i in range(n_reqs)]
    d = tempfile.mkdtemp(prefix="serve_elastic_")
    try:
        # loss only — NO scheduled return: the whole run serves degraded,
        # so the traces baseline below is captured BEFORE the grow-back
        # (a return firing inside run() would grow early and make the
        # zero-retraces comparison vacuously compare post-grow to itself)
        with fi.inject(fi.FaultPlan(serving_chip_loss_at={2: (1,)})):
            sup = ServingSupervisor(factory, num_replicas=2, mp=2,
                                    devices=_jax.devices()[:4],
                                    snapshot_dir=d, snapshot_every=2)
            results = sup.run(reqs)
            degraded = sup.telemetry()["replica0"]["mp"] == 1
        # plan deactivated = the chip came back: grow in the guard loop
        traces = profiler.serving_counters()["paged_traces"]
        guard = 0
        while sup.telemetry()["replica0"]["mp"] != 2 and guard < 64:
            sup.step()
            guard += 1
        grown = degraded and sup.telemetry()["replica0"]["mp"] == 2
        no_retrace = \
            profiler.serving_counters()["paged_traces"] == traces
        sup.shutdown()
        miss = [r for r in reqs if r.request_id not in results]
        shed = [r for r in reqs if r.request_id in results
                and results[r.request_id].finish_reason == "shed"]
        done = [r for r in reqs if r.request_id in results
                and results[r.request_id].finish_reason
                in ("stop", "length")]
        bitwise = all(results[r.request_id].tokens
                      == ref(r.prompt, r.max_new_tokens) for r in done)
        hints = all(results[r.request_id].retry_after is not None
                    for r in shed)
        return {"ok": (bitwise and hints and grown and no_retrace
                       and not miss and len(shed) > 0),
                "dropped": len(miss), "shed": len(shed),
                "completed": len(done), "bitwise": bitwise,
                "retry_hints": hints, "grew_back": grown,
                "zero_retraces": no_retrace}
    finally:
        paddle.set_flags(_saved)
        shutil.rmtree(d, ignore_errors=True)


def run_serving_elastic_ladder(deterministic=False, seed=7):
    """The topology-elastic SERVING ladder (chip-loss reform of mp groups
    on mp-portable snapshots). ``deterministic=True`` is the fast tier-1
    sub-rung: one chip-kill-reform trial + the degraded-shed-grow-back
    leg at tiny traffic. The full ladder runs several kill trials and
    reports the reform recovery-latency p99. Every leg is injected chip
    loss — zero wall-clock dependence; requests_dropped must be 0."""
    from paddle_tpu import profiler

    profiler.reset_serving_counters()
    if deterministic:
        ck = leg_serve_chip_kill_reform(trials=1, n_reqs=4, seed=seed)
        gb = leg_serve_degraded_shed_grow_back(seed + 40, n_reqs=10)
        dropped = ck["dropped"] + gb["dropped"]
        return {"chip_kill_reform": ck, "shed_grow_back": gb,
                "requests_dropped": dropped,
                "ok": ck["bitwise"] and gb["ok"] and dropped == 0,
                "elastic": profiler.elastic_counters()}
    ck = leg_serve_chip_kill_reform(trials=3, n_reqs=6, seed=seed)
    print(f"FAULT_SMOKE serve-chip-kill-reform: "
          f"{'OK' if ck['bitwise'] and not ck['dropped'] else 'FAIL'}  "
          f"trials={ck['trials']} dropped={ck['dropped']} "
          f"reform-p99={ck['recovery_p99_s'] * 1e3:.0f}ms "
          f"bitwise-equal-degraded")
    gb = leg_serve_degraded_shed_grow_back(seed + 40, n_reqs=16)
    print(f"FAULT_SMOKE serve-degraded-shed-grow-back: "
          f"{'OK' if gb['ok'] else 'FAIL'}  shed={gb['shed']} "
          f"completed={gb['completed']} dropped={gb['dropped']} "
          f"grew-back={gb['grew_back']} zero-retraces={gb['zero_retraces']}")
    dropped = ck["dropped"] + gb["dropped"]
    out = {"chip_kill_reform": ck, "shed_grow_back": gb,
           "requests_dropped": dropped,
           "ok": ck["bitwise"] and gb["ok"] and dropped == 0,
           "elastic": profiler.elastic_counters()}
    print(f"FAULT_SMOKE serving-elastic-ladder: "
          f"{'OK' if out['ok'] else 'FAIL'}  "
          f"requests-dropped={dropped}  {profiler.elastic_summary()}")
    return out


def run_serving_ladder(quick=True, deterministic=False, seed=7):
    """The serving chaos ladder. ``deterministic=True`` is the fast tier-1
    sub-rung: kill-resume + rolling-restart only, tiny traffic, no
    wall-clock reporting. The full ladder adds snapshot-IO chaos,
    stale-heartbeat failover and p99 recovery latency over several kill
    trials. Returns a machine-readable dict; total requests_dropped must
    be 0."""
    from paddle_tpu import profiler

    profiler.reset_serving_counters()
    if deterministic:
        kr = leg_serve_kill_resume(trials=1, n_reqs=4, seed=seed)
        rr = leg_serve_rolling_restart(n_reqs=4, seed=seed + 50)
        out = {"kill_resume": kr, "rolling_restart": rr,
               "requests_dropped": kr["dropped"] + rr["dropped"]}
        out["recovery"] = profiler.recovery_counters()
        return out
    trials = 3 if quick else 5
    kr = leg_serve_kill_resume(trials=trials, n_reqs=6, seed=seed)
    print(f"FAULT_SMOKE serve-kill-resume: "
          f"{'OK' if kr['bitwise'] and not kr['dropped'] else 'FAIL'}  "
          f"trials={kr['trials']} dropped={kr['dropped']} "
          f"recovery-p99={kr['recovery_p99_s'] * 1e3:.0f}ms bitwise-equal")
    rr = leg_serve_rolling_restart(n_reqs=6, seed=seed + 50)
    print(f"FAULT_SMOKE serve-rolling-restart: "
          f"{'OK' if rr['bitwise'] and not rr['dropped'] else 'FAIL'}  "
          f"dropped={rr['dropped']} alive={rr['alive']}/2 bitwise-equal")
    io = leg_serve_snapshot_io(seed=seed + 100)
    print(f"FAULT_SMOKE serve-snapshot-io: "
          f"{'OK' if io['recovered'] and not io['dropped'] else 'FAIL'}  "
          f"retries={io['retries']} quarantined={io['quarantined']} "
          f"fell-back-to=step_{io['fell_back_to']} dropped={io['dropped']}")
    hb = leg_serve_stale_heartbeat(seed=seed + 150)
    print(f"FAULT_SMOKE serve-stale-heartbeat: "
          f"{'OK' if hb['bitwise'] and not hb['dropped'] else 'FAIL'}  "
          f"beats-suppressed={hb['heartbeats_dropped']} "
          f"dropped={hb['dropped']} bitwise-equal")
    out = {"kill_resume": kr, "rolling_restart": rr, "snapshot_io": io,
           "stale_heartbeat": hb,
           "requests_dropped": (kr["dropped"] + rr["dropped"]
                                + io["dropped"] + hb["dropped"]),
           "recovery_p99_s": kr["recovery_p99_s"]}
    out["recovery"] = profiler.recovery_counters()
    print(f"FAULT_SMOKE serving-ladder: "
          f"{'OK' if out['requests_dropped'] == 0 else 'FAIL'}  "
          f"requests-dropped={out['requests_dropped']} "
          f"recovery-p99={out['recovery_p99_s'] * 1e3:.0f}ms  "
          f"{out['recovery']}")
    return out


# ---------------------------------------------------------------------------
# silent-data-corruption (SDC) ladder — fingerprints, peer repair, shadow
# audit, wire CRC, at-rest scrub


_SDC_FLAG_DEFAULTS = {
    "FLAGS_sdc_check_every": 0,
    "FLAGS_sdc_quarantine_threshold": 2,
    "FLAGS_serving_audit_rate": 0.0,
    "FLAGS_serving_audit_threshold": 2,
    "FLAGS_kv_transfer_crc": False,
}


def _sdc_train_run(flags, plan=None, steps=6, seed=7):
    """One short dp=8 data-parallel run under ``flags`` (and an optional
    fault plan); returns (loss, params, sdc counters, anomaly counters)."""
    import contextlib

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed import env as dist_env, integrity
    from paddle_tpu.jit.train_step import (anomaly_counters,
                                           reset_anomaly_counters)
    from paddle_tpu.utils import fault_injection as fi

    integrity.reset_sdc_counters()
    reset_anomaly_counters()
    dist_env.set_mesh(None)
    merged = dict(_SDC_FLAG_DEFAULTS)
    merged["FLAGS_grad_comm"] = "on"
    merged.update(flags or {})
    step = build_step(paddle, nn, seed, flags=merged,
                      mesh=dist_env.create_hybrid_mesh(dp=8))
    X, Y = make_data(steps, seed + 1)
    ctx = fi.inject(plan) if plan is not None else contextlib.nullcontext()
    with ctx:
        params, loss = run(paddle, step, X, Y)
    out = (loss, params, dict(integrity.sdc_counters()),
           dict(anomaly_counters()))
    dist_env.set_mesh(None)
    paddle.set_flags(dict(_SDC_FLAG_DEFAULTS))
    return out


def leg_sdc_train_repair(steps, seed, deterministic=False):
    """A mantissa bit flip lands on one replica's replicated params: the
    fused fingerprint catches it at the next check boundary, the majority
    vote localizes the minority replica, the peer repair rewrites its
    bytes in place and re-dispatches the SAME step — the final params are
    BITWISE the fault-free run's, with zero disk restores and zero steps
    lost. Also asserts the exactness contract (sdc-on clean == sdc-off
    clean, bitwise) and, in the full rung, that the verdict rides the
    guard's existing combined fetch (host_syncs == steps)."""
    from paddle_tpu.utils import fault_injection as fi

    g_loss, g_params, _, _ = _sdc_train_run({}, steps=steps, seed=seed)
    c_loss, c_params, c_sdc, _ = _sdc_train_run(
        {"FLAGS_sdc_check_every": 1}, steps=steps, seed=seed)
    clean_ok = (c_loss == g_loss
                and all(np.array_equal(c_params[n], g_params[n])
                        for n in g_params)
                and c_sdc["fingerprint_checks"] == steps
                and c_sdc["fingerprint_mismatches"] == 0)
    plan = fi.FaultPlan(bitflip_at={2: (3, None, 12)})
    f_loss, f_params, f_sdc, _ = _sdc_train_run(
        {"FLAGS_sdc_check_every": 1}, plan=plan, steps=steps, seed=seed)
    repaired_ok = (f_sdc["fingerprint_mismatches"] == 1
                   and f_sdc["repairs"] == 1
                   and f_sdc["repair_redispatches"] == 1
                   and f_sdc.get("repairs_rank3") == 1)
    bitwise = (f_loss == g_loss
               and all(np.array_equal(f_params[n], g_params[n])
                       for n in g_params))
    syncs_ok = True
    if not deterministic:
        _, _, _, s_an = _sdc_train_run(
            {"FLAGS_sdc_check_every": 1, "FLAGS_anomaly_policy": "skip"},
            steps=steps, seed=seed)
        syncs_ok = (s_an["steps"] == steps
                    and s_an["host_syncs"] == steps)
    return {"ok": clean_ok and repaired_ok and bitwise and syncs_ok,
            "clean_bitwise": clean_ok, "repaired": repaired_ok,
            "bitwise": bitwise, "host_syncs_flat": syncs_ok,
            "sdc": f_sdc}


def leg_sdc_train_quarantine(steps, seed):
    """A repeat offender: two flips land on the SAME rank across the run.
    Every one is repaired in place (training never rewinds), the repair
    ledger charges the rank, and once the charge crosses
    FLAGS_sdc_quarantine_threshold the quarantine policy reports the chip
    to the elastic supervisor's failure detector as LOST."""
    from paddle_tpu.distributed import integrity
    from paddle_tpu.distributed.elastic import ElasticMeshSupervisor
    from paddle_tpu.utils import fault_injection as fi

    plan = fi.FaultPlan(bitflip_at={1: (2, None, 12), 3: (2, None, 14)})
    loss, _, sdc, _ = _sdc_train_run(
        {"FLAGS_sdc_check_every": 1, "FLAGS_sdc_quarantine_threshold": 2},
        plan=plan, steps=steps, seed=seed)
    charged = sdc.get("repairs_rank2") == 2 and sdc["repairs"] == 2
    # the ledger survives the run teardown until reset: re-arm the
    # threshold flag and ask the detector what it would do about it
    import paddle_tpu as paddle
    paddle.set_flags({"FLAGS_sdc_check_every": 1,
                      "FLAGS_sdc_quarantine_threshold": 2})
    for _ in range(2):
        integrity.note_repair(2)
    sup = ElasticMeshSupervisor(lambda *a, **kw: None, None, 8,
                                quarantine=True)
    detected = 2 in sup._detect(0)
    sup_off = ElasticMeshSupervisor(lambda *a, **kw: None, None, 8)
    policy_gated = 2 not in sup_off._detect(0)
    integrity.reset_sdc_counters()
    paddle.set_flags(dict(_SDC_FLAG_DEFAULTS))
    return {"ok": charged and detected and policy_gated,
            "charged": charged, "detected": detected,
            "policy_gated": policy_gated, "loss_finite": np.isfinite(loss)}


def leg_sdc_serve_audit(seed):
    """FINITE KV-cache corruption on one replica: the all-finite anomaly
    guard is blind to it, but the sampled shadow audit replays finished
    greedy requests through the raw-params oracle, catches the token
    divergence, charges suspicion, and fails the replica over through the
    ordinary reform path — zero drops, every delivered stream bitwise."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import integrity
    from paddle_tpu.serving.supervisor import ServingSupervisor
    from paddle_tpu.utils import fault_injection as fi

    serving, factory, ref, _, _ = _serving_fixture()
    integrity.reset_sdc_counters()
    rng = np.random.default_rng(seed)
    reqs = [serving.Request(rng.integers(0, 97, 6 + (i % 3)),
                            max_new_tokens=8) for i in range(4)]
    gold = {r.request_id: ref(r.prompt, 8) for r in reqs}
    paddle.set_flags({"FLAGS_serving_audit_rate": 1.0,
                      "FLAGS_serving_audit_threshold": 1})
    try:
        sup = ServingSupervisor(factory, num_replicas=2,
                                audit_ref=(_SERVING_PC["params"],
                                           _SERVING_PC["cfg"]))
        # flip the top exponent bit of dim 0 of EVERY position's key in
        # one page of replica0's live pool: huge but FINITE numbers that
        # saturate the softmax — invisible to any isfinite sweep, fatal
        # to the owning stream's tokens (2048 bits span one position)
        flips = [(1, 0, 2048 * p + 30) for p in range(8)]
        with fi.inject(fi.FaultPlan(kv_bitflip_at={2: flips},
                                    kv_bitflip_engine_tag="replica0")):
            results = sup.run(reqs)
        sup.shutdown()
    finally:
        paddle.set_flags(dict(_SDC_FLAG_DEFAULTS))
    s = integrity.sdc_counters()
    miss = [r.request_id for r in reqs if r.request_id not in results]
    wrong = [r.request_id for r in reqs if r.request_id in results
             and list(results[r.request_id].tokens) != gold[r.request_id]]
    stats = fi.stats()
    integrity.reset_sdc_counters()
    return {"ok": (not miss and not wrong and s["audit_failures"] >= 1
                   and stats["kv_bitflips"] == 8),
            "dropped": len(miss), "wrong": len(wrong),
            "audits": s["audits"], "audit_failures": s["audit_failures"]}


def leg_sdc_kv_wire_crc(seed):
    """A KV page payload is corrupted ON THE WIRE between the prefill and
    decode workers: the CRC32 stamped at stream time refuses the seat,
    the transfer is dropped (typed, counted), the supervisor re-offers
    the RETAINED clean payloads, and the stream seats bitwise."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import integrity
    from paddle_tpu.serving import metrics as smetrics
    from paddle_tpu.serving.supervisor import ServingSupervisor
    from paddle_tpu.utils import fault_injection as fi

    serving, factory, ref, _, _ = _serving_fixture()
    integrity.reset_sdc_counters()
    before = smetrics.serving_counters()["transfer_crc_refusals"]
    rng = np.random.default_rng(seed)
    reqs = [serving.Request(rng.integers(0, 97, 13 + 4 * i),
                            max_new_tokens=4) for i in range(3)]
    gold = {r.request_id: ref(r.prompt, 4) for r in reqs}
    paddle.set_flags({"FLAGS_kv_transfer_crc": True})
    try:
        sup = ServingSupervisor(factory, num_replicas=2,
                                roles=("prefill", "decode"))
        with fi.inject(fi.FaultPlan(corrupt_kv_wire=[1])):
            results = sup.run(reqs)
        sup.shutdown()
    finally:
        paddle.set_flags(dict(_SDC_FLAG_DEFAULTS))
    s = integrity.sdc_counters()
    refused = smetrics.serving_counters()["transfer_crc_refusals"] - before
    miss = [r.request_id for r in reqs if r.request_id not in results]
    wrong = [r.request_id for r in reqs if r.request_id in results
             and list(results[r.request_id].tokens) != gold[r.request_id]]
    integrity.reset_sdc_counters()
    return {"ok": (not miss and not wrong and s["crc_refusals"] == 1
                   and s["crc_checks"] >= 1 and refused == 1),
            "dropped": len(miss), "wrong": len(wrong),
            "crc_checks": s["crc_checks"], "crc_refusals": s["crc_refusals"]}


def leg_sdc_ckpt_scrub(seed):
    """Bit rot in a RETAINED snapshot: the cadence scrub re-verifies the
    CRC manifests newest-first, quarantines the rotten step to
    ``*.corrupt``, and the fallback chain stays clean."""
    from paddle_tpu.distributed import integrity
    from paddle_tpu.incubate.checkpoint import CheckpointManager

    integrity.reset_sdc_counters()
    d = tempfile.mkdtemp(prefix="sdc_scrub_")
    try:
        mgr = CheckpointManager(d, keep_last_n=4, async_save=False)
        state = {"w": np.arange(8, dtype=np.float32),
                 "b": np.full((3,), float(seed), np.float32)}
        for s in (1, 2, 3):
            mgr.save(s, state)
        with open(os.path.join(d, "step_2", "state.pdckpt"), "r+b") as f:
            f.seek(-8, 2)
            f.write(b"\x00" * 8)            # rot the middle snapshot
        out = mgr.scrub()
        counters = integrity.sdc_counters()
        ok = (out["rot"] == [2] and out["scrubbed"] == 3
              and counters["rot_found"] == 1
              and not os.path.isdir(os.path.join(d, "step_2"))
              and os.path.isdir(os.path.join(d, "step_2.corrupt"))
              and mgr.latest_step() == 3
              and mgr.restore() is not None)
        integrity.reset_sdc_counters()
        return {"ok": ok, **out}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def run_sdc_ladder(deterministic=False, seed=7):
    """The silent-data-corruption ladder. ``deterministic=True`` is the
    fast tier-1 sub-rung: the train detect-repair leg (tiny, no host-sync
    audit run) + the at-rest scrub leg only. The full ladder adds the
    quarantine policy, the serving shadow audit and the wire-CRC refusal.
    Returns a machine-readable dict; ``ok`` must be True."""
    import paddle_tpu as paddle

    paddle.set_flags(dict(_SDC_FLAG_DEFAULTS))
    if deterministic:
        tr = leg_sdc_train_repair(steps=3, seed=seed, deterministic=True)
        sc = leg_sdc_ckpt_scrub(seed=seed + 10)
        return {"ok": tr["ok"] and sc["ok"], "train_repair": tr,
                "ckpt_scrub": sc}
    tr = leg_sdc_train_repair(steps=6, seed=seed)
    print(f"FAULT_SMOKE sdc-train-bitflip-repair: "
          f"{'OK' if tr['ok'] else 'FAIL'}  "
          f"mismatches={tr['sdc']['fingerprint_mismatches']} "
          f"repairs={tr['sdc']['repairs']} "
          f"redispatches={tr['sdc']['repair_redispatches']} "
          f"bitwise-equal host-syncs-flat={tr['host_syncs_flat']}")
    qa = leg_sdc_train_quarantine(steps=6, seed=seed + 20)
    print(f"FAULT_SMOKE sdc-train-quarantine: "
          f"{'OK' if qa['ok'] else 'FAIL'}  "
          f"charged={qa['charged']} detected-as-lost={qa['detected']} "
          f"policy-gated={qa['policy_gated']}")
    au = leg_sdc_serve_audit(seed=seed + 40)
    print(f"FAULT_SMOKE sdc-serve-audit-catch: "
          f"{'OK' if au['ok'] else 'FAIL'}  "
          f"audits={au['audits']} failures={au['audit_failures']} "
          f"dropped={au['dropped']} wrong={au['wrong']} bitwise-equal")
    wc = leg_sdc_kv_wire_crc(seed=seed + 60)
    print(f"FAULT_SMOKE sdc-kv-wire-crc: "
          f"{'OK' if wc['ok'] else 'FAIL'}  "
          f"checked={wc['crc_checks']} refused={wc['crc_refusals']} "
          f"dropped={wc['dropped']} wrong={wc['wrong']} bitwise-equal")
    sc = leg_sdc_ckpt_scrub(seed=seed + 80)
    print(f"FAULT_SMOKE sdc-ckpt-scrub: "
          f"{'OK' if sc['ok'] else 'FAIL'}  "
          f"scrubbed={sc['scrubbed']} rot={sc['rot']}")
    out = {"ok": all(x["ok"] for x in (tr, qa, au, wc, sc)),
           "train_repair": tr, "quarantine": qa, "serve_audit": au,
           "kv_wire_crc": wc, "ckpt_scrub": sc}
    print(f"FAULT_SMOKE sdc-ladder: {'OK' if out['ok'] else 'FAIL'}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--kill-step", type=int, default=0,
                    help="fixed kill point (default: seeded random)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--skip-serving", action="store_true",
                    help="skip the serving chaos ladder")
    ap.add_argument("--skip-elastic", action="store_true",
                    help="skip the topology-elastic ladder")
    ap.add_argument("--skip-sdc", action="store_true",
                    help="skip the silent-data-corruption ladder")
    args = ap.parse_args()

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed import env as dist_env
    from paddle_tpu.utils import fault_injection as fi

    leg_kill_resume(paddle, nn, fi, args)
    leg_kill_resume(
        paddle, nn, fi, args,
        flags={"FLAGS_grad_comm": "on", "FLAGS_weight_update_sharding": True},
        mesh_fn=lambda: dist_env.create_hybrid_mesh(dp=8), k=2,
        name="kill-resume-wus")
    dist_env.set_mesh(None)
    leg_nan_skip(paddle, nn, fi, args)
    leg_nan_rollback(paddle, nn, fi, args)
    leg_io_chaos(paddle, fi, args)
    paddle.set_flags(dict(DEFAULT_FLAGS))
    if not args.skip_elastic:
        out = run_elastic_ladder(seed=args.seed)
        assert out["ok"], out
    if not args.skip_serving:
        out = run_serving_ladder(quick=False, seed=args.seed)
        assert out["requests_dropped"] == 0, out
        out = run_serving_elastic_ladder(seed=args.seed)
        assert out["ok"], out
    if not args.skip_sdc:
        out = run_sdc_ladder(seed=args.seed)
        assert out["ok"], out
    print("FAULT_SMOKE all: OK")


if __name__ == "__main__":
    main()
