#!/usr/bin/env python
"""SLO chaos ladder: multi-tenant traffic management smoke on CPU
(JAX_PLATFORMS=cpu), exercising priority classes, load shedding,
autoscaling and hot weight swaps end to end under deterministic chaos.

Rungs (each seeded; traffic comes from fault_injection.ArrivalSurge, so
two runs see IDENTICAL arrivals step for step):

  1. surge-shed-recover — mixed interactive/batch/best_effort traffic
       through a sustained arrival surge with shedding + priority
       admission on: EVERY interactive request completes (zero dropped,
       none shed), best_effort degrades VISIBLY (shed > 0, retry-after
       hints attached, shed queue-wait in the ledger) and RECOVERABLY
       (post-surge best_effort completes again).
  2. upgrade-under-load — rolling_restart(new_params=) mid-traffic on a
       2-replica fleet: zero requests dropped, every result is
       SINGLE-VERSION consistent (tokens bitwise equal the golden
       reference for the weight version stamped on the result), the
       fleet converges to the new version, zero retraces.
  3. kill-during-surge — one replica killed (FaultPlan, abrupt) while
       the surge is at peak, snapshot respawn + replay: zero interactive
       requests dropped, interactive results bitwise.

Quick mode (default; tier-1 runs it via tests/test_slo_serving.py) keeps
every gate STRUCTURAL — counts, versions, bitwise tokens — so it cannot
flake under CI load. Full mode (--full) additionally gates the
interactive-class p99 TTFT under chaos against a calm-baseline multiple
(the ROADMAP "p99 held through surge + upgrade + kill" gate) and prints
the latency table.

  python tools_slo_smoke.py [--full] [--seed S]

Prints, machine-greppable:

  SLO_SMOKE <rung>: <status>  <details>
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

_FIXTURE = None

# every SLO knob the ladder touches, pinned to a known state per rung
BASE_FLAGS = {
    "FLAGS_serving_priority_classes": False,
    "FLAGS_serving_shed": False,
    "FLAGS_serving_shed_high": 0.75,
    "FLAGS_serving_shed_low": 0.5,
    "FLAGS_serving_shed_window": 4,
    "FLAGS_serving_preempt_margin_s": 0.0,
    "FLAGS_serving_tenant_rate": 0.0,
    "FLAGS_serving_autoscale": False,
}


def _fixture():
    """Tiny GPT + helpers, built once (executables are memoized per
    config, so every rung reuses the same compiled fused step). Two
    weight versions: v0 serves, v1 is the hot-upgrade target."""
    global _FIXTURE
    if _FIXTURE is not None:
        return _FIXTURE
    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.models.generation import generate_from_params
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.models.gpt_hybrid import init_gpt_params

    cfg = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
                    max_seq_len=128, dropout=0.0, use_flash=False,
                    compute_dtype="float32", remat=False)
    p0 = init_gpt_params(cfg, jax.random.key(0))
    p1 = init_gpt_params(cfg, jax.random.key(1))

    def factory(**kw):
        kw.setdefault("num_slots", 3)
        kw.setdefault("max_seq_len", 96)
        kw.setdefault("page_size", 8)
        kw.setdefault("prefill_chunk", 8)
        return serving.Engine(params=p0, config=cfg, **kw)

    _ref_cache = {}

    def ref(params_id, prompt, n, **kw):
        key = (params_id, tuple(np.asarray(prompt).tolist()), n,
               tuple(sorted(kw.items())))
        if key not in _ref_cache:
            params = p0 if params_id == 0 else p1
            out = np.asarray(generate_from_params(
                params, np.asarray(prompt)[None], cfg, max_new_tokens=n,
                **kw)._data)
            _ref_cache[key] = out[0, len(prompt):].tolist()
        return _ref_cache[key]

    _FIXTURE = (paddle, serving, cfg, p0, p1, factory, ref)
    return _FIXTURE


class _Traffic:
    """Deterministic mixed-class request stream: tenants 'web'
    (interactive, generous deadline), 'analytics' (batch) and 'scavenger'
    (best_effort), greedy and sampled interleaved."""

    def __init__(self, serving, seed, interactive_deadline=30.0):
        self.serving = serving
        self.rng = np.random.default_rng(seed)
        self.n = 0
        self.deadline = interactive_deadline

    def next(self):
        i = self.n
        self.n += 1
        cls, tenant, dl = [
            ("interactive", "web", self.deadline),
            ("batch", "analytics", None),
            ("best_effort", "scavenger", None),
            ("best_effort", "scavenger", None),
        ][i % 4]
        kw = {}
        if i % 3 == 2:
            kw = {"do_sample": True, "temperature": 0.7 + 0.05 * (i % 5),
                  "top_p": 0.9, "seed": 100 + i}
        return self.serving.Request(
            self.rng.integers(0, 97, 4 + (i % 4) * 2),
            max_new_tokens=3 + (i % 3), priority=cls, tenant=tenant,
            deadline_s=dl, **kw)


def _golden_kw(r):
    return ({"do_sample": True, "temperature": r.temperature,
             "top_p": r.top_p, "seed": r.seed} if r.do_sample else {})


def _drive(sup, traffic, total_steps, on_step=None):
    """The surge driver: at every boundary poll the deterministic surge
    schedule, submit that many requests, run one supervision round.
    Returns (submitted, refused) — refused carries (request, error) for
    ShedError / QueueFullError refusals (the visible degradation)."""
    from paddle_tpu.serving import QueueFullError
    from paddle_tpu.utils import fault_injection as fi

    submitted, refused = [], []
    step = 0
    while step < total_steps or sup.pending():
        for _ in range(fi.surge_arrivals(step)):
            req = traffic.next()
            try:
                sup.submit(req)
                submitted.append(req)
            except QueueFullError as e:   # ShedError subclasses it
                refused.append((req, e))
        if on_step is not None:
            on_step(step)
        sup.step()
        step += 1
        if step > 100000:
            raise RuntimeError("ladder did not converge")
    return submitted, refused


def rung_surge_shed_recover(seed=7):
    """Sustained surge with shedding + priority admission: interactive
    holds, best_effort sheds visibly and recovers."""
    paddle, serving, cfg, p0, p1, factory, ref = _fixture()
    from paddle_tpu.serving import ServingSupervisor
    from paddle_tpu.serving import metrics as sm
    from paddle_tpu.utils import fault_injection as fi

    paddle.set_flags(dict(BASE_FLAGS))
    sm.reset_serving_counters()
    sup = ServingSupervisor(
        lambda: factory(priority=True, shed=True, max_queue=12),
        num_replicas=1)
    traffic = _Traffic(serving, seed)
    surge = fi.ArrivalSurge(base_rate=0.4, surge_rate=5.0, surge_start=4,
                            surge_steps=24, total_steps=120, seed=seed)
    paddle.set_flags({"FLAGS_serving_shed_window": 3})
    with fi.inject(fi.FaultPlan(surge=surge)):
        submitted, refused = _drive(sup, traffic, surge.total_steps)
    results = sup.pop_results()

    # recovery: the surge is over and the queue drained — fresh
    # best_effort traffic must be served again (the shed latch released)
    recov = [traffic.next() for _ in range(2)]
    for r in recov:
        r.priority, r.tenant = "best_effort", "scavenger"
    recov_results = sup.run(recov)
    paddle.set_flags(dict(BASE_FLAGS))
    recovered = all(
        recov_results.get(r.request_id) is not None
        and recov_results[r.request_id].finish_reason in ("stop", "length")
        for r in recov)

    inter = [r for r in submitted if r.priority == "interactive"]
    inter_done = [r for r in inter
                  if results.get(r.request_id) is not None
                  and results[r.request_id].finish_reason
                  in ("stop", "length")]
    shed_results = [r for r in results.values() if r.finish_reason == "shed"]
    refused_shed = [e for _, e in refused
                    if getattr(e, "retry_after", None) is not None]
    c = sm.serving_counters()
    ok = (len(inter_done) == len(inter) and len(inter) > 0
          and c["shed"] > 0
          and all(r.retry_after is not None and r.retry_after > 0
                  for r in shed_results)
          and all(r.priority != "interactive" for r in shed_results)
          and c["dropped"] == 0
          and all(e.retry_after > 0 for e in refused_shed)
          and recovered)
    return {"ok": ok, "interactive": f"{len(inter_done)}/{len(inter)}",
            "shed": c["shed"], "refused": len(refused),
            "shed_wait_ms": round(c["shed_queue_wait_mean"] * 1e3, 1),
            "recovered": recovered,
            "summary_visible": "slo:" in sm.serving_summary()}


def rung_upgrade_under_load(seed=11):
    """Hot weight swap mid-traffic: zero drops, single-version bitwise
    results, fleet converges to the new version, zero retraces."""
    paddle, serving, cfg, p0, p1, factory, ref = _fixture()
    from paddle_tpu.serving import ServingSupervisor
    from paddle_tpu.serving import metrics as sm
    from paddle_tpu.utils import fault_injection as fi

    paddle.set_flags(dict(BASE_FLAGS))
    sm.reset_serving_counters()
    sup = ServingSupervisor(lambda: factory(max_queue=64), num_replicas=2)
    traffic = _Traffic(serving, seed)
    surge = fi.ArrivalSurge(base_rate=1.0, surge_rate=1.0, surge_start=0,
                            surge_steps=40, total_steps=40, seed=seed)
    swapped = []

    def on_step(step):
        if step == 12:
            t0 = sm.serving_counters()["paged_traces"]
            sup.rolling_restart(absorb_steps=1, new_params=p1)
            swapped.append(sm.serving_counters()["paged_traces"] - t0)

    with fi.inject(fi.FaultPlan(surge=surge)):
        submitted, refused = _drive(sup, traffic, surge.total_steps,
                                    on_step=on_step)
    results = sup.pop_results()

    done = [r for r in submitted if results.get(r.request_id) is not None]
    missing = len(submitted) - len(done)
    wrong = []
    for r in done:
        res = results[r.request_id]
        if res.finish_reason not in ("stop", "length"):
            continue
        gold = ref(res.params_version, r.prompt, r.max_new_tokens,
                   **_golden_kw(r))
        if res.tokens != gold:
            wrong.append(r.request_id)
    versions = sorted({res.params_version for res in results.values()
                       if res.params_version is not None})
    tel = sup.telemetry()
    post_versions = {tel[f"replica{i.idx}"]["params_version"]
                     for i in sup._replicas if i.engine is not None}
    c = sm.serving_counters()
    ok = (missing == 0 and not wrong and c["dropped"] == 0
          and swapped == [0]                 # the swap added ZERO retraces
          and post_versions == {1}
          and c["weight_swaps"] >= 2 and c["rolling_restarts"] == 1)
    return {"ok": ok, "requests": len(submitted), "missing": missing,
            "wrong": wrong, "versions_served": versions,
            "fleet_version": sorted(post_versions),
            "swap_retraces": swapped, "weight_swaps": c["weight_swaps"]}


def rung_kill_during_surge(seed=13):
    """Abrupt replica kill at surge peak: snapshot respawn + replay keep
    zero interactive drops and interactive results bitwise."""
    paddle, serving, cfg, p0, p1, factory, ref = _fixture()
    from paddle_tpu.serving import ServingSupervisor
    from paddle_tpu.serving import metrics as sm
    from paddle_tpu.utils import fault_injection as fi

    paddle.set_flags(dict(BASE_FLAGS))
    paddle.set_flags({"FLAGS_serving_preempt_margin_s": 60.0})
    sm.reset_serving_counters()
    d = tempfile.mkdtemp(prefix="slo_chaos_")
    try:
        sup = ServingSupervisor(
            lambda: factory(priority=True, max_queue=64),
            num_replicas=2, snapshot_dir=d, snapshot_every=2)
        traffic = _Traffic(serving, seed)
        surge = fi.ArrivalSurge(base_rate=0.5, surge_rate=4.0,
                                surge_start=4, surge_steps=16,
                                total_steps=80, seed=seed)
        plan = fi.FaultPlan(surge=surge, kill_at_decode_step=8,
                            kill_engine_tag="replica1")
        with fi.inject(plan):
            submitted, refused = _drive(sup, traffic, surge.total_steps)
        results = sup.pop_results()
        c = sm.serving_counters()
        inter = [r for r in submitted if r.priority == "interactive"]
        inter_wrong, inter_missing = [], []
        for r in inter:
            res = results.get(r.request_id)
            if res is None or res.finish_reason not in ("stop", "length"):
                inter_missing.append(r.request_id)
                continue
            gold = ref(res.params_version, r.prompt, r.max_new_tokens,
                       **_golden_kw(r))
            if res.tokens != gold:
                inter_wrong.append(r.request_id)
        ok = (plan.stats["serving_kills"] == 1
              and not inter_missing and not inter_wrong and len(inter) > 0
              and c["dropped"] == 0 and c["respawns"] >= 1)
        return {"ok": ok, "interactive": len(inter),
                "missing": inter_missing, "wrong": inter_wrong,
                "respawns": c["respawns"], "replayed": c["replayed"],
                "preempted": c["preempted"],
                "kills": plan.stats["serving_kills"]}
    finally:
        paddle.set_flags(dict(BASE_FLAGS))
        shutil.rmtree(d, ignore_errors=True)


def _interactive_p99(results, submitted):
    ttfts = [results[r.request_id].ttft for r in submitted
             if r.priority == "interactive"
             and results.get(r.request_id) is not None
             and results[r.request_id].ttft is not None]
    return float(np.percentile(ttfts, 99)) if ttfts else None


def rung_p99_held(seed=17):
    """Full-mode gate: interactive p99 TTFT through surge + upgrade +
    kill stays within a generous multiple of the calm baseline (absolute
    CPU numbers vary with CI load; the RATIO is the story)."""
    paddle, serving, cfg, p0, p1, factory, ref = _fixture()
    from paddle_tpu.serving import ServingSupervisor
    from paddle_tpu.serving import metrics as sm
    from paddle_tpu.utils import fault_injection as fi

    paddle.set_flags(dict(BASE_FLAGS))
    paddle.set_flags({"FLAGS_serving_preempt_margin_s": 60.0})

    def run(chaos):
        sm.reset_serving_counters()
        d = tempfile.mkdtemp(prefix="slo_p99_")
        try:
            sup = ServingSupervisor(
                lambda: factory(priority=True, shed=True, max_queue=14),
                num_replicas=2, snapshot_dir=d, snapshot_every=2)
            traffic = _Traffic(serving, seed)
            surge = fi.ArrivalSurge(
                base_rate=0.5, surge_rate=4.0 if chaos else 0.5,
                surge_start=6, surge_steps=20, total_steps=140, seed=seed)
            plan = fi.FaultPlan(
                surge=surge,
                kill_at_decode_step=10 if chaos else None,
                kill_engine_tag="replica1" if chaos else None)

            def on_step(step):
                if chaos and step == 9:
                    sup.rolling_restart(absorb_steps=1, new_params=p1)

            with fi.inject(plan):
                submitted, _ = _drive(sup, traffic, surge.total_steps,
                                      on_step=on_step)
            results = sup.pop_results()
            inter = [r for r in submitted if r.priority == "interactive"]
            missing = [r.request_id for r in inter
                       if results.get(r.request_id) is None
                       or results[r.request_id].finish_reason
                       not in ("stop", "length")]
            return _interactive_p99(results, submitted), missing, \
                sm.serving_counters()
        finally:
            shutil.rmtree(d, ignore_errors=True)

    calm_p99, calm_missing, _ = run(chaos=False)
    chaos_p99, chaos_missing, c = run(chaos=True)
    paddle.set_flags(dict(BASE_FLAGS))
    ok = (not calm_missing and not chaos_missing
          and calm_p99 is not None and chaos_p99 is not None
          and chaos_p99 <= max(10.0 * calm_p99, 2.0)
          and c["dropped"] == 0 and c["shed"] > 0)
    return {"ok": ok, "calm_p99_ms": round(calm_p99 * 1e3, 1),
            "chaos_p99_ms": round(chaos_p99 * 1e3, 1),
            "interactive_missing": chaos_missing,
            "shed": c["shed"], "respawns": c["respawns"]}


def run_ladder(full=False, seed=7):
    out = {}
    out["surge_shed_recover"] = rung_surge_shed_recover(seed)
    out["upgrade_under_load"] = rung_upgrade_under_load(seed + 4)
    out["kill_during_surge"] = rung_kill_during_surge(seed + 6)
    if full:
        out["p99_held"] = rung_p99_held(seed + 10)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="also run the (timing-sensitive) p99 gate rung")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    out = run_ladder(full=args.full, seed=args.seed)
    failed = 0
    for rung, info in out.items():
        status = "OK" if info.pop("ok") else "FAIL"
        failed += status == "FAIL"
        detail = "  ".join(f"{k}={v}" for k, v in info.items())
        print(f"SLO_SMOKE {rung}: {status}  {detail}")
    from paddle_tpu.serving import metrics as sm
    print("SLO_SMOKE summary:", sm.serving_summary())
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
