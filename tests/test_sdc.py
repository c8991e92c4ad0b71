"""Silent-data-corruption sentinel (distributed/integrity.py): the
FLAGS_sdc_check_every fused cross-replica fingerprint + majority-vote
localization + in-place peer repair on the 8-virtual-device CPU mesh;
the serving shadow audit that catches FINITE KV corruption the all-finite
guard is blind to; the kv_transfer CRC32 wire contract; and the
checkpoint at-rest scrub. Every fault is a deterministic FaultPlan
schedule — no randomness, no wall-clock."""
import contextlib
import os

import numpy as np
import jax
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed import env as dist_env
from paddle_tpu.distributed import integrity
from paddle_tpu.jit.train_step import anomaly_counters, \
    reset_anomaly_counters
from paddle_tpu.utils import fault_injection as fi


_DEFAULT_FLAGS = {
    "FLAGS_grad_comm": "auto",
    "FLAGS_weight_update_sharding": False,
    "FLAGS_anomaly_policy": "off",
    "FLAGS_sdc_check_every": 0,
    "FLAGS_sdc_quarantine_threshold": 2,
    "FLAGS_serving_audit_rate": 0.0,
    "FLAGS_serving_audit_threshold": 2,
    "FLAGS_kv_transfer_crc": False,
    "FLAGS_ckpt_scrub_every": 0,
}

AR = {"FLAGS_grad_comm": "on", "FLAGS_weight_update_sharding": False}
RS = {"FLAGS_grad_comm": "on", "FLAGS_weight_update_sharding": True}


@pytest.fixture(autouse=True)
def _reset(devices8):
    integrity.reset_sdc_counters()
    reset_anomaly_counters()
    yield
    paddle.set_flags(dict(_DEFAULT_FLAGS))
    dist_env.set_mesh(None)
    integrity.reset_sdc_counters()
    reset_anomaly_counters()


def _build(flags, seed=7):
    """Fresh dp=8 TrainStep for the given flags, plus its pristine
    state_dict (reloading the snapshot replays the trajectory from init
    bitwise when a test wants several runs out of one executable)."""
    paddle.set_flags(dict(_DEFAULT_FLAGS))
    paddle.set_flags(flags)
    dist_env.set_mesh(None)
    mesh = dist_env.create_hybrid_mesh(dp=8)
    paddle.seed(seed)
    m = nn.Sequential(nn.Linear(64, 64), nn.ReLU(), nn.Linear(64, 8))
    opt = paddle.optimizer.AdamW(0.01, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, nn.MSELoss(), opt, mesh=mesh)
    return step, step.state_dict()


def _run(step, plan=None, steps=3, seed=7):
    rng = np.random.default_rng(seed + 1)
    x = rng.standard_normal((16, 64)).astype(np.float32)
    y = rng.standard_normal((16, 8)).astype(np.float32)
    ctx = fi.inject(plan) if plan is not None else contextlib.nullcontext()
    with ctx:
        losses = [float(step(paddle.to_tensor(x),
                             paddle.to_tensor(y)).numpy())
                  for _ in range(steps)]
    return {n: np.asarray(a) for n, a in step.params.items()}, losses


def _train(flags, plan=None, steps=3, seed=7):
    step, _ = _build(flags, seed=seed)
    params, losses = _run(step, plan=plan, steps=steps, seed=seed)
    return params, losses, step


_BASELINE_CACHE = {}


def _baseline(cfg, steps=3, seed=7):
    # Fault-free sdc-off reference trajectory, one compile per config for
    # the whole module (three tests compare against it; the run touches no
    # sdc counters, so the per-test counter asserts stay valid).
    key = (tuple(sorted(cfg.items())), steps, seed)
    if key not in _BASELINE_CACHE:
        _BASELINE_CACHE[key] = _train(cfg, steps=steps, seed=seed)
    return _BASELINE_CACHE[key]


# ---------------------------------------------------------------------------
# integrity primitives (no mesh, no compile)


def test_fingerprint_single_bit_sensitivity():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((5, 7)).astype(np.float32)
    b = rng.standard_normal(11).astype(np.float32)
    fp0 = int(jax.device_get(integrity.fingerprint_arrays({"a": a, "b": b})))
    a2 = a.copy()
    a2.view(np.uint8).reshape(-1)[13] ^= 0x10     # one mantissa bit
    fp1 = int(jax.device_get(integrity.fingerprint_arrays({"a": a2, "b": b})))
    assert fp0 != fp1
    # hash-combine is leaf-ORDER sensitive: swapped leaves don't cancel
    fp2 = int(jax.device_get(integrity.fingerprint_arrays({"a": b, "b": a})))
    assert fp0 != fp2


def test_localize_minority_vote_shapes():
    assert integrity.localize_minority(np.array([7, 7, 7, 7])) == ()
    assert integrity.localize_minority(np.array([7, 9, 7, 7])) == (1,)
    assert integrity.localize_minority(np.array([7, 9, 9, 7, 7])) == (1, 2)
    # an even split has no majority: the caller must fall back to the
    # anomaly policy, not guess a donor
    assert integrity.localize_minority(np.array([7, 9])) is None


def test_quarantine_ledger_and_elastic_detect():
    from paddle_tpu.distributed.elastic import ElasticMeshSupervisor

    paddle.set_flags({"FLAGS_sdc_quarantine_threshold": 2})
    integrity.note_repair(2)
    assert integrity.quarantined_ranks() == frozenset()
    integrity.note_repair(2)
    assert integrity.quarantined_ranks() == frozenset({2})
    # the detector treats a quarantined chip as LOST only under the
    # opt-in policy — default supervisors never see it
    on = ElasticMeshSupervisor(lambda *a, **kw: None, None, 8,
                               quarantine=True)
    off = ElasticMeshSupervisor(lambda *a, **kw: None, None, 8)
    assert 2 in on._detect(0)
    assert 2 not in off._detect(0)


def test_payload_crc_stamp_verify_refuse():
    from paddle_tpu.serving.kv_transfer import (KVIntegrityError,
                                                PagePayload)

    k = np.arange(32, dtype=np.float32).reshape(2, 4, 4)
    payload = PagePayload(0, k, k + 1.0)
    assert payload.crc is None          # flags-off: never stamped
    payload.stamp()
    assert payload.crc is not None
    payload.verify()                    # clean bytes pass
    payload.k.view(np.uint8).reshape(-1)[3] ^= 0x01
    with pytest.raises(KVIntegrityError):
        payload.verify()


# ---------------------------------------------------------------------------
# training: fused fingerprint -> localize -> peer repair, bitwise


def test_sdc_flags_off_is_inert():
    _, _, step = _baseline(AR)
    assert step._sdc_jitted is None
    assert not any(integrity.sdc_counters().values())


def test_sdc_clean_run_bitwise_and_counters():
    """Flags-off and sdc-on are DIFFERENT executables with the same
    math: the clean sdc trajectory must be bitwise the flags-off one."""
    p0, l0, _ = _baseline(AR)
    p1, l1, _ = _train(dict(AR, FLAGS_sdc_check_every=1), steps=3)
    assert l0 == l1
    for n in p0:
        np.testing.assert_array_equal(p0[n], p1[n])
    s = integrity.sdc_counters()
    assert s["fingerprint_checks"] == 3
    assert s["fingerprint_mismatches"] == 0 and s["repairs"] == 0


def test_sdc_bitflip_detected_repaired_bitwise():
    """The chaos gate: a mantissa flip on rank 3's replicated params is
    detected at the next check boundary, localized by majority vote,
    repaired in place from a healthy peer, and the step re-dispatched —
    the final trajectory is BITWISE the fault-free one, zero restores."""
    p0, l0, _ = _baseline(AR)
    plan = fi.FaultPlan(bitflip_at={1: (3, None, 12)})
    p1, l1, _ = _train(dict(AR, FLAGS_sdc_check_every=1), plan=plan,
                       steps=3)
    s = integrity.sdc_counters()
    assert s["fingerprint_mismatches"] == 1
    assert s["repairs"] == 1 and s["repair_redispatches"] == 1
    assert s.get("repairs_rank3") == 1      # charged to the right chip
    assert fi.stats()["bitflips"] == 1
    assert l1 == l0
    for n in p0:
        np.testing.assert_array_equal(p1[n], p0[n]), n


def test_sdc_verdict_rides_the_guard_fetch():
    """With the anomaly guard on, the sdc verdict must NOT add a second
    host sync: one combined fetch per update step, audited."""
    _train(dict(AR, FLAGS_sdc_check_every=1,
                FLAGS_anomaly_policy="skip"), steps=3)
    c = anomaly_counters()
    assert c["steps"] == 3 and c["host_syncs"] == 3


def test_sdc_wus_repair_bitwise():
    """Weight-update sharding: only params are fingerprinted (packed
    slots legitimately differ per replica); a flip caught at the check
    boundary still repairs to a bitwise-identical trajectory."""
    p0, l0, _ = _train(RS, steps=3)
    plan = fi.FaultPlan(bitflip_at={1: (5, None, 12)})
    p1, l1, _ = _train(dict(RS, FLAGS_sdc_check_every=1), plan=plan,
                       steps=3)
    s = integrity.sdc_counters()
    assert s["fingerprint_mismatches"] == 1 and s["repairs"] == 1
    assert l1 == l0
    for n in p0:
        np.testing.assert_array_equal(p1[n], p0[n]), n


# ---------------------------------------------------------------------------
# serving: shadow audit + wire CRC (tiny GPT, shared per module)

from paddle_tpu import serving  # noqa: E402
from paddle_tpu.models.generation import generate_from_params  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig  # noqa: E402
from paddle_tpu.models.gpt_hybrid import init_gpt_params  # noqa: E402
from paddle_tpu.serving import metrics as smetrics  # noqa: E402
from paddle_tpu.serving.supervisor import ServingSupervisor  # noqa: E402

CFG = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
                max_seq_len=128, dropout=0.0, use_flash=False,
                compute_dtype="float32", remat=False)
_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = init_gpt_params(CFG, jax.random.key(0))
    return _PARAMS


def _engine():
    return serving.Engine(params=_params(), config=CFG, num_slots=3,
                          max_seq_len=96, page_size=8, prefill_chunk=8)


def _ref(prompt, n):
    out = np.asarray(generate_from_params(
        _params(), np.asarray(prompt)[None], CFG, max_new_tokens=n)._data)
    return out[0, len(prompt):].tolist()


def test_serving_audit_catches_finite_kv_bitflip():
    """The satellite gate: an exponent-bit KV flip is HUGE but finite —
    the all-finite anomaly guard cannot see it (no finish_reason=error),
    only the sampled shadow audit catches the token divergence; the
    replica fails over through the ordinary reform path with zero drops
    and every delivered stream bitwise equal the healthy oracle."""
    # seed matched to tools_fault_smoke's audit leg: page 1 of replica0's
    # pool is live with an audited stream's keys at flip step 2
    rng = np.random.default_rng(47)
    reqs = [serving.Request(rng.integers(0, 97, 6 + (i % 3)),
                            max_new_tokens=8) for i in range(4)]
    gold = {r.request_id: _ref(r.prompt, 8) for r in reqs}
    paddle.set_flags({"FLAGS_serving_audit_rate": 1.0,
                      "FLAGS_serving_audit_threshold": 1})
    sup = ServingSupervisor(_engine, num_replicas=2,
                            audit_ref=(_params(), CFG))
    # top-exponent-bit flips on dim 0 of every position's key in one live
    # page: huge but FINITE values that saturate the softmax (2048 bits
    # span one position in the [page_size, nh, d] page layout)
    flips = [(1, 0, 2048 * p + 30) for p in range(8)]
    with fi.inject(fi.FaultPlan(kv_bitflip_at={2: flips},
                                kv_bitflip_engine_tag="replica0")):
        results = sup.run(reqs)
    sup.shutdown()
    assert fi.stats()["kv_bitflips"] == 8
    s = integrity.sdc_counters()
    assert s["audits"] >= 1 and s["audit_failures"] >= 1
    for r in reqs:
        res = results[r.request_id]
        # the guard NEVER fired — the corruption was finite end to end
        assert res.finish_reason in ("stop", "length")
        assert list(res.tokens) == gold[r.request_id], r.request_id


def test_kv_wire_crc_refuses_and_reoffers_bitwise():
    """A page payload corrupted between the prefill and decode workers is
    refused by its CRC32 stamp (typed + counted), the transfer is
    dropped, the supervisor re-offers the RETAINED clean payloads, and
    the stream seats bitwise — zero drops."""
    before = smetrics.serving_counters()["transfer_crc_refusals"]
    rng = np.random.default_rng(31)
    reqs = [serving.Request(rng.integers(0, 97, 13 + 4 * i),
                            max_new_tokens=4) for i in range(3)]
    gold = {r.request_id: _ref(r.prompt, 4) for r in reqs}
    paddle.set_flags({"FLAGS_kv_transfer_crc": True})
    sup = ServingSupervisor(_engine, num_replicas=2,
                            roles=("prefill", "decode"))
    with fi.inject(fi.FaultPlan(corrupt_kv_wire=[1])):
        results = sup.run(reqs)
    sup.shutdown()
    s = integrity.sdc_counters()
    assert s["crc_checks"] >= 1 and s["crc_refusals"] == 1
    assert smetrics.serving_counters()["transfer_crc_refusals"] - before == 1
    for r in reqs:
        assert list(results[r.request_id].tokens) == gold[r.request_id]


# ---------------------------------------------------------------------------
# at-rest: checkpoint scrub


def test_ckpt_scrub_quarantines_rot(tmp_path):
    from paddle_tpu.incubate.checkpoint import CheckpointManager

    mgr = CheckpointManager(tmp_path, keep_last_n=4, async_save=False)
    state = {"w": np.arange(8, dtype=np.float32)}
    for s in (1, 2, 3):
        mgr.save(s, state)
    with open(os.path.join(tmp_path, "step_2", "state.pdckpt"),
              "r+b") as f:
        f.seek(-8, 2)
        f.write(b"\x00" * 8)
    out = mgr.scrub()
    assert out == {"scrubbed": 3, "rot": [2]}
    assert not os.path.isdir(os.path.join(tmp_path, "step_2"))
    assert os.path.isdir(os.path.join(tmp_path, "step_2.corrupt"))
    s = integrity.sdc_counters()
    assert s["scrubs"] == 1 and s["rot_found"] == 1
    assert mgr.latest_step() == 3 and mgr.restore() is not None
    # a second scrub over the pre-cleaned chain finds nothing
    assert mgr.scrub()["rot"] == []


def test_ckpt_scrub_cadence_from_prune(tmp_path):
    """FLAGS_ckpt_scrub_every: every Nth save opportunistically re-reads
    the retained chain — rot is quarantined WITHOUT anyone calling
    scrub() and without a restore ever tripping over it."""
    from paddle_tpu.incubate.checkpoint import CheckpointManager

    paddle.set_flags({"FLAGS_ckpt_scrub_every": 2})
    mgr = CheckpointManager(tmp_path, keep_last_n=4, async_save=False)
    state = {"w": np.zeros(4, np.float32)}
    mgr.save(1, state)
    with open(os.path.join(tmp_path, "step_1", "state.pdckpt"),
              "r+b") as f:
        f.seek(-4, 2)
        f.write(b"\xff" * 4)
    mgr.save(2, state)                  # cadence hits: scrub fires here
    assert os.path.isdir(os.path.join(tmp_path, "step_1.corrupt"))
    assert integrity.sdc_counters()["rot_found"] == 1
    assert mgr.latest_step() == 2


def test_scrub_flags_off_no_cadence(tmp_path):
    from paddle_tpu.incubate.checkpoint import CheckpointManager

    mgr = CheckpointManager(tmp_path, keep_last_n=4, async_save=False)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"w": np.zeros(2, np.float32)})
    assert integrity.sdc_counters()["scrubs"] == 0


# ---------------------------------------------------------------------------
# smoke-tool ladder


def _smoke():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tools_fault_smoke",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools_fault_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_sdc_ladder_deterministic_rung():
    """tools_fault_smoke's sdc ladder, deterministic sub-rung: train
    detect-localize-repair (bitwise vs golden) + the at-rest scrub leg."""
    out = _smoke().run_sdc_ladder(deterministic=True)
    assert out["ok"], out
    assert out["train_repair"]["bitwise"]
    assert out["ckpt_scrub"]["rot"] == [2]
