"""The phase clock of ``Engine.step`` (serving/metrics.PhaseClock) and the
names of the flash kernels.

Gates:
  * ``step_s`` and the four phase counters are in ``serving_counters()`` and
    in the Prometheus page; the phases are disjoint and, with a small
    remainder, sum to the step; ``decode_time_s + prefill_time_s`` is the
    feed + wait of the dispatches; ``launch_s``, the jitted calls alone, is
    a part of ``feed_s`` (a speculative engine's draft and verify too) and
    counts nothing outside a step (``warm_up``);
  * ``prefill_time_s`` and the ``prefill_chunk`` span end after the fetch of
    the chunk step's outputs (they timed the enqueue before);
  * ``admit_queue_waits`` follows ``admitted`` and ``first_tokens`` the fresh
    first tokens, through a drain and requeue too, flag or no flag;
  * the clock is host-side only: served tokens are ``generate_from_params``'s
    and no executable is traced for it;
  * a tracing engine exports one ``boundaries`` track whose phase spans do
    not overlap;
  * the training step lowered for the TPU carries the three kernel names.
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs, profiler, serving
from paddle_tpu.models.generation import generate_from_params
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.gpt_hybrid import HybridTrainStep, init_gpt_params
from paddle_tpu.observability import prometheus, tracing

CFG = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
                max_seq_len=128, dropout=0.0, use_flash=False,
                compute_dtype="float32", remat=False)
PHASES = ("admit_s", "feed_s", "wait_s", "emit_s")
_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = init_gpt_params(CFG, jax.random.key(0))
    return _PARAMS


def _engine(**kw):
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("num_slots", 9)   # a batch shape no trace gate owns
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 16)
    return serving.Engine(params=_params(), config=CFG, **kw)


def _requests(n=5, seed=3):
    rng = np.random.default_rng(seed)
    return [serving.Request(rng.integers(0, 97, int(rng.integers(5, 30))),
                            max_new_tokens=int(rng.integers(3, 8)))
            for _ in range(n)]


@pytest.fixture(autouse=True)
def _clean():
    profiler.reset_serving_counters()
    tracing.clear()
    yield
    tracing.clear()


def _warm(**kw):
    """Compile the file's executables, so that a timed run holds no
    compilation (a compile lands in one feed phase and proves nothing)."""
    _engine(**kw).run(_requests())
    profiler.reset_serving_counters()


@pytest.mark.parametrize("prefill_chunk", [16, 32])
def test_phases_are_disjoint_and_sum_to_the_step(prefill_chunk):
    # pages of 16: a chunk ladder of one rung, and of two
    kw = {"page_size": 16, "prefill_chunk": prefill_chunk}
    _warm(**kw)
    eng = _engine(**kw)
    eng.run(_requests())
    c = profiler.serving_counters()
    for k in PHASES + ("step_s",):
        assert isinstance(c[k], float) and c[k] > 0, k
    phases = sum(c[k] for k in PHASES)
    assert phases <= c["step_s"] * (1 + 1e-9)
    assert phases >= 0.9 * c["step_s"]
    # the launch is no phase of its own: it lies inside the feed
    assert 0 < c["launch_s"] <= c["feed_s"]
    # feed + wait of every dispatch go to exactly one of the two
    # executable-time counters
    assert c["decode_time_s"] + c["prefill_time_s"] == pytest.approx(
        c["feed_s"] + c["wait_s"], rel=1e-9)
    assert c["decode_time_s"] > 0 and c["prefill_time_s"] > 0
    assert c["tokens_per_s"] == pytest.approx(
        c["tokens_out"] / (c["feed_s"] + c["wait_s"]))


def test_phase_counters_reach_the_prometheus_page():
    _engine().run(_requests(2))
    page = prometheus.parse(prometheus.render(obs.snapshot()))
    c = profiler.serving_counters()
    for k in PHASES + ("step_s", "launch_s", "admit_queue_wait_s",
                       "admit_queue_waits", "prefill_span_s", "first_tokens"):
        assert page[f"paddle_tpu_serving_{k}"] == pytest.approx(c[k])
    assert "token_latency_p50" not in c


def test_launch_is_part_of_the_feed_in_a_speculative_engine_too():
    kw = {"num_slots": 6, "speculate_k": 2}
    _warm(**kw)
    _engine(**kw).run(_requests())
    c = profiler.serving_counters()
    assert c["draft_dispatches"] > 0 and c["verify_dispatches"] > 0
    assert 0 < c["launch_s"] <= c["feed_s"]
    assert sum(c[k] for k in PHASES) <= c["step_s"] * (1 + 1e-9)


def test_warm_up_counts_no_launch():
    """``warm_up`` dispatches outside any step: its compiles must not land
    in ``launch_s`` at the next boundary's flush."""
    eng = _engine(num_slots=4).warm_up()
    assert eng._clock.sums == {}
    eng.run(_requests(2))
    c = profiler.serving_counters()
    assert 0 < c["launch_s"] <= c["feed_s"]


class _SlowFetch:
    """A device array whose fetch to the host blocks for a set time, as a
    chunk step's outputs do on a device that is still running."""

    def __init__(self, arr, seconds):
        self.arr, self.seconds = arr, seconds

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.seconds)
        return np.asarray(self.arr, dtype)


def test_prefill_time_ends_after_the_fetch():
    """The dispatch returns its futures at once and the host waits at the
    one fetch of its outputs: ``prefill_time_s``, ``wait_s`` and the
    ``prefill_chunk`` span hold that wait. (``prefill_time_s`` read the
    enqueue alone before: near 0 here.)"""
    _warm()
    delay = 0.05
    eng = _engine(trace=True)
    real = eng._paged_step

    def stand_in(*args, layout):
        out = list(real(*args, layout=layout))
        if layout.B == 1:                   # ids [1, C]: a chunk step
            jax.block_until_ready(out)
            out[-1] = _SlowFetch(out[-1], delay)    # its one small output
        return tuple(out)

    eng._paged_step = stand_in
    req = serving.Request(np.arange(1, 41), max_new_tokens=2)   # 3 chunks
    eng.run([req])
    c = profiler.serving_counters()
    assert c["chunk_steps"] == 3
    assert c["prefill_time_s"] >= 3 * delay
    assert c["wait_s"] >= 3 * delay
    assert c["prefill_time_s"] / c["chunk_steps"] < delay + 0.05
    spans = [s for s in tracing.traces()[-1]["spans"]
             if s["name"] == "prefill_chunk"]
    assert len(spans) == 3
    assert all(s["t1"] - s["t0"] >= delay for s in spans)


@pytest.mark.parametrize("trace", [False, True])
def test_request_counters_follow_admissions_and_first_tokens(trace):
    eng = _engine(num_slots=2, trace=trace)
    reqs = _requests(4)
    eng.run(reqs)
    c = profiler.serving_counters()
    assert c["admit_queue_waits"] == c["admitted"] == 4
    assert c["first_tokens"] == 4
    assert c["admit_queue_wait_s"] > 0     # two waited for a slot
    assert c["prefill_span_s"] > 0
    # the engine's own split of the time to first token adds up: queue wait
    # and prefill span of a request meet at its admission instant
    assert c["admit_queue_wait_s"] + c["prefill_span_s"] == pytest.approx(
        sum(r.first_token_t - r.submit_t for r in reqs), rel=1e-6)


def test_request_counters_through_a_requeue():
    """A drained request is admitted, and counted, again; its first token
    counts once (the rule ``observe_ttft`` follows)."""
    eng = _engine(num_slots=2)
    reqs = [serving.Request(np.arange(1, 10), max_new_tokens=6, seed=i)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    assert all(r.first_token_t is not None for r in reqs)
    drained = eng.drain()
    assert len(drained) == 2
    eng2 = _engine(num_slots=2)
    for r in drained:
        eng2.requeue(r)
    eng2.run()
    c = profiler.serving_counters()
    assert c["requeued"] == 2
    assert c["admitted"] == 4 and c["admit_queue_waits"] == 4
    assert c["first_tokens"] == 2
    assert all(len(r.tokens) == 6 for r in reqs)


def test_clock_changes_no_token_and_traces_no_executable():
    reqs = _requests(4, seed=11)
    _engine().run([serving.Request(r.prompt, max_new_tokens=r.max_new_tokens)
                   for r in reqs])
    warm = profiler.serving_counters()["paged_traces"]
    res = _engine(trace=True).run(reqs)
    assert profiler.serving_counters()["paged_traces"] == warm
    for r in reqs:
        want = generate_from_params(_params(), r.prompt[None], CFG,
                                    max_new_tokens=r.max_new_tokens)
        assert res[r.request_id].tokens == \
            np.asarray(want)[0, r.prompt_len:].tolist()


def test_exported_trace_has_the_boundaries_track(tmp_path):
    eng = _engine(trace=True, tag="phases")
    eng.run(_requests(3))
    path = eng.export_trace(str(tmp_path / "trace.json"))
    evs = json.load(open(path))["traceEvents"]
    names = [e for e in evs if e["ph"] == "M" and e["name"] == "thread_name"
             and e["args"]["name"] == "boundaries"]
    assert len(names) == 1
    pid, tid = names[0]["pid"], names[0]["tid"]
    assert {"name": "process_name", "ph": "M", "pid": pid,
            "args": {"name": "serving:phases"}} in evs
    track = [e for e in evs if e["ph"] == "X" and e["pid"] == pid
             and e["tid"] == tid]
    steps = [e for e in track if e["name"] == "pt.serve.step"]
    phases = sorted((e for e in track if e["name"] != "pt.serve.step"),
                    key=lambda e: e["ts"])
    assert len(steps) == profiler.serving_counters()["boundaries"]
    assert {e["name"] for e in phases} == {
        "pt.serve.admit", "pt.serve.feed", "pt.serve.wait", "pt.serve.emit"}
    for a, b in zip(phases, phases[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-3      # microseconds
    kinds = {e["args"].get("kind") for e in phases
             if e["name"] in ("pt.serve.feed", "pt.serve.wait")}
    assert kinds == {"chunk", "decode"}
    exes = {e["args"].get("exe") for e in phases
            if e["name"] in ("pt.serve.feed", "pt.serve.wait")}
    assert "pt_paged_b9_t1" in exes
    assert exes <= {"pt_paged_b9_t1", "pt_paged_b1_t8", "pt_paged_b1_t16"}
    # every phase lies inside a step, and the same floats reach the ledger
    for e in phases:
        assert any(s["ts"] - 1e-3 <= e["ts"] and
                   e["ts"] + e["dur"] <= s["ts"] + s["dur"] + 1e-3
                   for s in steps)
    assert sum(s["dur"] for s in steps) / 1e6 == pytest.approx(
        profiler.serving_counters()["step_s"], rel=1e-6)
    # an engine that does not trace keeps no boundary
    tracing.clear()
    _engine().run(_requests(1))
    assert tracing.boundaries() == []


def test_lowered_training_step_names_the_flash_kernels(monkeypatch):
    """The three ``pallas_call``s sit in named scopes, which is where the
    chip's compiler takes the device operations' names from
    (``%flash_fwd.N``, ``%flash_bwd_dq.N``, ``%flash_bwd_dkv.N``), whatever
    wraps the call: custom_vjp, remat, the layer scan."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=1, max_seq_len=128, dropout=0.0,
                    use_flash=True, remat=True, compute_dtype="bfloat16")
    step = HybridTrainStep(cfg, paddle.optimizer.AdamW(2e-4))
    ids = jnp.zeros((2, 128), jnp.int32)
    text = jax.export.export(step._build(), platforms=["tpu"])(
        step._flat(step.params), step.opt_state, ids,
        jnp.asarray(2e-4, jnp.float32)).mlir_module()
    assert text.count("tpu_custom_call") >= 4   # fwd, its remat, dq, dkv
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert f"{name}/pallas_call" in text, name
