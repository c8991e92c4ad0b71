"""What runs on the device carries the program's names, and the reader that
turns a profile into seconds by them.

Gates:
  * the lowered module of each dispatch shape of an engine is named
    ``jit_pt_paged_b<B>_t<T>`` (a speculative engine's two
    ``pt_draft_*`` / ``pt_verify_*``, the page copy ``pt_page_copy``), one
    trace a shape as before;
  * in a profiler session the feed, launch and wait spans of one dispatch
    carry the same ``exe=`` and ``kind=``, the launch inside the feed;
  * the lowered GPT paged step's text holds every ``pt_*`` scope of its
    stages, and each served model's step ``pt_head`` and ``pt_tail``;
  * ``profiler.device_time``'s reduction on a hand-made list of events: two
    executables, three scopes, an operation under no scope, a ``%while``
    that spans its body counted once, the idle split three ways.
"""
import glob

import jax
import numpy as np
import pytest

from paddle_tpu import profiler, serving
from paddle_tpu.models import afmoe as A, lfm2 as L, xing4 as X
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.gpt_hybrid import init_gpt_params
from paddle_tpu.profiler import xplane as DT

SLOTS, PAGE, CHUNK = 3, 8, 16      # a batch shape no trace gate owns
GPT_SCOPES = ("pt_embed", "pt_layers", "pt_attn_qkv", "pt_kv_write", "pt_attn_read",
              "pt_attn_out", "pt_ffn", "pt_head", "pt_tail")


def _toy(model):
    if model == "gpt":
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_seq_len=64)
        return cfg, init_gpt_params(cfg, jax.random.key(0))
    if model == "xing4":
        cfg = X.Xing4Config(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_hidden_layers=3,
            first_k_dense_replace=1, n_routed_experts=4,
            num_experts_per_tok=2, num_attention_heads=2, q_lora_rank=8,
            kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, hc_mult=2)
        return cfg, X.init_xing4_params(cfg, jax.random.key(1))
    if model == "afmoe":
        cfg = A.AfmoeConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_hidden_layers=5,
            num_dense_layers=1, num_experts=4, num_experts_per_tok=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            sliding_window=16)
        return cfg, A.init_afmoe_params(cfg, jax.random.key(2))
    cfg = L.Lfm2Config(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=3, num_dense_layers=1,
        num_experts=4, num_experts_per_tok=2, num_attention_heads=4,
        num_key_value_heads=2, layer_types=(L.CONV, L.FULL, L.CONV))
    return cfg, L.init_lfm2_params(cfg, jax.random.key(3))


def _engine(model="gpt", **kw):
    cfg, params = _toy(model)
    kw.setdefault("num_slots", SLOTS)
    return serving.Engine(params=params, config=cfg, page_size=PAGE,
                          prefill_chunk=CHUNK, max_seq_len=64, **kw)


def _requests(n=3, seed=5):
    rng = np.random.default_rng(seed)
    return [serving.Request(rng.integers(1, 60, int(rng.integers(5, 30))),
                            max_new_tokens=int(rng.integers(3, 7)))
            for _ in range(n)]


def _lowered(eng, b, t):
    args, kw = eng._step_args(b, t)
    return eng._paged_step.lower(*args, **kw).as_text(debug_info=True)


# -- names ------------------------------------------------------------------

@pytest.mark.parametrize("b,t", [(SLOTS, 1), (1, PAGE), (1, CHUNK)])
def test_lowered_module_is_named_by_its_dispatch_shape(b, t):
    eng = _engine()
    assert eng._exe_name(b, t) == f"pt_paged_b{b}_t{t}"
    assert f"module @jit_pt_paged_b{b}_t{t} " in _lowered(eng, b, t)


def test_one_trace_a_shape_and_one_wrapper_a_layout():
    """Engines of one builder key share the wrapper of a layout: a second
    engine over warm shapes traces nothing, as with the one jit."""
    eng = _engine()
    eng.run(_requests())
    warm = profiler.serving_counters()["paged_traces"]
    other = _engine()
    assert other._paged_step is eng._paged_step
    layout = eng._operands(SLOTS, 1)[0]
    assert other._paged_step.exe(layout) is eng._paged_step.exe(layout)
    other.run(_requests(seed=6))
    assert profiler.serving_counters()["paged_traces"] == warm


def test_speculative_engine_and_page_copy_are_named_too():
    eng = _engine(num_slots=5, speculate_k=2)
    assert eng._spec_draft.__name__ == eng._draft_exe == "pt_draft_b5_t2"
    assert eng._spec_verify.__name__ == eng._verify_exe == "pt_verify_b5_t3"
    assert eng._page_copy.__name__ == "pt_page_copy"


# -- spans ------------------------------------------------------------------

def _session_spans(tmp_path, eng, reqs):
    """The ``pt.serve.*`` annotations of a profiler session around
    ``eng.run(reqs)``: ``[(name, start_ns, end_ns, {key: value})]``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    return sorted((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                   {k: str(v) for k, v in ev.stats})
                  for plane in pd.planes for line in plane.lines
                  for ev in line.events if ev.name.startswith("pt.serve."))


@pytest.mark.parametrize("spec", [False, True], ids=["paged", "speculative"])
def test_feed_launch_and_wait_of_a_dispatch_carry_one_name(tmp_path, spec):
    eng = _engine(num_slots=4, **({"speculate_k": 2} if spec else {}))
    eng.run(_requests())                    # compiled before the session
    spans = _session_spans(tmp_path, eng, _requests(seed=7))
    feeds = [s for s in spans if s[0] == "pt.serve.feed"]
    launches = [s for s in spans if s[0] == "pt.serve.launch"]
    waits = sorted((s for s in spans if s[0] == "pt.serve.wait"),
                   key=lambda s: s[1])
    assert feeds and len(feeds) == len(launches) == len(waits)
    seen = set()
    for feed, launch, wait in zip(sorted(feeds, key=lambda s: s[1]),
                                  sorted(launches, key=lambda s: s[1]),
                                  waits):
        assert feed[3] == launch[3] == wait[3], (feed, launch, wait)
        assert set(feed[3]) == {"kind", "exe"}
        assert feed[1] <= launch[1] and launch[2] <= feed[2]    # nested
        assert feed[2] <= wait[1]
        seen.add((feed[3]["kind"], feed[3]["exe"]))
    want = {("chunk", "pt_paged_b1_t8"), ("chunk", "pt_paged_b1_t16")}
    want |= {("draft", "pt_draft_b4_t2"), ("verify", "pt_verify_b4_t3")} \
        if spec else {("decode", "pt_paged_b4_t1")}
    assert seen <= want and {k for k, _ in seen} == {k for k, _ in want}
    # the other phases carry nothing
    assert all(not s[3] for s in spans
               if s[0] in ("pt.serve.step", "pt.serve.admit",
                           "pt.serve.emit"))


# -- scopes -----------------------------------------------------------------

@pytest.mark.parametrize("b,t", [(SLOTS, 1), (1, CHUNK)])
def test_lowered_gpt_step_holds_every_stage_scope(b, t):
    text = _lowered(_engine(), b, t)
    for scope in GPT_SCOPES:
        assert f"{scope}/" in text, scope


@pytest.mark.parametrize("model", ["gpt", "xing4", "afmoe", "lfm2"])
def test_every_served_models_step_holds_head_and_tail(model):
    eng = _engine(model)
    for b, t in ((SLOTS, 1), (1, CHUNK)):
        text = _lowered(eng, b, t)
        for scope in ("pt_head", "pt_tail"):
            assert f"{scope}/" in text, (model, b, t, scope)


def test_verify_step_holds_the_stage_scopes():
    eng = _engine(num_slots=5, speculate_k=2)
    z = lambda *sh, dt=np.int32: np.zeros(sh, dt)
    B, MP = 5, eng.pool.table.shape[1]
    text = eng._spec_verify.lower(
        eng.params, eng._kc, eng._vc, z(B, 3), z(B), z(B), z(B, dt=bool),
        z(B, MP), z(B), z(B, dt=bool), np.ones(B, np.float32),
        np.ones(B, np.float32), z(B, 2, dt=np.uint32)).as_text(
            debug_info=True)
    assert "module @jit_pt_verify_b5_t3 " in text
    for scope in ("pt_attn_qkv", "pt_kv_write", "pt_attn_read",
                  "pt_attn_out", "pt_ffn", "pt_head"):
        assert f"{scope}/" in text, scope


# -- the reader of a profile ------------------------------------------------

MS = 1_000_000
DECODE, CHUNK_EXE = "pt_paged_b16_t1", "pt_paged_b1_t16"


def _events():
    """One device: a chunk run 5-11 ms (an embed and an ffn operation), two
    decode runs 16-26 and 31-39 (the first a ``%while`` of 10 ms around a
    4 ms ffn and a 5 ms qkv operation: 1 ms its own, under no scope), a page
    copy 40-41 that no span names. Host: chunk feed 2-4 and wait 4-12,
    decode feed 13-15 and wait 15-27, decode feed 29-30 and wait 30-40."""
    stack = "jit(pt_paged_b16_t1)/jit(main)/while/body/"
    modules = [(f"jit_{CHUNK_EXE}(123)", 5 * MS, 6 * MS),
               (f"jit_{DECODE}(456)", 16 * MS, 10 * MS),
               (f"jit_{DECODE}(456)", 31 * MS, 8 * MS),
               ("jit_pt_page_copy(789)", 40 * MS, 1 * MS)]
    ops = [("%fusion.1 = bf16[8] fusion()", 5 * MS, 3 * MS,
            "jit(x)/jit(main)/pt_embed/add"),
           ("%fusion.2 = bf16[8] fusion()", 8 * MS, 3 * MS,
            "pt_layers/while/body/closed_call/pt_ffn/dot_general"),
           ("%while.3 = (s32[]) while()", 16 * MS, 10 * MS,
            "jit(x)/jit(main)/while"),
           ("%fusion.4 = bf16[8] fusion()", 16 * MS, 4 * MS,
            stack + "pt_ffn/dot_general"),
           ("%fusion.5 = bf16[8] fusion()", 20 * MS, 5 * MS,
            stack + "pt_attn_qkv/jit(inner)/dot_general"),
           ("%fusion.4 = bf16[8] fusion()", 31 * MS, 8 * MS,
            stack + "pt_ffn/dot_general"),
           ("%copy.6 = bf16[8] copy()", 40 * MS, 1 * MS, "")]

    def span(name, kind, exe, a, b):
        return (f"pt.serve.{name}", a * MS, (b - a) * MS,
                {"kind": kind, "exe": exe})

    spans = [span("feed", "chunk", CHUNK_EXE, 2, 4),
             span("launch", "chunk", CHUNK_EXE, 3, 4),
             span("wait", "chunk", CHUNK_EXE, 4, 12),
             ("pt.serve.emit", 12 * MS, 1 * MS, {}),
             span("feed", "decode", DECODE, 13, 15),
             span("wait", "decode", DECODE, 15, 27),
             span("feed", "decode", DECODE, 29, 30),
             span("wait", "decode", DECODE, 30, 40)]
    return modules, ops, spans


@pytest.mark.parametrize("exe,want", [
    (DECODE, {"kind": "decode", "runs": 2, "device_s": 0.018, "scopes": {
        "pt_ffn": 0.012, "pt_attn_qkv": 0.005, DT.UNSCOPED: 0.001}}),
    (CHUNK_EXE, {"kind": "chunk", "runs": 1, "device_s": 0.006, "scopes": {
        "pt_embed": 0.003, "pt_ffn": 0.003}}),
    ("pt_page_copy", {"kind": None, "runs": 1, "device_s": 0.001,
                      "scopes": {DT.UNSCOPED: 0.001}}),
])
def test_device_time_by_executable_and_scope(exe, want):
    got = DT.reduce_events(*_events())["executables"][exe]
    scopes, want_scopes = got.pop("scopes"), want.pop("scopes")
    assert got == pytest.approx(want)
    assert scopes == pytest.approx(want_scopes)


@pytest.mark.parametrize("early_ms", [0, 2])
def test_device_time_splits_the_idle_three_ways(early_ms):
    """Window 5-41 ms: idle 11-16 (fetch 1, turn 1, launch 3), 26-31
    (fetch 1, turn 2, launch 2), 39-40 (fetch 1); the same off a device
    plane that lies 2 ms early, given the shift that lays it back."""
    modules, ops, spans = _events()
    early = early_ms * MS
    got = DT.reduce_events(
        [(n, s - early, d) for n, s, d in modules],
        [(n, s - early, d, o) for n, s, d, o in ops], spans, early)
    assert got["clock_shift_s"] == pytest.approx(early_ms / 1e3)
    assert (got["window_s"], got["busy_s"]) == pytest.approx((0.036, 0.025))
    assert got["idle"] == pytest.approx({
        "launch_s": 0.005, "fetch_s": 0.003, "turn_s": 0.003,
        "paged_runs": 3})
    idle = got["idle"]
    assert idle["launch_s"] + idle["fetch_s"] + idle["turn_s"] == \
        pytest.approx(got["idle_s"])


def _pb(*fields):
    """A protobuf message from ``(field, value)`` pairs: an int a varint,
    bytes a length-delimited field."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for field, value in fields:
        if isinstance(value, int):
            out += varint(field << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(field << 3 | 2) + varint(len(value)) + value
    return out


def test_op_names_off_the_files_event_metadata():
    """An XSpace by hand, as xplane.proto lays it out: a TPU plane whose
    event metadata carry ``program_id`` and ``tf_op`` (one as a string, one
    as a reference to a stat metadata's name), an event metadata with no
    ``tf_op``, and a host plane, which is passed over."""
    big = 2 ** 63 + 5                       # a program id past int64

    def stat_meta(i, name):
        return (5, _pb((1, i), (2, _pb((1, i), (2, name)))))

    def event_meta(i, text, *stats):
        return (4, _pb((1, i), (2, _pb((1, i), (2, text),
                                       *((5, _pb(*st)) for st in stats)))))

    tpu = _pb((1, 7), (2, "/device:TPU:0"), (3, b"\x12\x03abc"),
              stat_meta(1, "program_id"), stat_meta(2, "tf_op"),
              stat_meta(3, "flops"),
              stat_meta(9, "jit(f)/pt_head/dot_general:"),
              event_meta(1, "%fusion.1 = bf16[8] fusion()", ((1, 1), (3, big)),
                         ((1, 3), (3, 64)),
                         ((1, 2), (5, "jit(f)/while/body/pt_ffn/add:"))),
              event_meta(2, "%fusion.2 = bf16[8] fusion()", ((1, 1), (3, 11)),
                         ((1, 2), (7, 9))),
              event_meta(3, "%copy-done = bf16[8] copy-done()",
                         ((1, 1), (3, 11))))
    host = _pb((2, "/host:CPU"), stat_meta(2, "tf_op"),
               event_meta(1, "x", ((1, 2), (5, "pt_ffn/y"))))
    got = DT.op_names(_pb((1, tpu), (1, host)))
    assert got == {0: {
        (big, "%fusion.1 = bf16[8] fusion()"): "jit(f)/while/body/pt_ffn/add:",
        (11, "%fusion.2 = bf16[8] fusion()"): "jit(f)/pt_head/dot_general:"}}


def test_scope_of_takes_the_innermost():
    assert DT.scope_of("jit(f)/pt_attn_full/pt_attn_gate/mul") == \
        "pt_attn_gate"
    assert DT.scope_of("jit(pt_paged_b1_t16)/jit(main)/add") == DT.UNSCOPED
    assert DT.scope_of("jit(f)/while/body/pt_ffn/add:") == "pt_ffn"
    assert DT.scope_of("") == DT.scope_of(None) == DT.UNSCOPED


def test_summary_names_every_executable_and_scope(capsys):
    text = DT.format_summary(DT.reduce_events(*_events()))
    for word in (DECODE, CHUNK_EXE, "kind=decode", "kind=chunk", "pt_ffn",
                 "pt_attn_qkv", DT.UNSCOPED, "launch", "fetch", "turn"):
        assert word in text, word
    # a profile of a CPU run holds no device plane: said, not guessed
    with pytest.raises(FileNotFoundError):
        profiler.device_time_summary(str("/nonexistent-profile-dir"))
