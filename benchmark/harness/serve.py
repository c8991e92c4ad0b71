"""A serving run: one engine holding the seed's weights, load from one
process and one thread (closed loop of waiting callers, or an open loop at a
fixed rate), every token timed on the client's side through ``on_token``,
and a seeded sample of the finished requests compared with the plain
reference once the window has closed."""
import time

import jax.numpy as jnp
import numpy as np

from . import compare, events, reference, runner, spans as spans_mod, sut, \
    traffic

STEP_SPAN = "Engine.step"
SUBMIT_SPAN = "Engine.submit"
NEVER = 1e9          # seconds: a request that failed or was refused
WAIT_AFTER_CLOSE_S = 60.0


class Rec:
    """One request as its client saw it."""
    __slots__ = ("prompt", "olen", "due", "issued", "times", "tokens", "req",
                 "failed", "ramp", "ended")

    def __init__(self, prompt, olen, due, ramp):
        self.prompt, self.olen = prompt, olen
        self.due, self.ramp = due, ramp
        self.issued = None
        self.times, self.tokens = [], []
        self.req = None
        self.failed = False
        self.ended = None          # the program's finish reason, once it ends

    def on_token(self, _req, tok):
        self.times.append(time.perf_counter())
        self.tokens.append(int(tok))

    @property
    def plen(self):
        return len(self.prompt)

    @property
    def done(self):
        if self.ended is None and self.req is not None:
            self.ended = self.req.finish_reason
        return self.failed or self.ended is not None \
            or len(self.tokens) >= self.olen

    @property
    def missed(self):
        """Failed, refused, ended by anything but its length, or never
        answered: it misses any latency limit."""
        return self.failed or not self.times \
            or self.ended not in (None, "length")


class ServeLog:
    """Every request of the run, for the metrics and the per-layer readers."""

    def __init__(self):
        self.recs = []
        self.t0 = self.t_close = None

    def token_times(self):
        return np.concatenate([np.asarray(r.times) for r in self.recs
                               if r.times] or [np.zeros(0)])

    def tokens_out(self, a, b):
        t = self.token_times()
        return int(np.sum((t >= a) & (t <= b)))

    def issued_in(self, a, b):
        return [r for r in self.recs if r.issued is not None
                and a <= r.issued < b and not r.ramp]

    def ttfts(self, a, b):
        return np.array([NEVER if r.missed else r.times[0] - r.due
                         for r in self.issued_in(a, b)])

    def gaps(self, a, b):
        out = []
        for r in self.recs:
            t = np.asarray(r.times)
            if len(t) > 1:
                g, end = np.diff(t), t[1:]
                out.append(g[(end >= a) & (end <= b)])
        return np.concatenate(out or [np.zeros(0)])

    def processed(self, a, b):
        """Required work done between host instants a and b, from the
        client's side. A first token that fell in [a, b] stands for its whole
        prompt (tokens = plen, each attending its causal prefix); every later
        token for one decode step whose input attended plen + i positions."""
        tok = ctx = dtok = dctx = 0
        for r in self.recs:
            p = r.plen
            for i, t in enumerate(r.times):
                if a <= t <= b:
                    if i == 0:
                        tok += p
                        ctx += p * (p + 1) // 2
                    else:
                        dtok += 1
                        dctx += p + i
        return {"tokens": tok + dtok, "ctx_positions": ctx + dctx,
                "decode_tokens": dtok, "decode_ctx_positions": dctx}


def _issue(engine, rec, log, sp):
    rec.issued = time.perf_counter()
    if rec.due is None:
        rec.due = rec.issued
    log.recs.append(rec)
    try:
        with sp.span(SUBMIT_SPAN):
            rec.req = sut.make_request(rec.prompt, rec.olen, rec.on_token)
            engine.submit(rec.req)
    except Exception as e:  # noqa: BLE001 — a refusal is a failed request
        rec.failed = True
        print(f"request refused: {type(e).__name__}: {e}", flush=True)


def _new_rec(source, due=None):
    return Rec(*source.next(), due, False)


def _step(engine, sp):
    with sp.span(STEP_SPAN):
        return engine.step()


def warm_up(engine, cfg, page, sp):
    """The cell's own shapes and no others: two identical short requests, one
    after the other, so that the chunk step, the decode step and the page
    copy of a shared prefix are all compiled before the ramp."""
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg["vocab_size"], 2 * page + 3).astype(np.int32)
    for _ in range(2):
        rec = Rec(prompt, 3, None, True)
        rec.req = sut.make_request(prompt, 3, rec.on_token)
        engine.submit(rec.req)
        while not rec.done:
            _step(engine, sp)
    engine.pop_results()
    sp.rows.clear()


def drive(engine, mix, source, seconds, log, sp, tracer, on_open):
    """Ramp, window, and the wait after it. ``on_open()`` is called at the
    first timed instant."""
    closed = mix["kind"] == "serve_closed"
    late = []
    if closed:
        clients = [Rec(p, o, None, True)
                   for p, o in source.ramp(mix["clients"])]
        for r in clients:
            _issue(engine, r, log, sp)
        while not all(r.times or r.failed for r in log.recs if r.ramp):
            _step(engine, sp)
            for k, r in enumerate(clients):
                if r.done:
                    clients[k] = _new_rec(source)
                    _issue(engine, clients[k], log, sp)
        pending = None
    else:
        ramp_s = mix.get("ramp_s", 5.0)
        due = traffic.arrival_times(mix, ramp_s + seconds)
        clients = None
        base = time.perf_counter()
        pending = [base + d for d in due]
        while time.perf_counter() - base < ramp_s:
            now = time.perf_counter()
            while pending and pending[0] <= now:
                r = _new_rec(source, due=pending.pop(0))
                r.ramp = True
                _issue(engine, r, log, sp)
            if not _step(engine, sp):
                time.sleep(0.001)

    on_open()
    tracer.start()
    log.t0 = t0 = time.perf_counter()
    steps = 0
    while True:
        now = time.perf_counter()
        if now - t0 >= seconds:
            break
        if closed:
            for k, r in enumerate(clients):
                if r.done:
                    clients[k] = _new_rec(source)
                    _issue(engine, clients[k], log, sp)
            busy = _step(engine, sp)
        else:
            while pending and pending[0] <= now:
                r = _new_rec(source, due=pending.pop(0))
                _issue(engine, r, log, sp)
                late.append(r.issued - r.due)
            busy = _step(engine, sp)
            if not busy:
                time.sleep(0.0005)
        steps += 1
        tracer.maybe_stop()
        if steps % 64 == 0:
            engine.pop_results()
    # the window closes with the step that passed the mark, as a training
    # window does: a whole number of boundaries, so that the rate does not
    # move by a boundary's tokens (0.4%) when the last one ends a millisecond
    # early or late
    log.t_close = t_close = now
    tracer.stop()
    # after the close nothing new is issued; wait for every first token
    waiting = log.issued_in(t0, t_close)
    deadline = t_close + WAIT_AFTER_CLOSE_S
    while any(not (r.times or r.failed) for r in waiting) \
            and time.perf_counter() < deadline:
        if not _step(engine, sp):
            break
    if late:
        print(f"generator lateness ms: median {1e3 * np.median(late):.3f} "
              f"max {1e3 * np.max(late):.3f} over {len(late)} arrivals",
              flush=True)
    return steps


def pick_sample(log, seed, k):
    """A seeded sample of the requests the window finished (all are greedy),
    with the longest in it."""
    done = [r for r in log.recs if not r.ramp and not r.failed
            and len(r.tokens) >= r.olen and r.times
            and r.times[-1] <= log.t_close + WAIT_AFTER_CLOSE_S]
    if not done:
        return []
    longest = max(done, key=lambda r: r.plen + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 5])
    pick = list(rng.choice(len(rest), size=min(k - 1, len(rest)),
                           replace=False)) if rest and k > 1 else []
    return [longest] + [rest[i] for i in pick]


def sample_rows(sample, mix):
    """ids [n, T] (prompt then served tokens but the last, right-padded: the
    model is causal) at one fixed T for the mix, and (prompt_len, tokens)."""
    T = -(-(mix["prompt_len"]["max"] + mix["output_len"]["max"]) // 128) * 128
    ids = np.zeros((len(sample), T), np.int32)
    rows = []
    for i, r in enumerate(sample):
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        ids[i, :len(seq)] = seq
        rows.append((r.plen, list(r.tokens)))
    return ids, rows


def reference_gap(cell, seed, ids, rows, mm="exact"):
    """The widest gap by which a served token's logit lies below the
    reference's best. With ``mm`` naming the control, the tokens are instead
    those that the lower precision puts first at the same positions."""
    cfg = cell.config
    served_logits = cell.family.reference.served_logits
    ref = served_logits(cfg, seed, jnp.asarray(ids), cfg["dtypes"]["params"],
                        reference.mm_exact)
    if mm != "exact":
        low = served_logits(cfg, seed, jnp.asarray(ids),
                            cfg["dtypes"]["params"], reference.MATMULS[mm])
        first = np.asarray(jnp.argmax(low, axis=-1))
        rows = [(p, first[i, p - 1:p - 1 + len(t)].tolist())
                for i, (p, t) in enumerate(rows)]
    gaps = compare.logit_gaps(ref, rows)
    return float(max(g.max() for g in gaps)), int(sum(len(g) for g in gaps))


def run(cell, seed, seconds, want_trace, t_start, devices):
    cfg, mix, chk = cell.config, cell.traffic, cell.file["check"]
    sp = spans_mod.Spans()
    ev = events.JaxEvents()
    w = cell.family.weights.make_weights(cfg, seed, cfg["dtypes"]["params"])
    engine = cell.family.sut.make_engine(cfg, cell.file["engine"], w)
    del w
    warm_up(engine, cfg, engine.page_size, sp)
    source = traffic.RequestSource(mix, seed, cfg["vocab_size"])
    log = ServeLog()
    tracer = runner.Tracer(sp, want_trace, cell.file.get("trace_seconds", 5))
    mark = {}

    def on_open():
        mark["counters"] = sut.serving_counters()
        mark["compiles"] = ev.compiles
        mark["setup_s"] = time.perf_counter() - t_start

    drive(engine, mix, source, seconds, log, sp, tracer, on_open)
    c1 = sut.serving_counters()
    counters = {k: c1[k] - mark["counters"].get(k, 0) for k in c1
                if isinstance(c1[k], (int, float))}
    compiles = ev.compiles - mark["compiles"]

    peak = runner.memory_peak_bytes(devices)
    sample = pick_sample(log, seed, chk["requests"])
    for r in log.recs:
        r.done                       # keeps the finish reason
        r.req = None
    del engine
    sut.free_device_memory()
    trace = tracer.load()

    checks = compare.Checks()
    extra = {}
    if sample:
        ids, rows = sample_rows(sample, mix)
        gap, n_tok = reference_gap(cell, seed, ids, rows)
        checks.add("served_logit_gap_max", gap, chk["limits"]["logit_gap"])
        extra["tokens_compared"] = n_tok
    t0, t1 = log.t0, log.t_close
    window_s = t1 - t0
    issued = log.issued_in(t0, t1)
    ttft = log.ttfts(t0, t1)
    gaps = log.gaps(t0, t1)
    failed = sum(1 for r in issued if r.missed)
    values = {
        "serve_tokens_per_s": log.tokens_out(t0, t1) / window_s,
        "gap_p95_ms": 1e3 * float(np.percentile(gaps, 95)) if len(gaps)
        else 1e3 * NEVER,
        "setup_s": mark["setup_s"],
    }
    if len(ttft):
        extra["ttft_s"] = {"n": len(ttft), "mean": float(np.mean(ttft)),
                           "p50": float(np.median(ttft)),
                           "p90": float(np.percentile(ttft, 90)),
                           "max": float(np.max(ttft))}
    facts = {"kind": mix["kind"], "log": log, "ttft": ttft,
             "compiles_in_window": compiles, "requests": len(issued),
             "window_t0": t0}
    ctx = runner.Context(cell, devices, window_s, sp, counters, facts, trace,
                         (tracer.t0, tracer.t1), tracer.stop_cost_s)
    return {"checks": checks, "attempted": len(issued), "failed": failed,
            "values": values, "ctx": ctx, "peak": peak, "extra": extra}
