#!/usr/bin/env python3
"""Readings for a serving cell's limit, with the exact reference computed
ONCE a seed (``readings.py`` computes it anew for every comparison; at a size
where one forward of the reference takes a minute that is most of a call).
For each seed: the engine serves the cell's mix for ``--seconds``, a sample of
the finished requests gets the exact reference forward, and against it are
read the program's served tokens, then on the first ``--control`` seeds the
float8 control's first tokens and on the first ``--faults`` seeds those of
each planted fault that the cell's family keeps in an optional
``families/<family>/faults.py`` (``FAULTS``: name -> context in which the
family's reference computes the faulty model). Every number goes through the
run's own ``Checks`` with the cell's limit. Not run by the benchmark's runs.

    python3 benchmark/tools/serve_readings.py --workload <cell> \
        --seeds 1,2,3 --control 2 --faults 1 [--seconds 25]
"""
import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def serve_sample(cell, seed, seconds):
    """ids [n, T] and (prompt_len, tokens) of a sample of what the engine
    served, as ``readings.serve_seed`` takes it."""
    from benchmark.harness import runner, serve, spans, sut, traffic
    cfg, mix = cell.config, cell.traffic
    sp = spans.Spans()
    w = cell.family.weights.make_weights(cfg, seed, cfg["dtypes"]["params"])
    engine = cell.family.sut.make_engine(cfg, cell.file["engine"], w)
    del w
    serve.warm_up(engine, cfg, engine.page_size, sp)
    log = serve.ServeLog()
    serve.drive(engine, mix, traffic.RequestSource(mix, seed, cfg["vocab_size"]),
                seconds, log, sp, runner.Tracer(sp, False, 0), lambda: None)
    sample = serve.pick_sample(log, seed, cell.file["check"]["requests"])
    for r in log.recs:
        r.req = None
    del engine
    sut.free_device_memory()
    return serve.sample_rows(sample, mix)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=2)
    ap.add_argument("--faults", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=25)
    ap.add_argument("--out", default="chiprun_out/readings")
    a = ap.parse_args()
    import jax.numpy as jnp
    import numpy as np
    from benchmark.harness import compare, loader, reference, runner, sut
    cell = loader.load_cell(a.workload)
    runner.require_chips(cell.chips)
    sut.use_cache_dir(os.path.join(ROOT, ".jax_cache"))
    os.makedirs(a.out, exist_ok=True)
    path = os.path.join(a.out, a.workload + ".serve.jsonl")
    cfg, fam = cell.config, cell.family
    limit = cell.file["check"]["limits"]["logit_gap"]
    try:
        faults = importlib.import_module(
            fam.reference.__package__ + ".faults").FAULTS
    except ModuleNotFoundError:
        faults = {}

    def logits(seed, ids, mm):
        return fam.reference.served_logits(
            cfg, seed, jnp.asarray(ids), cfg["dtypes"]["params"], mm)

    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        ids, rows = serve_sample(cell, seed, a.seconds)
        t_serve = time.perf_counter() - t0
        t0 = time.perf_counter()
        ref = logits(seed, ids, reference.mm_exact)
        ref.block_until_ready()
        out = {"seed": seed, "t_serve_s": t_serve,
               "t_reference_s": time.perf_counter() - t0,
               "lengths": [(p, len(t)) for p, t in rows]}

        def read(tag, rows):
            gaps = np.concatenate(compare.logit_gaps(ref, rows))
            checks = compare.Checks()
            checks.add("served_logit_gap_max", gaps.max(), limit)
            for line in checks.lines():
                print(f"seed {seed} {tag}: {line}", flush=True)
            out[tag] = {"max": float(gaps.max()), "mean": float(gaps.mean()),
                        "p99": float(np.percentile(gaps, 99)),
                        "over_half_max": int((gaps > gaps.max() / 2).sum()),
                        "tokens": int(len(gaps)), "correct": checks.correct}

        def in_place(low):
            """The tokens that another model puts first at the positions
            the program served."""
            first = np.asarray(jnp.argmax(low, axis=-1))
            return [(p, first[k, p - 1:p - 1 + len(t)].tolist())
                    for k, (p, t) in enumerate(rows)]

        read("program", rows)
        if i < a.control:
            read("control_fp8", in_place(logits(seed, ids, reference.mm_fp8)))
        if i < a.faults:
            for name, fault in faults.items():
                with fault():
                    low = logits(seed, ids, reference.mm_exact)
                read("fault_" + name, in_place(low))
        del ref
        sut.free_device_memory()
        out["t_total_s"] = time.perf_counter() - t0 + t_serve
        print(json.dumps(out), flush=True)
        with open(path, "a") as f:
            f.write(json.dumps(out) + "\n")


if __name__ == "__main__":
    main()
