#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from, many seeds in
one process (set-up is long): for each seed the program's numbers against the
plain reference (the lower reading), and on the first ``--control`` seeds the
control's (the reference in float8 put in the program's place) and, for a
training cell, the planted fault's (half of the batch left out, the mean
taken over the rest). Every set of numbers goes through the run's own
``Checks`` with the cell's limits, so each prints ``ok`` or ``NOT CORRECT``
as a run would. Not run by the benchmark's own runs.

    python3 benchmark/tools/readings.py --workload <cell> --seeds 1,2,3 \
        --control 3 [--seconds 25] [--out chiprun_out/readings]
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _verdict(tag, seed, checks):
    """The numbers compared beside the cell's limits, as a run prints them."""
    for line in checks.lines():
        print(f"seed {seed} {tag}: {line}", flush=True)
    return {"compared": checks.as_dict(), "correct": checks.correct}


def train_seed(cell, seed, control):
    from benchmark.harness import spans, sut, train
    train.require_trainable(cell)
    cfg, trainer, chk = cell.config, cell.file["trainer"], cell.file["check"]
    fam = cell.family
    ref_steps, limits = chk["reference_steps"], chk["limits"]
    mesh = sut.make_mesh(trainer.get("mesh"))
    sh = fam.sut.param_shardings(cfg, mesh)
    t0 = time.perf_counter()
    w = fam.weights.make_weights(cfg, seed, cfg["dtypes"]["params"], sh)
    step = fam.sut.make_trainer(cfg, trainer, w, mesh)
    del w
    prog = train.program_readings(step, cell, seed, spans.Spans(), chk["steps"])
    t_prog = time.perf_counter() - t0
    sut.release_trainer(step)
    del step
    sut.free_device_memory()
    t0 = time.perf_counter()
    ref = train.reference_readings(cell, seed, ref_steps)
    t_ref = time.perf_counter() - t0
    out = {"seed": seed, "t_program_s": t_prog, "t_reference_s": t_ref,
           "program_losses": prog["losses"], "reference_losses": ref["losses"],
           "reference_step_seconds": ref["seconds"],
           "program": _verdict("program", seed,
                               train.compare_readings(prog, ref, limits))}
    if control:
        for tag, kw in (("control_fp8", {"mm": "fp8"}),
                        ("fault_half_batch",
                         {"batch_rows": slice(0, cell.traffic["batch"] // 2)})):
            sut.free_device_memory()
            alt = train.reference_readings(cell, seed, ref_steps, **kw)
            out[tag] = _verdict(tag, seed,
                                train.compare_readings(alt, ref, limits))
    sut.free_device_memory()
    return out


def serve_seed(cell, seed, control, seconds):
    from benchmark.harness import compare, runner, serve, spans, sut, traffic
    cfg, mix, chk = cell.config, cell.traffic, cell.file["check"]
    fam = cell.family
    sp = spans.Spans()
    w = fam.weights.make_weights(cfg, seed, cfg["dtypes"]["params"])
    engine = fam.sut.make_engine(cfg, cell.file["engine"], w)
    del w
    serve.warm_up(engine, cfg, engine.page_size, sp)
    log = serve.ServeLog()
    tracer = runner.Tracer(sp, False, 0)
    serve.drive(engine, mix, traffic.RequestSource(mix, seed, cfg["vocab_size"]),
                seconds, log, sp, tracer, lambda: None)
    sample = serve.pick_sample(log, seed, chk["requests"])
    for r in log.recs:
        r.req = None
    del engine
    sut.free_device_memory()
    ids, rows = serve.sample_rows(sample, mix)
    def verdict(tag, mm):
        gap, n_tok = serve.reference_gap(cell, seed, ids, rows, mm=mm)
        checks = compare.Checks()
        checks.add("served_logit_gap_max", gap, chk["limits"]["logit_gap"])
        return {**_verdict(tag, seed, checks), "tokens": n_tok}

    t0 = time.perf_counter()
    out = {"seed": seed, "program": verdict("program", "exact"),
           "t_reference_s": time.perf_counter() - t0,
           "lengths": [(p, len(t)) for p, t in rows]}
    if control:
        out["control_fp8"] = verdict("control_fp8", "fp8")
    sut.free_device_memory()
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=25)
    ap.add_argument("--out", default="chiprun_out/readings")
    a = ap.parse_args()
    from benchmark.harness import loader, runner, sut
    cell = loader.load_cell(a.workload)
    runner.require_chips(cell.chips)
    sut.use_cache_dir(os.path.join(ROOT, ".jax_cache"))
    os.makedirs(a.out, exist_ok=True)
    path = os.path.join(a.out, a.workload + ".jsonl")
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        if cell.traffic["kind"] == "train":
            out = train_seed(cell, seed, i < a.control)
        else:
            out = serve_seed(cell, seed, i < a.control, a.seconds)
        out["t_total_s"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
        with open(path, "a") as f:
            f.write(json.dumps(out) + "\n")


if __name__ == "__main__":
    main()
