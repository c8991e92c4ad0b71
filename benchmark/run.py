#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run. Everything that belongs to the cell is found
by its name in BENCHMARK.json (see harness/loader.py). Without a TPU holding
the chips the cell asks for it exits with another code than 0 and prints no
result; the last line of standard output is otherwise the one JSON result."""
import time

T_START = time.perf_counter()          # set-up runs from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def run_cell(cell, seed, seconds, want_trace, t_start=None, require_chip=True):
    """Drive one run of ``cell`` and print its result line. ``require_chip``
    is False only for the rehearsal on the CPU (benchmark/tests), whose
    output says ``platform: cpu`` and carries no device metric."""
    import jax
    from benchmark.harness import runner, serve, sut, train
    t_start = time.perf_counter() if t_start is None else t_start
    if require_chip:
        runner.require_chips(cell.chips)
    sut.use_cache_dir(os.path.join(ROOT, ".jax_cache"))
    devices = jax.devices()[:cell.chips]
    kinds = {"train": train.run, "serve_closed": serve.run,
             "serve_open": serve.run}
    res = kinds[cell.traffic["kind"]](cell, seed, seconds, want_trace,
                                      t_start, devices)
    ctx = res["ctx"]
    device = runner.device_info(devices, res["peak"])
    brk = None
    if want_trace:
        metrics = runner.per_layer_metrics(cell, ctx)
        if ctx.trace is not None and ctx.trace.used_devices():
            devs = ctx.trace.used_devices()
            device["busy_s"] = sum(ctx.trace.busy_s(d) for d in devs) / len(devs)
            device["window_s"] = ctx.trace.window_s
            brk = runner.breakdown(ctx.trace)
    else:
        metrics = runner.end_to_end_metrics(cell, res["values"])
    if devices[0].platform != "tpu":
        metrics = runner.off_chip_names(metrics)
    return runner.emit(res["checks"], res["attempted"], res["failed"],
                       metrics, device, brk, res.get("extra"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        sys.exit("benchmark: the program (paddle_tpu/) is not in this "
                 "directory; nothing was run")
    from benchmark.harness import loader
    cell = loader.load_cell(args.workload)
    run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
