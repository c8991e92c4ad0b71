"""A training run: one compiled step with its state is built from the seed,
driven through its first steps by the window's own call and feed, handed to
the measured window, and then compared with the plain reference."""
import functools
import sys
import time

import jax

from . import compare, events, reference, runner, spans as spans_mod, sut, \
    traffic

STEP_SPAN = "HybridTrainStep.__call__"
FEED_SPAN = "input batch"
WAIT_SPAN = "block_until_ready"


def _feed_and_step(step, mix, seed, i, vocab, sp):
    """The window's own call and feed: a new batch of seeded ids made on the
    host, handed to the step, the step ended by block_until_ready."""
    with sp.span(FEED_SPAN):
        ids = traffic.train_batch(mix, seed, i, vocab)
    with sp.span(STEP_SPAN):
        loss = step(ids)
    with sp.span(WAIT_SPAN):
        jax.block_until_ready(loss)
    return loss


def require_trainable(cell):
    """A family may serve only: a training cell of one is refused in a
    sentence, before any set-up."""
    fam = cell.family
    if not hasattr(fam.reference, "loss_and_grads"):
        sys.exit(f"benchmark: {cell.name} is a training cell, and the model "
                 f"family {fam.name!r} ({fam.path}) has no loss_and_grads in "
                 f"its reference.py: it serves only; nothing was run")


def _leaf_sq_norms(cell):
    return functools.partial(compare.leaf_sq_norms,
                             leaf_parts=cell.family.weights.leaf_parts)


def program_readings(step, cell, seed, sp, n_check):
    """Drive the step through its first ``n_check`` steps; read each loss,
    the first gradient's per-leaf norms out of the optimizer's state after
    step 1, and the parameters' change after every step."""
    cfg, mix, fam = cell.config, cell.traffic, cell.family
    b1 = cell.file["trainer"]["optimizer"]["beta1"]
    losses, change = [], []
    grad = None
    for i in range(n_check):
        losses.append(float(_feed_and_step(step, mix, seed, i,
                                           cfg["vocab_size"], sp)))
        if i == 0:
            m = compare.norms(_leaf_sq_norms(cell)(
                fam.sut.trainer_moment1(step)))
            grad = {k: v / (1 - b1) for k, v in m.items()}
        change.append(compare.norms(compare.change_sq_norms(
            step.params, fam.weights, cfg, seed, cfg["dtypes"]["params"])))
    return {"losses": losses, "grad": grad, "change": change}


def reference_readings(cell, seed, n_steps, mm="exact", batch_rows=None):
    """The same first steps by the plain reference (or, with ``mm``, by the
    control). ``batch_rows`` keeps only some rows of each batch (a planted
    fault)."""
    cfg, mix, chk = cell.config, cell.traffic, cell.file["check"]
    hp = cell.file["trainer"]["optimizer"]
    ref = reference.TrainReference(
        cell.family, cfg, seed, hp, rows=chk["reference_rows"],
        mm=reference.MATMULS[mm],
        param_dtype=cfg["dtypes"]["params"],
        moment_dtype=cfg["dtypes"]["moments"])
    losses, change = [], []
    grad = None
    for i in range(n_steps):
        ids = traffic.train_batch(mix, seed, i, cfg["vocab_size"])
        if batch_rows is not None:
            ids = ids[batch_rows]
        losses.append(ref.step(
            ids, leaf_sq_norms=_leaf_sq_norms(cell) if i == 0 else None,
            last=i == n_steps - 1))
        if i == 0:
            grad = compare.norms(ref.grad_sq)
        change.append(compare.norms(compare.change_sq_norms(
            ref.params, cell.family.weights, cfg, seed,
            cfg["dtypes"]["params"])))
    ref.params = ref.m = ref.v = None
    return {"losses": losses, "grad": grad, "change": change,
            "seconds": ref.seconds}


def compare_readings(prog, ref, limits, checks=None):
    """The widest gap of a step's loss over the steps the reference follows,
    and the first gradient's norm and the parameters' change after the
    reference's last step, each by the worst leaf."""
    checks = checks or compare.Checks()
    n = len(ref["losses"])
    gaps = [abs(prog["losses"][i] - ref["losses"][i]) / abs(ref["losses"][i])
            for i in range(n)]
    checks.add("loss_gap_max", max(gaps), limits["loss_gap"])
    g, at = compare.worst_leaf_gap(prog["grad"], ref["grad"])
    checks.add("grad_norm_gap", g, limits["grad_norm_gap"])
    skip = compare.nought_gradient_leaves(ref["grad"])
    c, at_c = compare.worst_leaf_gap(prog["change"][n - 1],
                                     ref["change"][n - 1], skip)
    checks.add("change_norm_gap", c, limits["change_norm_gap"])
    checks.worst_leaves = {"grad": at, "change": at_c}
    return checks


def run(cell, seed, seconds, want_trace, t_start, devices):
    require_trainable(cell)
    cfg, mix, chk = cell.config, cell.traffic, cell.file["check"]
    trainer, fam = cell.file["trainer"], cell.family
    sp = spans_mod.Spans()
    ev = events.JaxEvents()
    mesh = sut.make_mesh(trainer.get("mesh"))
    w = fam.weights.make_weights(cfg, seed, cfg["dtypes"]["params"],
                                 fam.sut.param_shardings(cfg, mesh))
    step = fam.sut.make_trainer(cfg, trainer, w, mesh)
    del w
    prog = program_readings(step, cell, seed, sp, chk["steps"])
    tokens_per_step = mix["batch"] * mix["seq"]

    tracer = runner.Tracer(sp, want_trace, cell.file.get("trace_seconds", 5))
    compiles0 = ev.compiles
    tracer.start()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    n, i = 0, chk["steps"]
    while True:
        _feed_and_step(step, mix, seed, i, cfg["vocab_size"], sp)
        i += 1
        n += 1
        now = time.perf_counter()
        tracer.maybe_stop(now)
        if now - t0 >= seconds:
            break
    window_s = now - t0            # closes with the step that passed the mark
    compiles = ev.compiles - compiles0
    tracer.stop()

    peak = runner.memory_peak_bytes(devices)
    sut.release_trainer(step)
    del step
    sut.free_device_memory()
    trace = tracer.load()

    ref = reference_readings(cell, seed, chk["reference_steps"])
    checks = compare_readings(prog, ref, chk["limits"])

    values = {"train_tokens_per_s": n * tokens_per_step / window_s,
              "setup_s": setup_s}
    facts = {"kind": "train", "steps": n, "tokens": n * tokens_per_step,
             "batch": mix["batch"], "seq": mix["seq"],
             "compiles_in_window": compiles, "losses": prog["losses"],
             "reference_losses": ref["losses"], "window_t0": t0}
    ctx = runner.Context(cell, devices, window_s, sp, {}, facts, trace,
                         (tracer.t0, tracer.t1), tracer.stop_cost_s)
    return {"checks": checks, "attempted": n, "failed": 0, "values": values,
            "ctx": ctx, "peak": peak}
