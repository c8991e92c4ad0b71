"""The jamba family's adapter to the system under test: the only file of the
family that imports the program. It builds the program's configuration object
from a configuration file's published keys and hands the program the
benchmark's weights. The family serves only. No arithmetic of the yardstick
lives here.

A checkout whose program lacks the model (the parent of the PR that brought
it) is refused when the family is loaded, before any set-up: the weights
alone are 6 GB and a minute."""
import os

from benchmark.harness.loader import ROOT

NO_PROGRAM = ("this checkout's paddle_tpu has no models/jamba.py: it cannot "
              "serve a configuration of the jamba family")
if not os.path.isfile(os.path.join(ROOT, "paddle_tpu", "models", "jamba.py")):
    raise SystemExit(NO_PROGRAM)


def program_config(cfg, **over):
    try:
        from paddle_tpu.models.jamba import JambaConfig
    except ImportError:
        raise SystemExit(NO_PROGRAM) from None
    return JambaConfig.from_dict(cfg, compute_dtype=cfg["dtypes"]["compute"],
                                 **over)


def make_engine(cfg, engine_args, weights_tree):
    """The engine with every rung of its chunk ladder and its decode step
    compiled before it is handed over: the harness's two short warm-up
    requests reach only the lowest rungs."""
    from paddle_tpu import serving
    engine = serving.Engine(params=weights_tree, config=program_config(cfg),
                            **engine_args)
    return engine.warm_up()
