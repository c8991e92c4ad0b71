"""The xing4 family's adapter to the system under test: the only file of the
family that imports the program. It builds the program's configuration object
from a configuration file's published keys and hands the program the
benchmark's weights. The family serves only. No arithmetic of the yardstick
lives here."""


def program_config(cfg, **over):
    try:
        from paddle_tpu.models.xing4 import Xing4Config
    except ImportError:
        raise SystemExit("this checkout's paddle_tpu has no models/xing4.py: "
                         "it cannot serve a configuration of the xing4 "
                         "family") from None
    return Xing4Config.from_dict(cfg, compute_dtype=cfg["dtypes"]["compute"],
                                 **over)


def make_engine(cfg, engine_args, weights_tree):
    """The engine with every rung of its chunk ladder and its decode step
    compiled before it is handed over: the harness's two short warm-up
    requests reach only the lowest rungs."""
    from paddle_tpu import serving
    engine = serving.Engine(params=weights_tree, config=program_config(cfg),
                            **engine_args)
    return engine.warm_up()
