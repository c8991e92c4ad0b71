"""The GPT family's adapter to the system under test: the only file of the
family that imports the program. It builds the program's own configuration
object from a configuration file, hands the program the benchmark's weights,
and reads back the optimizer's state. No arithmetic of the yardstick lives
here."""
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

CONFIG_KEYS = ("vocab_size", "hidden_size", "num_layers", "num_heads",
               "max_seq_len", "ffn_mult", "layer_norm_epsilon",
               "initializer_range")


def program_config(cfg, **over):
    from paddle_tpu.models.gpt import GPTConfig
    kw = {k: cfg[k] for k in CONFIG_KEYS}
    kw["compute_dtype"] = cfg["dtypes"]["compute"]
    kw.update(over)
    return GPTConfig(**kw)


def param_shardings(cfg, mesh):
    if mesh is None:
        return None
    from paddle_tpu.models.gpt_hybrid import gpt_param_specs
    specs = gpt_param_specs(program_config(cfg), pp=mesh.shape.get("pp", 1))
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs)


def make_trainer(cfg, trainer, weights_tree, mesh):
    """HybridTrainStep as the cell's file states it, holding the benchmark's
    weights. The program makes a tree of its own first (it takes none): that
    costs set-up only the program can shorten."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt_hybrid import HybridTrainStep
    hp = trainer["optimizer"]
    clip = hp.get("clip_global_norm")
    opt = paddle.optimizer.AdamW(
        hp["lr"], beta1=hp["beta1"], beta2=hp["beta2"], epsilon=hp["epsilon"],
        weight_decay=hp["weight_decay"],
        grad_clip=paddle.nn.ClipGradByGlobalNorm(clip) if clip else None,
        moment_dtype=cfg["dtypes"]["moments"])
    pcfg = program_config(cfg, use_flash=trainer.get("flash", True),
                          remat=trainer.get("remat", True))
    step = HybridTrainStep(pcfg, opt, mesh=mesh,
                           param_dtype=jnp.dtype(cfg["dtypes"]["params"]))
    old = step.params
    step.params = weights_tree
    for leaf in jax.tree_util.tree_leaves(old):
        leaf.delete()
    return step


def trainer_moment1(step):
    """The optimizer's first moments as a tree in the parameters' layout."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(step.params)
    names = ["/".join(str(p) for p in path) for path, _ in flat]
    slots = step.opt_state["slots"]
    return jax.tree_util.tree_unflatten(
        treedef, [slots[n]["moment1"] for n in names])


def make_engine(cfg, engine_args, weights_tree):
    from paddle_tpu import serving
    return serving.Engine(params=weights_tree, config=program_config(cfg),
                          **engine_args)
