"""The rehearsal's second family's adapter to the program: its keys
translated, then the GPT family's adapter, which imports the program."""
from .weights import GPT, as_gpt


def program_config(cfg, **over):
    return GPT.sut.program_config(as_gpt(cfg), **over)


def param_shardings(cfg, mesh):
    return GPT.sut.param_shardings(as_gpt(cfg), mesh)


def make_trainer(cfg, trainer, weights_tree, mesh):
    return GPT.sut.make_trainer(as_gpt(cfg), trainer, weights_tree, mesh)


def make_engine(cfg, engine_args, weights_tree):
    return GPT.sut.make_engine(as_gpt(cfg), engine_args, weights_tree)


trainer_moment1 = GPT.sut.trainer_moment1
