"""The system under test: the jitted round step of ``core/fedavg``, built from
the program's own pieces as ``launch/train.main`` builds it, for one
configuration file and one traffic file, with the configuration's model
family module (``bench/reference/<reference>.py``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import inspect
import math
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from bench import inputs

#: configuration-file key -> the program's ModelCfg field it must equal, for
#: the keys every language model's configuration has; the family module
#: checks the rest
MODEL_FIELDS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab",
    "tie_word_embeddings": "tie_embeddings",
}


class Program(NamedTuple):
    args: Any           # train.parse_args of the traffic's flags
    model: Any          # the ModelCfg that runs
    bundle: Any
    fed: Any            # FedConfig
    comp: Any           # the compression pipeline
    sampler: Any
    step: Any           # jax.jit(round_step, donate_argnums=(0,))
    shapes: Any         # eval_shape of the weights
    layout: tuple       # (groups, clients, E, micro)
    seq: int
    plan: Any           # the fedavg.CohortPlan the round runs
    family: ModuleType  # bench/reference/<reference>.py

    @property
    def clients(self) -> int:
        return self.layout[0] * self.layout[1]

    @property
    def tokens_per_round(self) -> int:
        return math.prod(self.layout) * self.seq


def spec_args(spec: str) -> dict:
    """'zsign_packed(z=1,sigma=0.01)' -> {'z': '1', 'sigma': '0.01'}."""
    return dict(re.findall(r"(\w+)\s*=\s*([^,()]+)", spec))


def train_argv(config: dict, traffic: dict) -> list:
    argv = ["--arch", config["program"]["registry"]]
    for flag, value in traffic["train_args"].items():
        argv += [f"--{flag}", str(value)]
    return argv


def load_family(config: dict, root: Path) -> ModuleType:
    """The model family module the configuration names with its
    ``"reference"`` key: ``bench/reference/<reference>.py``."""
    name, where = config.get("reference"), root / "bench" / "reference"
    if not (isinstance(name, str) and name.isidentifier()):
        raise ValueError(f"configuration {config['name']!r} names no model "
                         f"family module: its \"reference\" is {name!r}, "
                         f"where {where}/<reference>.py is looked for")
    path = where / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"configuration {config['name']!r} names the model "
                         f"family {name!r}: looked for {path}, not found")
    spec = importlib.util.spec_from_file_location(f"bench_family_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_model(model, config: dict, family: ModuleType) -> None:
    """The configuration file has to describe what runs: the keys every
    language model has here, the rest in the family module's ``check``."""
    from repro.models import layers
    wrong = {k: (config[k], getattr(model, f)) for k, f in MODEL_FIELDS.items()
             if config[k] != getattr(model, f)}
    if jnp.dtype(model.dtype).name != config["torch_dtype"]:
        wrong["torch_dtype"] = (config["torch_dtype"],
                                jnp.dtype(model.dtype).name)
    facts = {"rms_norm_eps":
             inspect.signature(layers.rms_norm).parameters["eps"].default}
    wrong.update(family.check(config, model, facts))
    if wrong:
        raise ValueError(f"configuration {config['name']!r} does not describe "
                         f"the model that runs, checked with "
                         f"{family.__file__} (file, program): {wrong}")


def build(config: dict, traffic: dict, root: Path) -> Program:
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.configs.common import get_arch
    from repro.core import fedavg
    from repro.launch import train
    from repro.models.api import build_model

    family = load_family(config, root)
    args = train.parse_args(train_argv(config, traffic))
    model = dataclasses.replace(get_arch(args.arch).model,
                                **config["program"].get("model", {}))
    check_model(model, config, family)
    bundle = build_model(model)
    comp = train.build_compressor(args)
    fed = train.fed_config(args)
    ctx = fedavg.RoundContext(
        agg_backend=args.agg_backend, encode_backend=args.encode_backend,
        weights_are_mask=True, dynamic_sigma=args.plateau,
        cohort=args.cohort, adversary=args.adversary,
        round_mode=args.round_mode, latency=args.latency)
    step = fedavg.build_round_step(bundle.loss_fn, comp, fed, ctx)
    step = jax.jit(step, donate_argnums=(0,) if ctx.donate_state else ())
    shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    n = sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes))
    plan = fedavg.resolve_cohort(args.cohort, args.groups * args.clients, n)
    return Program(args=args, model=model, bundle=bundle, fed=fed, comp=comp,
                   sampler=train.make_sampler(args), step=step, shapes=shapes,
                   layout=(args.groups, args.clients, args.local_steps,
                           args.micro_batch),
                   seq=args.seq_len, plan=plan, family=family)


def init_state(prog: Program, params, seed: int):
    from repro.core import fedavg
    return fedavg.init_server_state(params, prog.fed, prog.comp,
                                    inputs.server_key(seed),
                                    sigma0=prog.args.sigma)


def batch(prog: Program, key, t: int) -> dict:
    return {"tokens": inputs.tokens(key, t, prog.layout + (prog.seq,),
                                    prog.model.vocab)}


def mask(prog: Program):
    return jnp.asarray(prog.sampler.mask(prog.layout[:2]))
