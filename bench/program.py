"""What the benchmark takes from the program: its public entry points and the
model configuration they are given.  Nothing else under ``bench/`` imports
``repro``, and the references import nothing of it.

A configuration file names its program configuration in a ``program``
block: ``{"config": <name in repro.configs.registry>, "replace": {<field>:
value, <sub>.<field>: value}}``.  The registry's configuration is taken as
it is, each replacement applied (a dotted key reaches a nested
configuration such as ``attention`` or ``moe``), and the file's
``compute_dtype`` and ``param_dtype`` set its numerics.  So a configuration
of any architecture the registry has is added as a file, with no code here.
"""
from __future__ import annotations

import dataclasses

from repro.cluster import SliceSpec, Supercomputer  # noqa: F401
from repro.configs import registry
from repro.configs.base import (ModelConfig, OptimizerConfig,  # noqa: F401
                                ParallelConfig, RunConfig, ShapeConfig)
from repro.optim.adam import init as optimizer_init  # noqa: F401
from repro.train.trainer import TrainerState  # noqa: F401

# one block of the modelled machine; the program runs it on the chips that
# JAX gives this process
SLICE = (4, 4, 4)


def _replace(obj, path, value):
    head, *rest = path
    if rest:
        value = _replace(getattr(obj, head), rest, value)
    return dataclasses.replace(obj, **{head: value})


def model_config(c: dict) -> ModelConfig:
    """The program's ``ModelConfig`` that configuration file ``c`` names.
    An unknown registry name raises ``KeyError``."""
    p = c["program"]
    cfg = registry.get_config(p["config"])
    for key, value in p["replace"].items():
        cfg = _replace(cfg, key.split("."), value)
    return cfg.replace(dtype=c["compute_dtype"], param_dtype=c["param_dtype"])
