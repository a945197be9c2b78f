"""Model families.  ``benchmark/families/<family>.py`` exposes
``build(config, traffic) -> Family`` for any configuration file whose
``family`` names it; the harness knows nothing else about a model."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict


@dataclasses.dataclass(frozen=True)
class Family:
    """What the harness needs from a model family.  The functions are
    traced under ``jax.jit`` by the harness; none touches a device when
    the family is built."""

    init: Callable[[Any], Any]              # PRNG key -> params
    loss_fn: Callable[..., Any]             # (params, *local_batch) -> loss; the system's
    optimizer: Any                          # optax transformation (unwrapped)
    make_batch: Callable[[Any, int], tuple]  # (key, samples) -> batch arrays
    unit: str                               # "tokens" | "images": <unit>_per_s_chip
    units_per_sample: int
    flops_per_unit: float                   # forward + backward, from shapes
    sample_size: int                        # samples in the reference check
    reference_loss: Callable[..., Any]      # (params, *batch) -> loss; benchmark/reference
    sample_env: Callable[[bool], Dict[str, str]]  # step has a Mosaic call -> env for the sample trace
    tolerances: Dict[str, Any]


def make_optimizer(spec: Dict[str, Any]):
    import optax

    spec = dict(spec)
    name = spec.pop("name")
    if name == "adamw":
        return optax.adamw(**spec)
    if name == "sgd":
        return optax.sgd(**spec)
    raise ValueError(f"unknown optimizer {name!r} in the configuration file")


def dtype_of(name: str):
    import jax.numpy as jnp

    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]
