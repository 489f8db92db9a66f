"""The few things the drivers take from the program to build the system
under test from a configuration file."""

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp


def model_config(arch: Dict, overrides: Dict):
    """The program's ``ModelConfig`` for a configuration file, read the way
    a checkpoint's ``config.json`` is, plus the mix's ``model_overrides``."""
    from areal_tpu.models import hf as hf_conv

    cfg = hf_conv.family_for_model_type(arch["model_type"]).config_from_hf(arch)
    return dataclasses.replace(cfg, dtype=arch["serving_dtype"], **overrides)


def weight_shapes(cfg, dtype):
    """The weight tree the program's init would build, as shapes only."""
    from areal_tpu.models import transformer as tfm

    return jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.key(0), dtype=jnp.dtype(dtype))
    )
