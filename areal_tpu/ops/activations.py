"""Activation functions (counterpart of ``realhf/impl/model/modules/activations.py``)."""

import jax
import jax.numpy as jnp

ACT2FN = {
    "silu": jax.nn.silu,
    # jax.nn.gelu defaults to the tanh approximation; HF "gelu" is exact erf
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "gelu_new": lambda x: jax.nn.gelu(x, approximate=True),
    "gelu_pytorch_tanh": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
    # squared ReLU (``nemotron_h``'s experts)
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    "tanh": jnp.tanh,
}
