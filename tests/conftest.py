"""Test configuration: force an 8-device virtual CPU mesh.

Analog of the reference's testing gap fix (SURVEY §4): JAX's CPU backend
with xla_force_host_platform_device_count gives a free "fake TPU slice" so
every functional + sharding test runs devicelessly.
"""

import os

# Must run before jax initializes a backend: the tests run on the virtual
# CPU mesh whatever platform the environment names.
os.environ.pop("JAX_PLATFORMS", None)
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

# `transformers` (the two tests against Hugging Face's Llama) imports
# TensorFlow and Flax where it finds them, for nobody here: 15 s an import
# with them, 9 without (ROADMAP D10)
os.environ.setdefault("USE_TF", "0")
os.environ.setdefault("USE_FLAX", "0")

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
