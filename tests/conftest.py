"""Test config: run on CPU with 8 virtual devices so multi-chip sharding
paths are exercised without TPU hardware (SURVEY environment notes)."""

import os

# JAX_PLATFORMS=cpu alone pins the CPU (and keeps the package's compile
# cache off: core/compile_cache.py); tests never touch a chip
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest

import paddle_tpu as pt

assert jax.devices()[0].platform == "cpu", jax.devices()


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs, scope and name counters."""
    main, startup = pt.Program(), pt.Program()
    prev_main = pt.core.program.switch_main_program(main)
    prev_startup = pt.core.program.switch_startup_program(startup)
    scope = pt.Scope()
    pt.core.scope._scope_stack.append(scope)
    pt.core.unique_name.reset()
    np.random.seed(0)
    yield
    pt.core.scope._scope_stack.pop()
    pt.core.program.switch_main_program(prev_main)
    pt.core.program.switch_startup_program(prev_startup)
