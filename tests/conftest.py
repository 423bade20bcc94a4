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
# Tier-1 checks the programs' logic, their lowering and their HLO, not
# XLA:CPU's code generator: compile at backend optimisation level 0 with
# LLVM's expensive passes off.  They are a third of the suite's compile
# CPU for code that then runs for milliseconds; the HLO passes, the SPMD
# partitioner and every lowered text are what they were; and two
# spellings of one arithmetic then give the same bits, where the
# optimiser contracts and re-associates each program its own way.
jax.config.update("jax_disable_most_optimizations", True)

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


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described ``v5e:2x2``: what the
    ``compiles_for_chip`` files compile for, without a chip."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])
