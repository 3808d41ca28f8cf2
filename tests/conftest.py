"""Test configuration.

Tests run on CPU with 8 virtual XLA host devices so the multi-chip sharding
paths (jax.sharding.Mesh over the doc axis) are exercised without TPU
hardware.  Both must be set before any backend initializes.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402


@pytest.fixture
def recompile_sentinel():
    """Per-jit-site XLA compile counter (jax_log_compiles-backed).

    Active for the whole test: run warmup, ``mark()``, run the steady-state
    rounds, then ``assert_steady_state()`` to require zero recompiles.
    """
    from peritext_tpu.observability import RecompileSentinel

    with RecompileSentinel() as sentinel:
        yield sentinel
