"""Test harness: 8 virtual CPU devices so every multi-chip sharding path runs
without TPU hardware (SURVEY.md §4: the reference's `TestMultipleGpus` local-subprocess
simulator maps to XLA's forced host platform device count)."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
# tests never read or write a persistent compilation cache: an inherited
# JAX_COMPILATION_CACHE_DIR must not carry compiled programs between runs
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)
# ... and the master switch is off, because tests drive entry points in
# process (run_pretrain.main, ServingServer.run) and those place the cache
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs
