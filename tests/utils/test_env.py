"""utils/env.py: the repo's one peak-FLOP/s table and the placeable persistent
compilation cache."""

import math
import os
import subprocess
import sys

import pytest

from paddlenlp_tpu.observability import goodput
from paddlenlp_tpu.utils import env

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class TestPeakTable:
    def test_known_kinds(self):
        # device_kind exactly as the chip reports it ("TPU v5 lite" is a v5e)
        assert env.device_peak_flops("TPU v5 lite") == 197e12
        assert env.device_peak_flops("TPU v4") == 275e12

    def test_cpu_is_nan(self):
        assert math.isnan(env.device_peak_flops("cpu"))
        assert math.isnan(env.device_peak_flops())  # the tests' own device

    @pytest.mark.parametrize("kind", ["NVIDIA H100", "TPU v9", "TPU v5e", "tpu v5 lite"])
    def test_unknown_accelerator_raises(self, kind):
        """No default for a device the table does not list — not even one
        that looks like a TPU, and no substring guessing."""
        with pytest.raises(ValueError, match="PEAK_FLOPS_BY_DEVICE_KIND"):
            env.device_peak_flops(kind)

    def test_one_table(self):
        assert goodput.device_peak_flops is env.device_peak_flops
        assert not hasattr(goodput, "_PEAK_FLOPS_BY_KIND")


PRINT_CACHE = (
    "import jax; from paddlenlp_tpu.utils.env import enable_compile_cache; "
    "print(enable_compile_cache()); print(jax.config.jax_compilation_cache_dir)")


def cache_dirs(tmp_path, env_dir):
    """(returned, what jax uses) from a fresh process started somewhere else."""
    child_env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    child_env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    if env_dir is not None:
        child_env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", PRINT_CACHE], cwd=tmp_path, env=child_env,
                         capture_output=True, text=True, timeout=120, check=True)
    return out.stdout.split()[-2:]


class TestCompileCache:
    def test_placed_from_outside(self, tmp_path):
        """JAX_COMPILATION_CACHE_DIR set: jax uses it, the code sets no other."""
        placed = str(tmp_path / "placed")
        assert cache_dirs(tmp_path, placed) == [placed, placed]

    def test_fixed_path_in_the_checkout(self, tmp_path):
        """Unset: the fixed in-checkout path — the same from two processes
        started in two directories (the path is part of the cache key)."""
        other = tmp_path / "elsewhere"
        other.mkdir()
        fixed = os.path.join(REPO, ".jax_cache")
        assert cache_dirs(tmp_path, None) == [fixed, fixed]
        assert cache_dirs(other, None) == [fixed, fixed]
        assert env.DEFAULT_COMPILE_CACHE_DIR == fixed

    def test_tests_run_with_the_cache_off(self):
        """Whatever directory an entry point driven in process has placed,
        nothing is cached in a test session."""
        import jax

        assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
        assert jax.config.jax_enable_compilation_cache is False
