"""runtime.py: the compile cache is placed from outside; no silent CPU."""

import os
import subprocess
import sys

import jax
import pytest

from llm_instance_gateway_tpu import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore the suite's own cache placement after a test moved it."""
    env = os.environ.get(runtime.CACHE_ENV)
    cfg = jax.config.jax_compilation_cache_dir
    yield
    if env is None:
        os.environ.pop(runtime.CACHE_ENV, None)
    else:
        os.environ[runtime.CACHE_ENV] = env
    jax.config.update("jax_compilation_cache_dir", cfg)


class TestCompileCache:
    def test_variable_set_means_nothing_is_set_in_code(
            self, cache_config, monkeypatch, tmp_path):
        placed = str(tmp_path / "from-outside")
        monkeypatch.setenv(runtime.CACHE_ENV, placed)
        before = jax.config.jax_compilation_cache_dir
        assert runtime.configure_compile_cache() == placed
        assert runtime.configure_compile_cache(".jax_cache_tests") == placed
        assert jax.config.jax_compilation_cache_dir == before
        assert os.environ[runtime.CACHE_ENV] == placed

    def test_unset_means_the_fixed_path_in_the_checkout(
            self, cache_config, monkeypatch):
        monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert runtime.configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        # exported, so children land in the same place
        assert os.environ[runtime.CACHE_ENV] == want
        # fixed: no temp name, pid or time in it — twice gives the same
        monkeypatch.delenv(runtime.CACHE_ENV)
        assert runtime.configure_compile_cache() == want


class TestPlatformResolver:
    def test_cpu_not_named_exits_nonzero(self, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(SystemExit) as e:
            runtime.resolve_platform(None)
        assert e.value.code not in (0, None)
        assert "no accelerator" in str(e.value.code)

    def test_platform_cpu_by_flag_serves(self, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        info = runtime.resolve_platform("cpu")
        assert info.platform == "cpu" and info.count == len(jax.devices())

    def test_jax_platforms_naming_cpu_serves(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert runtime.resolve_platform(None).platform == "cpu"

    def test_chip_tools_refuse_the_cpu_even_by_name(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        with pytest.raises(SystemExit) as e:
            runtime.require_accelerator("tools/onchip_pallas_check.py")
        assert "onchip_pallas_check.py needs a TPU" in str(e.value.code)


def test_server_binary_exits_when_cpu_was_not_named():
    """The real entry point: no --platform, no JAX_PLATFORMS, no chip here
    -> JAX falls back to the CPU and the server refuses to serve on it."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "llm_instance_gateway_tpu.server.api_http",
         "--model", "llama3-tiny", "--port", "18949"],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no accelerator" in r.stderr
    assert "Running on" not in r.stdout + r.stderr
