"""Entry-point set-up (launch/runtime.py) and the no-silent-fallback rules:
the compile cache goes where JAX_COMPILATION_CACHE_DIR says or to the fixed
in-repo path, a chip-path process that finds no TPU fails, kernels run
interpreted only on the CPU backend, and a worker spawned for a platform
it did not come up on refuses its init with a typed error."""
import jax
import pytest

from repro.kernels import ops
from repro.launch import runtime
from repro.serving.replica import ProcessReplica

from conftest import TINY_CFGS


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after a test sets it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_var_wins_and_nothing_is_set(monkeypatch,
                                                       cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert runtime.setup_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch,
                                                   cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = runtime.setup_compile_cache()
    assert got == str(runtime.REPO_CACHE)
    assert runtime.REPO_CACHE.name == ".jax_cache"
    assert (runtime.REPO_CACHE.parent / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == got
    assert runtime.setup_compile_cache() == got     # stable across calls


def test_require_platform_refuses_the_cpu():
    with pytest.raises(runtime.PlatformError, match="'tpu'"):
        runtime.require_platform("tpu")
    assert runtime.require_platform("cpu").platform == "cpu"


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False)])
def test_kernels_interpret_only_on_the_cpu_backend(monkeypatch, backend,
                                                   want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops._interpret_default() is want


def test_kernels_refuse_other_backends(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops._interpret_default()


def test_worker_on_the_wrong_platform_fails_init_typed():
    """A worker spawned for the chip that comes up on the CPU must refuse
    to serve: its init RPC answers a typed PlatformError, the stub marks
    the replica failed and reaps the process."""
    with pytest.raises(runtime.PlatformError, match="expected a 'tpu'"):
        ProcessReplica(TINY_CFGS["dense"], slots=2, max_seq=16,
                       platform="tpu")


def test_worker_on_the_named_platform_serves():
    rep = ProcessReplica(TINY_CFGS["dense"], slots=2, max_seq=16,
                         platform="cpu")
    try:
        assert not rep.failed
    finally:
        rep.close()
