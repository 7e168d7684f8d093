"""Backend-derived runtime decisions: where the persistent compile cache
lives, and that Pallas interpret mode exists only on the CPU."""
import jax
import pytest

from repro import runtime
from repro.configs import get_config
from repro.kernels import ops
from repro.models import init_params
from repro.serving import PagedEngine


@pytest.fixture
def cache_dir_restored():
    """Each case sets the cache directory; put the previous one back so
    the rest of the suite never writes to the checkout's cache."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_uses_environment_dir(monkeypatch, tmp_path,
                                            cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_fixed_path_in_checkout(monkeypatch,
                                              cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = runtime.enable_compile_cache()
    assert runtime.enable_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
    checkout = runtime.COMPILE_CACHE_DIR.parent
    assert (checkout / "src" / "repro" / "runtime.py").is_file()
    assert first == str(checkout / ".jax_compile_cache")


@pytest.mark.parametrize("backend,asked,expected", [
    ("cpu", None, True), ("cpu", True, True), ("cpu", False, False),
    ("tpu", None, False), ("tpu", False, False), ("tpu", True, None),
])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, asked, expected):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if expected is None:
        with pytest.raises(ValueError, match="interpret"):
            ops.interpret_mode(asked)
    else:
        assert ops.interpret_mode(asked) is expected


@pytest.mark.parametrize("backend,kernels", [("cpu", False), ("tpu", True)])
def test_paged_engine_selects_kernels_on_tpu(monkeypatch, backend, kernels):
    """The production engine runs the kernels where they compile; an
    explicit runtime (the jnp reference oracle) still wins."""
    cfg = get_config("dialogpt-medium").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    eng = PagedEngine(cfg, params, max_batch=1, capacity=32, block_size=8)
    assert eng.rt.use_pallas is kernels
    ref = PagedEngine(cfg, params, max_batch=1, capacity=32, block_size=8,
                      rt=runtime.Runtime(use_pallas=False))
    assert ref.rt.use_pallas is False
