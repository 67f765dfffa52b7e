"""What the entry points decide before any model runs: which config an
``--arch`` builds, which kernel path the platform gets, where compiled
programs are cached, and that a mesh never quietly shrinks."""
import jax
import pytest

from repro.configs import get_config, launch_config
from repro.kernels import ops
from repro.launch import compile_cache
from repro.launch.mesh import make_debug_mesh


def test_launch_config_published_and_reduced():
    full = launch_config("qwen2.5-0.5b", vocab=54)
    assert full == get_config("qwen2.5-0.5b")
    assert (full.n_layers, full.d_model, full.n_heads, full.n_kv_heads,
            full.vocab_size) == (24, 896, 14, 2, 151936)
    small = launch_config("qwen2.5-0.5b-reduced", vocab=54)
    assert small.name == "qwen2.5-0.5b-reduced"
    assert (small.n_layers, small.vocab_size) == (2, 54)
    with pytest.raises(ValueError, match="cannot hold"):
        launch_config("qwen2.5-0.5b", vocab=200_000)
    with pytest.raises(KeyError):
        launch_config("no-such-arch", vocab=54)


@pytest.mark.parametrize("backend,mode", [("tpu", "pallas"),
                                          ("cpu", "reference"),
                                          ("gpu", "reference")])
def test_kernel_mode_follows_platform(monkeypatch, backend, mode):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops.kernel_mode() == mode
    assert ops._pallas_kwargs(None) == (
        None if mode == "reference" else {"interpret": False})
    # An explicit mode always wins over the platform's.
    assert ops._pallas_kwargs("pallas_interpret") == {"interpret": True}
    with pytest.raises(ValueError, match="kernel mode"):
        ops._pallas_kwargs("fast")


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """With JAX_COMPILATION_CACHE_DIR set nothing is set in code;
    without it the cache sits at one fixed path in the checkout."""
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        compile_cache.enable_compile_cache()
        got = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env_dir is None:
        assert got == str(compile_cache.CACHE_DIR)
        assert compile_cache.CACHE_DIR.parent.joinpath(
            "pyproject.toml").exists()
    else:
        assert got is None


def test_make_debug_mesh_raises_instead_of_shrinking():
    n = len(jax.devices())
    assert make_debug_mesh(data=n).shape["data"] == n
    with pytest.raises(ValueError, match=f"needs {n + 1} devices"):
        make_debug_mesh(data=n + 1)
    with pytest.raises(ValueError, match="needs"):
        make_debug_mesh(data=n, model=2)
