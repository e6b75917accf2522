"""Placement of JAX's persistent compilation cache (repro.xla_cache)."""
from pathlib import Path

from repro import xla_cache


def test_environment_variable_wins():
    path, from_env = xla_cache.resolve_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"})
    assert (path, from_env) == ("/somewhere/else", True)


def test_fallback_is_fixed_and_inside_the_checkout():
    checkout = Path(xla_cache.__file__).resolve().parents[2]
    first, from_env = xla_cache.resolve_cache_dir({})
    again, _ = xla_cache.resolve_cache_dir({"JAX_COMPILATION_CACHE_DIR": ""})
    assert not from_env
    assert first == again == str(checkout / ".jax_cache")
    assert (checkout / "src" / "repro" / "xla_cache.py").is_file()
