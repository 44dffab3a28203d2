"""Placement of JAX's persistent compilation cache (launch/compile_cache)."""
from pathlib import Path

from repro.launch.compile_cache import ENV_VAR, compile_cache_dir


def test_env_var_wins():
    assert compile_cache_dir({ENV_VAR: "/data/jax-cache"}) == \
        "/data/jax-cache"


def test_default_is_one_fixed_dir_inside_the_checkout():
    first, second = compile_cache_dir({}), compile_cache_dir({})
    assert first == second
    repo = Path(__file__).resolve().parents[1]
    assert Path(first) == repo / ".jax_cache"


def test_empty_env_var_falls_back_to_the_fixed_dir():
    assert compile_cache_dir({ENV_VAR: ""}) == compile_cache_dir({})
