"""Where the persistent compile cache lives: ``JAX_COMPILATION_CACHE_DIR``
when it is set, and otherwise the checkout's fixed ``.jax_cache``."""
import os
import subprocess
import sys
import textwrap
import uuid

import pytest

from repro.launch import fleet as fleet_mod
from repro.launch.compile_cache import CACHE_DIR_ENV, default_cache_dir

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cached(path, name):
    """Cache entries of the jitted function ``name`` under ``path``."""
    if not os.path.isdir(path):
        return []
    return [f for f in os.listdir(path) if f.startswith(f"jit_{name}-")]


def _compile_in_child(env, name):
    """Compile a function no earlier process compiled, in a fresh process
    that sets up the cache the way every entry point does."""
    script = textwrap.dedent(f"""
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        import jax

        def {name}(x):
            return x * 3.0 + 1.0

        jax.jit({name})(2.0).block_until_ready()
        """)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr


def test_default_cache_is_the_checkouts_jax_cache():
    assert default_cache_dir() == os.path.join(ROOT, ".jax_cache")


@pytest.mark.parametrize("env_set", [True, False])
def test_compiles_land_only_in_the_chosen_cache(tmp_path, env_set):
    env = fleet_mod._worker_env()
    env.pop(CACHE_DIR_ENV, None)
    chosen, other = default_cache_dir(), str(tmp_path / "cache")
    if env_set:
        chosen, other = other, chosen
        env[CACHE_DIR_ENV] = chosen
    name = f"probe_{uuid.uuid4().hex}"
    _compile_in_child(env, name)
    assert _cached(chosen, name), f"{name} was not cached in {chosen}"
    assert not _cached(other, name)
