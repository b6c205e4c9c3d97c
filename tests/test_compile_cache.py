"""Placement of JAX's persistent compilation cache by the entry points
(``launch.compile_cache.use_compile_cache``): the environment variable
wins when set, otherwise a fixed directory in the checkout, resolved from
the helper's own file. Each case runs in a fresh interpreter, since the
helper changes process-wide JAX config, on a copy of the helper placed in a
temporary checkout, and compiles one function to show where entries land."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HELPER = ROOT / "src" / "repro" / "launch" / "compile_cache.py"
PROBE = """
import importlib.util, sys
import jax, jax.numpy as jnp
spec = importlib.util.spec_from_file_location("compile_cache", sys.argv[1])
cc = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cc)
print(cc.use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
# cache every executable, however small, so one compile leaves an entry
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(4)).block_until_ready()
"""


def _probe(tmp_path, env_dir=None):
    """Copy the helper into ``<tmp>/checkout/src/repro/launch/`` (its own
    location decides the default), run one compile, return the checkout
    and the two directories the probe printed."""
    checkout = tmp_path / "checkout"
    helper = checkout / "src" / "repro" / "launch" / "compile_cache.py"
    helper.parent.mkdir(parents=True)
    shutil.copy(HELPER, helper)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", PROBE, str(helper)],
                         env=env, cwd=tmp_path, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    returned, configured = out.stdout.split()
    return checkout, returned, configured


def _entries(d: Path):
    return sorted(p.name for p in d.iterdir()) if d.exists() else []


def test_cache_defaults_to_checkout_dir(tmp_path):
    checkout, returned, configured = _probe(tmp_path)
    cache = checkout / ".jax_cache"
    assert returned == configured == str(cache)
    assert _entries(cache)
    assert _entries(tmp_path) == ["checkout"]


def test_cache_env_var_wins(tmp_path):
    env_dir = tmp_path / "env_cache"
    checkout, returned, configured = _probe(tmp_path, env_dir)
    assert returned == configured == str(env_dir)
    assert _entries(env_dir)
    assert not (checkout / ".jax_cache").exists()
