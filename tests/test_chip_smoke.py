"""The chip smoke refuses the CPU, and the entry points' compile cache lands
where it should. Each case runs a fresh interpreter: the platform and the
cache directory are fixed when JAX starts."""
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_cpu(where, tmp_path):
    """On the CPU, and in a directory that holds chip_smoke.py and nothing
    else of the repo, the script exits non-zero and prints no result."""
    script = os.path.join(REPO, "chip_smoke.py")
    if where == "alone":
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          env=_env(), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0, proc.stdout
    assert '"ok"' not in proc.stdout, proc.stdout
    if where == "repo":
        assert "no TPU" in proc.stderr, proc.stderr[-2000:]


_COMPILE_ONE = """
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from repro.utils.compile_cache import setup_compile_cache
print(setup_compile_cache({root!r}))
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_directory(env_set, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the cache is written there and
    nowhere else; without it, to <repo>/.jax_cache."""
    root, env_dir = tmp_path / "root", tmp_path / "env_cache"
    extra = {"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    if env_set:
        extra["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = _COMPILE_ONE.format(src=os.path.join(REPO, "src"), root=str(root))
    proc = subprocess.run([sys.executable, "-c", code], env=_env(**extra),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = env_dir if env_set else root / ".jax_cache"
    assert proc.stdout.split()[-1] == str(want)
    assert any(want.iterdir()), f"nothing cached in {want}"
    other = root / ".jax_cache" if env_set else env_dir
    assert not other.exists(), f"a cache was written to {other} too"
