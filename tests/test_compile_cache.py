"""The persistent compile cache lands where ``launch.compile_cache`` says:
in ``$JAX_COMPILATION_CACHE_DIR`` when that is set (and nowhere else),
else in ``<checkout>/.jax_cache``.

Each case runs in a fresh process (JAX reads its cache config once) on a
copy of the module placed in a stand-in checkout under ``tmp_path``, so the
default directory is that checkout's and the real one is never written.
"""
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODULE = os.path.join(REPO, "src", "repro", "launch", "compile_cache.py")

SCRIPT = """
import importlib.util, sys
spec = importlib.util.spec_from_file_location("compile_cache", sys.argv[1])
cc = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cc)
import jax, jax.numpy as jnp
print(cc.enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.tanh(x) * 3 + 1)(jnp.ones(16)).block_until_ready()
"""


def _files(path):
    if not os.path.isdir(path):
        return []
    return [f for _, _, fs in os.walk(path) for f in fs]


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "default"])
def test_compile_cache_dir(tmp_path, from_env):
    checkout = tmp_path / "checkout"
    mod = checkout / "src" / "repro" / "launch" / "compile_cache.py"
    mod.parent.mkdir(parents=True)
    shutil.copy(MODULE, mod)
    default = str(checkout / ".jax_cache")
    outside = str(tmp_path / "outside")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = outside
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(mod)],
                          capture_output=True, text=True, env=env,
                          cwd=str(tmp_path), timeout=120)
    assert proc.returncode == 0, proc.stderr
    returned, configured = proc.stdout.split()[:2]
    want, other = (outside, default) if from_env else (default, outside)
    assert returned == configured == want
    assert _files(want), f"nothing cached in {want}"
    assert not _files(other), f"cache entries leaked into {other}"
