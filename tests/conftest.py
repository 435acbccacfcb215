"""Test configuration.

NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
single real CPU device.  Multi-device tests run in subprocesses (see
tests/dist_cases/) with --xla_force_host_platform_device_count set there.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "dist: multi-device subprocess tests (8 host devices)")


def run_dist_case(script_name: str, n_devices: int = 8,
                  timeout: int = 900) -> str:
    """Run a tests/dist_cases/<script> in a subprocess with N host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    path = os.path.join(REPO, "tests", "dist_cases", script_name)
    proc = subprocess.run([sys.executable, path], capture_output=True,
                          text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise AssertionError(
            f"{script_name} failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")
    return proc.stdout


@pytest.fixture(scope="session")
def dist_runner():
    return run_dist_case


def _sliced_layers(body, x, blocks, cache, block_tbl=None, *, scan=True):
    """The model's layer loop with every cache leaf, paged K/V pools
    included, sliced out of the stack per layer and written back, the
    block table as given: the reference that
    ``transformer._layers_in_place`` must match bitwise."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    n = jax.tree.leaves(blocks)[0].shape[0]
    if scan:
        def step(carry, inp):
            x, cache = carry
            i, bp = inp
            with jax.named_scope("kv_carry"):
                cl = jax.tree.map(
                    lambda t: lax.dynamic_index_in_dim(t, i, keepdims=False),
                    cache)
            x, nc = body(x, bp, cl, block_tbl, 0)
            with jax.named_scope("kv_carry"):
                cache = jax.tree.map(
                    lambda t, u: lax.dynamic_update_index_in_dim(
                        t, u.astype(t.dtype), i, 0), cache, nc)
            return (x, cache), None

        (x, cache), _ = lax.scan(step, (x, cache),
                                 (jnp.arange(n, dtype=jnp.int32), blocks))
        return x, cache
    ncs = []
    for i in range(n):
        x, nc = body(x, jax.tree.map(lambda t: t[i], blocks),
                     jax.tree.map(lambda t: t[i], cache), block_tbl, 0)
        ncs.append(nc)
    return x, jax.tree.map(lambda *xs: jnp.stack(xs), *ncs)


@pytest.fixture
def sliced_layers(monkeypatch):
    """A context manager inside which the model runs its layers through
    the sliced reference loop (``_sliced_layers``)."""
    import contextlib

    from repro.models import transformer

    @contextlib.contextmanager
    def patched():
        with monkeypatch.context() as m:
            m.setattr(transformer, "_layers_in_place", _sliced_layers)
            yield

    return patched
