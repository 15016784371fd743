"""Shared fixtures/helpers.

NOTE: no XLA_FLAGS here — unit/smoke tests see the 1 real CPU device.
Multi-device integration tests spawn subprocesses that set
``--xla_force_host_platform_device_count`` before importing jax
(tests/drivers/*.py).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
DRIVERS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "drivers")


def run_driver(name: str, *args: str, devices: int = 8, timeout: int = 420):
    """Run tests/drivers/<name>.py in a subprocess with N fake CPU devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(DRIVERS, name + ".py"), *args],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"driver {name} failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr[-4000:]}"
        )
    return proc.stdout


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def dict_aggregate(keys, values, op="sum"):
    """Brute-force python oracle: group values by key, reduce with ``op``.

    Covers every registered AggOp (repro.core.aggops) so cascade tests can
    compare any op's *finalized* output against first-principles semantics.
    """
    groups = {}
    for k, v in zip(np.asarray(keys).tolist(), np.asarray(values).tolist()):
        if k == -1:
            continue
        groups.setdefault(k, []).append(v)
    reducers = {
        "sum": np.sum,
        "max": np.max,
        "min": np.min,
        "count": len,
        "mean": np.mean,
        "logsumexp": lambda xs: float(
            np.logaddexp.reduce(np.asarray(xs, np.float64))),
    }
    f = reducers[op]
    return {k: f(vs) for k, vs in groups.items()}


def build_off_cpu(fn, **static):
    """A fresh jit of a jitted ``core.kvagg`` entry point's body that runs,
    here on the CPU, the group map kvagg lowers for a TPU
    (``_group_reduce_sorted``) where ``lax.platform_dependent`` would pick
    the CPU's search.

    The map is swapped only while the returned function runs, so its
    traces see it and no other trace does (the entry points' own jit
    caches keep the CPU branch).
    """
    import functools

    import jax

    from repro.core import kvagg

    jitted = jax.jit(functools.partial(fn.__wrapped__, **static))

    def call(*args, **kwargs):
        on_cpu = kvagg._group_reduce
        kvagg._group_reduce = kvagg._group_reduce_sorted
        try:
            return jitted(*args, **kwargs)
        finally:
            kvagg._group_reduce = on_cpu

    return call
