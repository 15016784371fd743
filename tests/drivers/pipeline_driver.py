"""GPipe pipeline schedule: correctness vs sequential execution. 8 devices."""

import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh
from repro.train.pipeline import bubble_fraction, make_gpipe_fn

assert jax.device_count() == 8


def check_pipeline_matches_sequential():
    n_stages, m, mb, d = 8, 16, 4, 32
    mesh = make_mesh((8,), ("stage",))
    rng = np.random.default_rng(0)
    # per-stage params: one linear + nonlinearity per stage
    w = jnp.asarray(rng.standard_normal((n_stages, d, d)).astype(np.float32) * 0.2)
    x = jnp.asarray(rng.standard_normal((m, mb, d)).astype(np.float32))

    def stage_fn(wi, xi):
        return jnp.tanh(xi @ wi)

    fn = make_gpipe_fn(mesh, "stage", n_stages, stage_fn)
    got = np.asarray(fn(w, x))

    want = np.asarray(x)
    for s in range(n_stages):
        want = np.tanh(want @ np.asarray(w[s]))
    np.testing.assert_allclose(got, want, atol=1e-5)
    print(f"pipeline == sequential OK (stages={n_stages}, micro={m}, "
          f"bubble={bubble_fraction(m, n_stages):.2f})")


def check_bubble_math():
    assert abs(bubble_fraction(16, 8) - 7 / 23) < 1e-12
    assert bubble_fraction(1000, 8) < 0.01  # M >> S amortizes the bubble
    print("bubble math OK")


if __name__ == "__main__":
    check_pipeline_matches_sequential()
    check_bubble_math()
    print("ALL OK")
