"""Seconds JAX spent building programs over the whole run, set-up and
window: compiles and loads from the persistent cache, as the program's
``jax.compile_s`` histogram sums them.  Nothing where the program keeps
no such histogram."""


def read(w):
    from repro.core import dataplane  # noqa: F401  (installs the listener)
    from repro.obs import metrics

    if not hasattr(metrics, "record_compile"):
        return None
    return sum((h["sum"] for _, h in metrics.get_registry().find(
        "jax.compile_s")), 0.0)
