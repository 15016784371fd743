"""System-level coherence: registry, cells, public imports, mesh factory."""

import os

import jax
import numpy as np
import pytest

import repro.configs as configs
from repro.configs.base import ALL_SHAPES


def test_all_archs_registered():
    assert len(configs.ARCH_IDS) == 10
    for a in configs.ARCH_IDS:
        cfg = configs.get_config(a)
        assert cfg.name == a
        assert cfg.source, f"{a} missing provenance"
        # layer arithmetic closes
        assert len(cfg.prefix) + len(cfg.pattern) * cfg.n_groups == cfg.n_layers


def test_cells_enumeration():
    live = configs.cells()
    everything = configs.cells(include_skipped=True)
    assert len(everything) == 40  # 10 archs x 4 shapes
    assert len(live) == 34        # 6 long_500k skips (pure full-attention)
    skipped = {(a, s.name) for a, s, l in everything if not l}
    assert all(s == "long_500k" for _, s in skipped)
    assert {a for a, _ in skipped} == {
        "phi4-mini-3.8b", "qwen3-32b", "deepseek-v2-236b", "olmoe-1b-7b",
        "paligemma-3b", "musicgen-medium"}


def test_shapes_match_brief():
    by = {s.name: s for s in ALL_SHAPES}
    assert (by["train_4k"].seq_len, by["train_4k"].global_batch) == (4096, 256)
    assert (by["prefill_32k"].seq_len, by["prefill_32k"].global_batch) == (32768, 32)
    assert (by["decode_32k"].seq_len, by["decode_32k"].global_batch) == (32768, 128)
    assert (by["long_500k"].seq_len, by["long_500k"].global_batch) == (524288, 1)
    assert by["decode_32k"].kind == "decode" and by["long_500k"].kind == "decode"


def test_public_imports():
    import repro.core.collectives
    import repro.core.kvagg
    import repro.core.planner
    import repro.core.reduction_model
    import repro.core.tree
    import repro.checkpoint.manager
    import repro.data.pipeline
    import repro.kernels.ops
    import repro.kernels.ref
    import repro.launch.hlo_analysis
    import repro.launch.hlo_cost
    import repro.launch.mesh
    import repro.launch.profiles
    import repro.models.model
    import repro.optim.adamw
    import repro.runtime.fault_tolerance
    import repro.train.step  # noqa: F401


def test_mesh_factory_is_lazy():
    """Importing mesh.py must not touch device state; constants defined."""
    from repro.launch import mesh as m

    assert callable(m.make_production_mesh)
    assert m.PEAK_FLOPS_BF16 == 197e12
    assert m.HBM_BW == 819e9


def test_make_mesh_axes_are_auto():
    from jax.sharding import AxisType

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(from_env, tmp_path, monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache sits
    at the checkout's own gitignored .jax_cache."""
    from conftest import REPO
    from repro.launch.compile_cache import enable_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        where = enable_compile_cache()
        if from_env:
            assert where == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            assert where == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == where
            with open(os.path.join(REPO, ".gitignore")) as f:
                assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_vocab_shards_over_model_axis():
    for a in configs.ARCH_IDS:
        cfg = configs.get_config(a)
        assert cfg.padded_vocab % 16 == 0  # model axis of the production mesh
        assert cfg.padded_vocab >= cfg.vocab_size
