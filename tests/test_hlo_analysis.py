"""Roofline math + collective accounting (the §Roofline source of truth)."""

import pytest

import repro.configs as configs
from repro.configs.base import shape_by_name
from repro.launch import hlo_analysis as ha
from repro.launch.mesh import (DCN_BW, HBM_BW, ICI_BW, ICI_LINKS,
                               PEAK_FLOPS_BF16, make_mesh)


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def test_roofline_terms_math():
    coll = ha.CollectiveStats(ici_bytes=2 * ICI_BW * ICI_LINKS,
                              dcn_bytes=3 * DCN_BW)
    r = ha.roofline_terms(hlo_flops=PEAK_FLOPS_BF16 * 0.5, hlo_bytes=HBM_BW * 4,
                          coll=coll, n_chips=256, model_flops=PEAK_FLOPS_BF16 * 0.25)
    assert r.compute_s == pytest.approx(0.5)
    assert r.memory_s == pytest.approx(4.0)
    assert r.collective_s == pytest.approx(5.0)
    assert r.dominant == "collective"
    assert r.useful_flops_ratio == pytest.approx(0.5)
    # fraction: model_flops is per-device, so ideal = 0.25 s; bound = 5 s
    assert r.roofline_fraction == pytest.approx(0.25 / 5.0)


def test_collectives_level_accounting():
    """all-reduce over (data,pod): ring bytes at data level, shard/16 at pod."""
    mesh512 = type("M", (), {})()  # fake mesh-like for sizes
    real = make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("pod", "data", "model")

        class devices:
            shape = (2, 16, 16)

    events = {"all-reduce|data,pod|32": 1024.0 * 1024.0}
    stats = ha.collectives_from_events(events, FakeMesh)
    mb = 1024.0 * 1024.0
    want_ici = 2 * 15 / 16 * mb            # data level on the full tensor
    want_dcn = 2 * 1 / 2 * (mb / 16)       # pod level on the 1/16 shard
    assert stats.ici_bytes == pytest.approx(want_ici)
    assert stats.dcn_bytes == pytest.approx(want_dcn)
    assert stats.by_op["all-reduce"] == pytest.approx(want_ici + want_dcn)


def test_collectives_all_gather_output_sized():
    class FakeMesh:
        axis_names = ("data", "model")

        class devices:
            shape = (16, 16)

    events = {"all-gather|model|16": 1e6}
    stats = ha.collectives_from_events(events, FakeMesh)
    assert stats.ici_bytes == pytest.approx(15 / 16 * 1e6)
    assert stats.dcn_bytes == 0.0


def test_model_flops_6nd():
    cfg = configs.get_config("phi4-mini-3.8b")
    n = cfg.active_param_count()
    train = ha.model_flops_for(cfg, shape_by_name("train_4k"))
    assert train == pytest.approx(6.0 * n * 256 * 4096)
    dec = ha.model_flops_for(cfg, shape_by_name("decode_32k"))
    assert dec == pytest.approx(2.0 * n * 128)
    # MoE uses ACTIVE params
    moe = configs.get_config("deepseek-v2-236b")
    t = ha.model_flops_for(moe, shape_by_name("train_4k"))
    assert t < 6.0 * moe.param_count() * 256 * 4096 * 0.2


def test_shape_bytes_parser():
    assert ha._shape_bytes("bf16[256,4096]") == 256 * 4096 * 2
    assert ha._shape_bytes("f32[10]") == 40
    assert ha._shape_bytes("pred[8]") == 8
    assert ha._shape_bytes("u8[3,3]") == 9
