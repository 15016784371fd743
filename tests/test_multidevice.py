"""Multi-device integration tests (subprocess drivers, 8 fake CPU devices).

These exercise the actual SwitchAgg dataplane on a (pod, data, model) mesh:
collective equivalence, compressed exchange exactness, the word-count KV
tree, end-to-end training in every exchange mode, checkpoint/elastic
restart, and TP+cache-sharded serving.
"""

import pytest

from conftest import run_driver


@pytest.mark.integration
def test_collectives_dataplane():
    out = run_driver("collectives_driver")
    assert "ALL OK" in out


@pytest.mark.integration
def test_train_e2e_modes_checkpoint_elastic():
    out = run_driver("train_e2e_driver", timeout=600)
    assert "ALL OK" in out


@pytest.mark.integration
def test_sharded_serving():
    out = run_driver("serve_driver", timeout=600)
    assert "ALL OK" in out


@pytest.mark.integration
def test_compressed_exchange_training():
    out = run_driver("compressed_driver", timeout=600)
    assert "lossless limit OK" in out
    assert "ALL OK" in out


@pytest.mark.integration
def test_gpipe_pipeline():
    out = run_driver("pipeline_driver", timeout=420)
    assert "pipeline == sequential OK" in out
    assert "ALL OK" in out
