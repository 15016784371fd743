"""Each cell's job loop and comparison at a tiny size on the CPU, through
the harness's own internals with the Pallas kernel interpreted: sound runs
come out correct, and the control and each planted fault come out not
correct.  Also: a configuration or metric added as files alone is picked
up, and the command refuses to run without a TPU."""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from chipbench import compare, faults, harness
from chipbench.jobs import JobResult

BASE = pathlib.Path(__file__).resolve().parents[1]
REPO = BASE.parent
TINY_TREE = {"pods": 2, "tors_per_pod": 2, "hosts_per_tor": 2,
             "table_pairs": 64}
# 10 words in 4,096 records: counts past 256, where bfloat16 stops counting
# by ones, so the control fails at this size as it does at the cell's
TINY = {"hibench-wordcount": {"records": 1 << 12, "vocabulary": 10,
                              "expected_records": 1 << 12,
                              "key_variety": 10},
        "tpch-q18": {"orders_per_scale": 2000, "expected_records": 8000,
                     "key_variety": 2000}}
CELLS = ["hibench-wordcount.batch", "tpch-q18.stream"]
SEED = 2**31 + 99


def tiny_cell(name: str, root=REPO, base=None) -> harness.Cell:
    cell = harness.load_cell(root, name, base)
    cell.config.update(TINY.get(cell.config["name"], {}), fat_tree=TINY_TREE)
    if "batch_records" in cell.traffic:
        cell.traffic["batch_records"] = 256
    return cell


def run_tiny(cell, seconds=0.3, trace=False):
    return harness.run_cell(cell, SEED, seconds, trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            interpret=True)


@pytest.fixture(autouse=True)
def _fresh_jit_caches():
    # a planted fault patches functions that jitted callers may have traced
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("name", CELLS)
def test_tiny_cell_is_correct(name):
    r = run_tiny(tiny_cell(name))
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    want = {m["name"] for m in harness.load_cell(REPO, name).metrics(
        "end_to_end")}
    assert set(r["metrics"]) == want
    assert 0 < r["metrics"]["reduction_pct"]["value"] < 100
    assert list(r)[-1] == "checks"
    assert all(c["value"] == 0 for c in r["checks"].values())
    assert r["device"]["count"] == len(jax.devices())


def test_wrong_total_fails_the_comparison():
    keys = np.array([3, 1, 7, -1], np.int32)
    vals = np.array([2.0, 5.0, 1.0, 0.0], np.float32)
    ids, tot = np.array([1, 3, 7]), np.array([5.0, 2.0, 1.0])
    assert compare.compare(keys, vals, ids, tot, 3, 3) == {
        "keys_wrong": 0, "total_err_max": 0.0, "leaf_gap": 0}
    vals[2] += 1.0
    bad = compare.compare(keys, vals, ids, tot, 3, 3)
    assert bad == {"keys_wrong": 1, "total_err_max": 1.0, "leaf_gap": 0}
    assert not compare.passes(bad)
    # a key repeated, its total split over two slots: the sum is right,
    # the result is not
    split = compare.compare(np.array([3, 1, 7, 1], np.int32),
                            np.array([2.0, 4.0, 1.0, 1.0]), ids, tot, 4, 4)
    assert split["keys_wrong"] == 1 and split["total_err_max"] == 0.0
    # a key missing and one the reference does not have
    miss = compare.compare(np.array([3, 1, 9], np.int32),
                           np.array([2.0, 5.0, 4.0]), ids, tot, 3, 3)
    assert miss["keys_wrong"] == 2 and miss["total_err_max"] == 4.0


def test_leaf_counter_off_the_records_sent_fails_the_comparison():
    keys = np.array([3, 1, 7], np.int32)
    vals = np.array([2.0, 5.0, 1.0], np.float32)
    ids, tot = np.array([1, 3, 7]), np.array([5.0, 2.0, 1.0])
    for n_in, gap in ((4, 1), (2, 1), (3, 0)):
        got = compare.compare(keys, vals, ids, tot, n_in, 3)
        assert got["leaf_gap"] == gap
        assert compare.passes(got) == (gap == 0)


def test_end_to_end_metrics_count_the_records_sent():
    cell = harness.load_cell(REPO, CELLS[0])
    # the program counts padding as records: the harness's count stands
    job = JobResult(None, None, 1500, 100, (1500,), (100,), 1000)
    w = harness.Window(cell, [job, job], 2.0, 1.0, 1)
    read = {m: cell.module("metrics", m).read(w)
            for m in ("records_per_s", "reduction_pct")}
    assert read == {"records_per_s": 1000.0, "reduction_pct": 90.0}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", faults.FAULTS)
def test_planted_fault_is_not_correct(monkeypatch, name, fault):
    cell = tiny_cell(name)
    entry = cell.module("drivers", cell.traffic["driver"]).ENTRY
    faults.plant(fault, entry, monkeypatch.setattr)
    r = run_tiny(cell)
    assert not r["correct"] and r["failed"] == r["attempted"] >= 1
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("name", CELLS)
def test_bfloat16_control_is_not_correct(monkeypatch, name):
    cell = tiny_cell(name)
    ref = cell.module("references", cell.config["reference"])
    driver = cell.module("drivers", cell.traffic["driver"])

    def control_prepare(cell, keys, values, *, interpret=False):
        keys, values = np.asarray(keys), np.asarray(values)

        def job():
            ids, tot = ref.control(keys, values)
            return JobResult(ids.astype(np.int32), tot.astype(np.float32),
                             keys.shape[0], ids.shape[0], (keys.shape[0],),
                             (ids.shape[0],), keys.shape[0])
        return job

    monkeypatch.setattr(driver, "prepare", control_prepare)
    r = run_tiny(cell)
    assert not r["correct"]
    assert r["checks"]["total_err_max"]["value"] > 0
    assert r["checks"]["keys_wrong"]["value"] > 0


def test_files_alone_add_a_configuration_and_a_metric(tmp_path):
    base = tmp_path / "chipbench"
    shutil.copytree(BASE, base, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", ".jax_cache"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    conf = json.loads((BASE / "configs" / "hibench-wordcount.json").read_text())
    conf.update(TINY["hibench-wordcount"], name="wordcount-small", skew=1.2)
    (base / "configs" / "wordcount-small.json").write_text(json.dumps(conf))
    (base / "metrics" / "jobs_done.py").write_text(
        "def read(w):\n    return len(w.jobs)\n")
    bench["configs"].append({"name": "wordcount-small", "source": "test",
                             "file": "chipbench/configs/wordcount-small.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "wordcount-small.stream",
                               "config": "wordcount-small",
                               "traffic": "stream", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "jobs_done", "unit": "jobs",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["wordcount-small.stream"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = tiny_cell("wordcount-small.stream", root=tmp_path)
    assert cell.base == base and cell.config["skew"] == 1.2
    r = run_tiny(cell)
    assert r["correct"]
    assert r["metrics"]["jobs_done"]["value"] == r["attempted"]
    assert "batch_ingest_p95_ms" not in r["metrics"]  # listed for another cell


def _run_command(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    proc = _run_command(REPO)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "TPU" in proc.stderr


def test_command_refuses_without_the_program(tmp_path):
    shutil.copytree(BASE, tmp_path / "chipbench", ignore=shutil.ignore_patterns(
        "__pycache__", ".jax_cache"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    proc = _run_command(tmp_path)
    assert proc.returncode != 0 and proc.stdout == ""


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
