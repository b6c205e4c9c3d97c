"""The harness finds a cell's configuration, traffic mix, limits, runner
and per-layer metrics by name, from files alone: a fixture adds a new
configuration, traffic mix, cell and metric as files, and a run of the
new cell picks all of them up without a change to the harness."""
import json
import shutil

import jax
import pytest

from bench import run as RUN
from bench import spec


@pytest.fixture()
def bench_with_new_cell(tmp_path):
    d = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "runners", "metrics"):
        shutil.copytree(spec.BENCH / sub, d / sub)
    cfg = json.loads((spec.BENCH / "configs" / "netflix-k100.json")
                     .read_text())
    cfg.update(name="tiny-k4", K=4, n_rows=1200, n_cols=300, nnz=24000,
               grid=[2, 2])
    (d / "configs" / "tiny-k4.json").write_text(json.dumps(cfg))
    (d / "traffic" / "chain_tiny.json").write_text(json.dumps(
        {"runner": "chain", "block": [0, 1], "pattern_seed": 3,
         "priors": "nw", "sweeps": 2, "burnin": 1}))
    (d / "limits" / "tiny-k4.chain.json").write_text(json.dumps(
        {"state": 1e-3, "summary": 1e-1}))
    (d / "metrics" / "calls_per_window.py").write_text(
        "def read(r):\n    return float(r.info['calls'])\n")
    bench = spec.benchmark()
    bench = dict(bench, workloads=[{"name": "tiny-k4.chain",
                                    "config": "tiny-k4",
                                    "traffic": "chain_tiny", "chips": 1,
                                    "why": "test"}])
    bench["end_to_end"] = [dict(m, workloads=["tiny-k4.chain"])
                           for m in bench["end_to_end"]
                           if m["name"] in ("train_ratings_per_s",
                                            "setup_s")]
    bench["per_layer"] = [{"name": "calls_per_window", "unit": "calls",
                           "better": "higher", "source": "host_clock",
                           "layer": "chain", "moves": "train_ratings_per_s",
                           "workloads": ["tiny-k4.chain"]}]
    return bench, d


def test_new_cell_config_traffic_and_metric_from_files(bench_with_new_cell):
    bench, d = bench_with_new_cell
    assert spec.config("tiny-k4", d)["K"] == 4
    res = RUN.run_cell("tiny-k4.chain", 5, 0.2, False, jax.devices(),
                       bench=bench, bench_dir=d)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"train_ratings_per_s", "setup_s"}
    assert list(res)[-1] == "check"
    traced = RUN.run_cell("tiny-k4.chain", 5, 0.2, True, jax.devices(),
                          bench=bench, bench_dir=d)
    assert traced["metrics"]["calls_per_window"]["value"] >= 1
    assert {"busy_s", "window_s"} <= set(traced["device"])


def test_benchmark_json_names_existing_files():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        spec.config(w["config"])
        t = spec.traffic(w["traffic"])
        spec.runner(t["runner"])
        spec.limits(w["name"])
    for m in bench["per_layer"]:
        spec.metric_reader(m["name"])
    for c in bench["configs"]:
        assert (spec.ROOT / c["file"]).is_file()


def test_run_exits_nonzero_without_a_chip(capsys):
    cell = spec.benchmark()["workloads"][0]["name"]
    assert RUN.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                     "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""
