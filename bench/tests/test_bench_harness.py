"""The harness finds cells, configurations and metrics by name, and refuses
to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT, make_tree

from harness import spec as SPEC


def test_new_cell_config_and_metric_found_by_name(tmp_path):
    bench = make_tree(tmp_path)
    cfg = json.loads((bench / "configs" / "tpugraphs-tiny.json").read_text())
    cfg["name"] = "tpugraphs-tiny2"
    (bench / "configs" / "tpugraphs-tiny2.json").write_text(json.dumps(cfg))
    (bench / "workloads" / "train.other.json").write_text(
        json.dumps({"driver": "single", "batch_size": 2, "num_sampled": 1}))
    cell = "tpugraphs-tiny2.train.other"
    (bench / "limits" / f"{cell}.json").write_text('{"grad_gap": 0.5}')
    (bench / "metrics" / "probe_metric.train.py").write_text(
        "def read(run):\n    return 42.0\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tpugraphs-tiny2", "source": "test",
                            "file": "bench/configs/tpugraphs-tiny2.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": cell, "config": "tpugraphs-tiny2",
                              "traffic": "train.other", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "probe_metric.train", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "train_graphs_per_s",
                              "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    c = SPEC.load(cell, bench)
    assert c.config["name"] == "tpugraphs-tiny2"
    assert c.traffic == {"driver": "single", "batch_size": 2,
                         "num_sampled": 1}
    assert c.limits == {"grad_gap": 0.5}
    names = [m["name"] for m in c.per_layer]
    assert "probe_metric.train" in names and "seg_fill.train" in names
    assert SPEC.reader(bench, "probe_metric.train")(None) == 42.0
    # the metric names its cells: another cell does not report it
    other = SPEC.load("tpugraphs-tiny.train.tiny-single", bench)
    assert "probe_metric.train" not in [m["name"] for m in other.per_layer]


def _run(cwd, workload):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    p = _run(ROOT, spec["workloads"][0]["name"])
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_bare_checkout_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    p = _run(tmp_path, spec["workloads"][0]["name"])
    assert p.returncode != 0
    assert "{" not in p.stdout
