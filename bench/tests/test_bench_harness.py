"""The harness finds cells, configurations, metrics, drivers, datasets and
references by name, and refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT, make_tree

import run as R
from harness import check
from harness import spec as SPEC

SPEC_FILE = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC_FILE["workloads"]])
def test_listed_cell_loads_and_limits_name_compared_numbers(cell):
    """Each cell of the repository's ``BENCHMARK.json`` loads with its
    configuration, traffic, limits, code modules and metric readers, and
    its limits name only numbers the comparison reads."""
    c = SPEC.load(cell)
    assert c.chips in (1, 4)
    assert c.driver.Driver and c.dataset.build and c.reference.run
    for m in c.per_layer:
        assert callable(SPEC.reader(BENCH, m["name"]))
    assert c.limits and set(c.limits) <= set(check.NUMBERS)


@pytest.mark.parametrize("path", sorted((BENCH / "limits").glob("*.json")),
                         ids=lambda p: p.stem)
def test_every_limits_file_names_compared_numbers(path):
    """Every limits file, also one kept for a cell not yet listed, names
    only numbers the comparison reads, each with a number."""
    limits = json.loads(path.read_text())
    assert limits and set(limits) <= set(check.NUMBERS)
    assert all(isinstance(v, (int, float)) and not isinstance(v, bool)
               for v in limits.values())


def test_benchmark_file_holds_together():
    """Metrics' ``workloads`` lists name only listed cells, every
    configuration is some cell's, and at most half the cells (one at
    least) ask for four chips."""
    cells = {w["name"]: w for w in SPEC_FILE["workloads"]}
    for m in SPEC_FILE["end_to_end"] + SPEC_FILE["per_layer"]:
        assert set(m.get("workloads", [])) <= set(cells), m["name"]
    assert ({c["name"] for c in SPEC_FILE["configs"]}
            == {w["config"] for w in cells.values()})
    four = sum(w["chips"] == 4 for w in cells.values())
    assert four <= max(1, len(cells) // 2)


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
    assert c.driver.__file__ == str(bench / "drivers" / "single.py")
    assert c.dataset.__file__ == str(bench / "datasets" / "graphs.py")
    assert c.reference.__file__ == str(bench / "references" / "gnn.py")
    assert c.traffic == {"driver": "single", "batch_size": 2,
                         "num_sampled": 1}
    assert c.limits == {"grad_gap": 0.5}
    names = [m["name"] for m in c.per_layer]
    assert "probe_metric.train" in names and "seg_fill.train" in names
    assert SPEC.reader(bench, "probe_metric.train")(None) == 42.0
    # the metric names its cells: another cell does not report it
    other = SPEC.load("tpugraphs-tiny.train.tiny-single", bench)
    assert "probe_metric.train" not in [m["name"] for m in other.per_layer]


def test_cell_of_new_files_runs(tmp_path):
    """A cell whose driver, dataset and reference exist only as new files
    of the tree, under names the harness has never seen, runs correct; the
    modules the tiny cells use are taken away first."""
    bench = make_tree(tmp_path)
    for kind, old, new in (("drivers", "single", "probe_loop"),
                           ("datasets", "graphs", "probe_data"),
                           ("references", "gnn", "probe_ref")):
        src = bench / kind / f"{old}.py"
        (bench / kind / f"{new}.py").write_text(src.read_text())
        src.unlink()
    cfg = json.loads((bench / "configs" / "tpugraphs-tiny.json").read_text())
    cfg.update(name="probe", dataset="probe_data", reference="probe_ref")
    (bench / "configs" / "probe.json").write_text(json.dumps(cfg))
    (bench / "workloads" / "train.probe.json").write_text(json.dumps(
        {"driver": "probe_loop", "batch_size": 4, "num_sampled": 2}))
    cell = "probe.train.probe"
    shutil.copy(bench / "limits" / "tpugraphs-tiny.train.tiny-single.json",
                bench / "limits" / f"{cell}.json")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "probe", "source": "test",
                            "file": "bench/configs/probe.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": cell, "config": "probe",
                              "traffic": "train.probe", "chips": 1,
                              "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    c = SPEC.load(cell, bench)
    assert c.driver.__file__ == str(bench / "drivers" / "probe_loop.py")
    res = R.run_cell(c, 3000000047, 0.2, trace=False, require_tpu=False)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0


def test_dist_driver_reads_the_cli_defaults_without_running():
    """The data-parallel driver takes ``launch/train_dist.py``'s defaults
    from its parser, which ``main`` builds, without training."""
    dist = SPEC.module(BENCH, "drivers", "dist")
    got = dist.cli_defaults()
    assert {k: got[k] for k in dist.PLAIN} == dist.PLAIN
    assert got["epochs"] == 5 and got["devices"] is None


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
