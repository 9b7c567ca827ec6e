"""CPU-only tests of the benchmark harness: a tiny copy of the benchmark's
data files under a temporary root, run with Pallas in interpret mode."""
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

TINY = {
    "tpugraphs-tiny": {
        "config": {"n_graphs": 16, "head": "segment_sum", "agg": "sum",
                   "loss": "pairwise_hinge", "n_out": 1,
                   "data": {"generator": "tpugraphs", "n_configs": 4,
                            "n_types": 5, "comm_range": [3, 5],
                            "comm_size_range": [10, 20], "seed": 1,
                            "max_seg_nodes": 32, "partition": "bfs"}},
        "traffic": ("train.tiny-single",
                    {"driver": "single", "batch_size": 4, "num_sampled": 2}),
    },
}
COMMON = {"backbone": "sage", "n_feat": 8, "hidden": 128, "n_pre": 1,
          "n_mp": 2, "n_post": 1, "variant": "gst_efd", "keep_prob": 0.5,
          "optimizer": "adam", "lr": 0.005, "max_grad_norm": 1.0,
          "use_pallas": True}
# the numbers the real cell compares, at limits for float32 on the CPU
LIMITS = {"grad_gap": 1e-3, "grad_elem_gap": 1e-3, "step1_elem_gap": 1e-4,
          "change_gap": 1e-2, "table1_gap": 1e-4, "table_mismatch": 0}


def make_tree(root: Path, chips: int = 1) -> Path:
    """A benchmark root holding tiny cells ``<config>.<traffic>``; returns
    its bench directory.  Code and readers are the real ones."""
    bench = root / "bench"
    for d in ("configs", "workloads", "limits"):
        (bench / d).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["cpu"] = peaks["TPU v5 lite"]
    (bench / "peaks.json").write_text(json.dumps(peaks))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"], spec["workloads"] = [], []
    for name, t in TINY.items():
        (bench / "configs" / f"{name}.json").write_text(
            json.dumps({"name": name, **COMMON, **t["config"]}))
        traffic, mix = t["traffic"]
        (bench / "workloads" / f"{traffic}.json").write_text(json.dumps(mix))
        cell = f"{name}.{traffic}"
        (bench / "limits" / f"{cell}.json").write_text(json.dumps(LIMITS))
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "test"})
        spec["workloads"].append({"name": cell, "config": name,
                                  "traffic": traffic, "chips": chips,
                                  "why": "test"})
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return bench


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("benchroot"))
