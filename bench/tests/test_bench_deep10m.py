"""The deep-10M cell: it loads by name with its seven per-layer metrics,
a rehearsed run prints a well-formed, correct last line, and the probe's
required work is counted as worked out by hand."""
import json
import os
import subprocess
import sys

import pytest

from harness.spec import ROOT, load_cell
from reference.probe_work import probe_shape, probe_work

CELL = "deep10m.bulk256"
PER_LAYER = ["probe_ms.deep10m", "probe_roofline.deep10m", "scan_ms.deep10m",
             "scan_roofline.deep10m", "rerank_ms.deep10m",
             "idle_share.deep10m", "peak_hbm_gb.deep10m"]


def test_the_cell_loads_and_its_metrics_move_search_qps():
    cell = load_cell(CELL)
    assert cell.chips == 1
    assert [m.name for m in cell.end_to_end] == ["search_qps",
                                                 "recall_at_10", "setup_s"]
    assert [m.name for m in cell.per_layer] == PER_LAYER
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        if m["name"] in PER_LAYER:
            assert m["moves"] == "search_qps"
            assert CELL in e2e[m["moves"]]["workloads"]
    cfg = cell.config
    assert cfg["shape"]["rows"] == 10_000_000 and cfg["shape"]["dim"] == 96
    assert cfg["reduced"] == []
    assert probe_shape(cfg["spec"]) == (16384, 32, 32)


def test_a_rehearsal_prints_a_well_formed_correct_line():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL,
         "--seed", str(2 ** 33 + 15), "--seconds", "1", "--trace", "0",
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"search_qps", "recall_at_10", "setup_s"}
    assert set(line["checks"]) == {"recall_at_10", "dist_gap",
                                   "bad_answers"}


def test_probe_work_counts_a_hand_worked_case():
    # 2 calls of 3 queries each, 4 cells of dim 5, 2 probed a query:
    # flops 2 * 6 * 4 * 5 = 240; bytes: centroids 2 * 4 * 5 * 4 = 160,
    # queries 6 * 5 * 4 = 120, probed ids 6 * 2 * 4 = 48
    assert probe_work(6, 2, 4, 2, 5) == {"bytes": 328, "flops": 240}


@pytest.mark.parametrize("spec,shape", [
    ("qpad32>ivf16384x32>pq16x256>rr64", (16384, 32, 32)),
    ("ivf256x4>pq16x256>rr64", (256, 4, None)),
])
def test_probe_shape_reads_the_spec(spec, shape):
    assert probe_shape(spec) == shape


def test_probe_shape_needs_a_coarse_stage():
    with pytest.raises(ValueError):
        probe_shape("qpad32>pq16x256>rr64")
