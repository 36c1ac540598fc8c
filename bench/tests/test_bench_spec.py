"""BENCHMARK.json keeps to the benchmark's contract, and every cell,
configuration, traffic mix and metric is found by name from its own file,
including ones added in a copy of the tree without editing a file."""
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from harness.spec import ROOT, load_cell

STREAM_CELL = "turing1m-stream.slide"   # the cell of conftest's stream_root

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# widths may never be cut (the model-configs guide, section 4 and 5)
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|projection|_dim$|"
                    r"_rank$|head|expansion|per_tok|^dim$)")


@pytest.fixture(scope="module")
def bench():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_check_budget_fits_with_24_cells(bench):
    runs = 2 + 14 * 24
    total = runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(names) == len(set(names))
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTHS.search(key)
            assert key in cfg["shape"]
        assert cfg["reduced"] == c["reduced"]
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))


def test_cells(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(names) // 2)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    allm = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in allm}) == len(allm)
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in cells
            moved = e2e[m["moves"]]
            assert "workloads" not in moved or cell in moved["workloads"]
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["layer"], m["layer"])
    for m in allm:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("cell", ["sift1m.bulk256", "sift1m.single-open",
                                  STREAM_CELL])
def test_every_cell_loads_and_reports_enough(cell, request):
    root = request.getfixturevalue("stream_root") if cell == STREAM_CELL \
        else ROOT
    c = load_cell(cell, root=root)
    e2e = [m.name for m in c.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    assert all(callable(m.read) for m in c.end_to_end + c.per_layer)


def test_a_new_cell_is_only_new_files(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a cell added
    to a copy of the tree as new files plus new BENCHMARK.json entries run
    without any existing file changing."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((ROOT / "bench/configs/sift1m-qpad-ivfpq.json")
                     .read_text())
    cfg["name"] = "tiny-qpad-ivfpq"
    (tmp_path / "bench/configs/tiny-qpad-ivfpq.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/trickle.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 50.0, "search_batch": 2,
         "query_order": "uniform", "pool": "queries"}))
    (tmp_path / "bench/metrics/answered.trickle.py").write_text(
        "def read(ctx):\n    return float(len(ctx.searches))\n")
    bench["configs"].append({"name": "tiny-qpad-ivfpq", "source": "test",
                             "file": "bench/configs/tiny-qpad-ivfpq.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.trickle",
                               "config": "tiny-qpad-ivfpq",
                               "traffic": "trickle", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "answered.trickle", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "recall_at_10",
                               "workloads": ["tiny.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = load_cell("tiny.trickle", root=tmp_path)
    assert cell.traffic["rate_per_s"] == 50.0
    assert [m.name for m in cell.per_layer] == ["answered.trickle"]
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tiny.trickle",
         "--seed", "3", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env={**_env(), "JAX_COMPILATION_CACHE_DIR": str(ROOT / ".jax_cache")})
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["metrics"]["answered.trickle"]["value"] == 50.0
    after = {p: p.read_bytes() for p in before}
    assert after == before


def _env():
    import os
    return {k: v for k, v in os.environ.items()
            if k not in ("PYTHONPATH",)} | {"JAX_PLATFORMS": "cpu"}
