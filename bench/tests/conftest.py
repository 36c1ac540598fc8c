"""The benchmark's modules import as the harness itself imports them, with
``bench/`` and the program's ``src/`` on the path."""
import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

STREAM_CELL = "turing1m-stream.slide"
STREAM_CONFIG = "bench/configs/turing1m-qpad-ivfpq-stream.json"


@pytest.fixture(scope="session")
def stream_root(tmp_path_factory):
    """A copy of the tree whose BENCHMARK.json also holds a stream cell, so
    that the harness's write path, compaction and delta scan run in the
    tests. Its mix (``data/slide.json``) is a test mix: the benchmark has
    no stream cell until a streaming-track runbook gives one."""
    root = tmp_path_factory.mktemp("stream")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    (root / "src").symlink_to(BENCH.parent / "src")
    shutil.copy(BENCH / "tests" / "data" / "slide.json",
                root / "bench" / "traffic" / "slide.json")
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH.parent / STREAM_CONFIG).read_text())
    bench["configs"].append({"name": cfg["name"], "source": cfg["source"],
                             "file": STREAM_CONFIG,
                             "reduced": cfg["reduced"], "why": "tests"})
    bench["workloads"].append({"name": STREAM_CELL, "config": cfg["name"],
                               "traffic": "slide", "chips": 1,
                               "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "sift1m.single-open" in m.get("workloads", ()):
            m["workloads"].append(STREAM_CELL)
    bench["per_layer"] += [
        {"name": name, "unit": "ms", "better": "lower", "source": source,
         "layer": "write path: segments", "moves": "search_p50_ms",
         "workloads": [STREAM_CELL]}
        for name, source in (("write_p95_ms.stream", "host_clock"),
                             ("compact_ms.stream", "device_trace"),
                             ("upsert_ms.stream", "device_trace"))]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
