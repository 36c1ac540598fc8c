"""``bench/run.py`` end to end on the CPU: one rehearsed run of each cell
prints a well-formed last line; without a TPU, or without the program
beside the benchmark, it exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from harness.spec import ROOT

STREAM_CELL = "turing1m-stream.slide"   # the cell of conftest's stream_root

CELLS = ["sift1m.bulk256", "sift1m.single-open", STREAM_CELL]


def _run(args, cwd=ROOT):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.parametrize("cell,trace", [(c, 0) for c in CELLS]
                         + [(STREAM_CELL, 1)])
def test_rehearsal_prints_a_well_formed_last_line(cell, trace, request):
    root = request.getfixturevalue("stream_root") if cell == STREAM_CELL \
        else ROOT
    out = _run(["--workload", cell, "--seed", str(2 ** 33 + 9),
                "--seconds", "1", "--trace", str(trace), "--rehearse"],
               cwd=root)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    keys = list(line)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] >= 1
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    if trace:
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert "window_s" in line["device"] and "busy_s" in line["device"]
        assert "dispatch_ms.open" in line["metrics"]
    else:
        assert "setup_s" in line["metrics"]
        assert "recall_at_10" in line["metrics"]
    # every compared number is on stderr beside its limit, last
    tail = out.stderr.strip().splitlines()[-len(line["checks"]):]
    for name, c in line["checks"].items():
        assert any(name in t and c["limit"] in t for t in tail)


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    out = _run(["--workload", "sift1m.single-open", "--seed", "1",
                "--seconds", "1", "--trace", "0"])
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def test_without_the_program_the_run_fails(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = _run(["--workload", "sift1m.single-open", "--seed", "1",
                "--seconds", "1", "--trace", "0", "--rehearse"],
               cwd=tmp_path)
    assert out.returncode != 0
    assert not out.stdout.strip()
