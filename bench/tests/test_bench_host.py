"""The host's side of a traced window (``harness.host``): each search
paired with its program and its wait, the four host numbers, idle gaps
named by the innermost host event over them, on hand-made events; and,
on a real CPU profile, that the program's spans and the runtime's events
leave ``trace.load``'s reduction as it was."""
import glob
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from harness.cell import Context
from harness.host import Event, HostView
from harness.spec import BENCH_DIR, ROOT, _load_reader
from harness.trace import Span, Summary, load

MS = 1e6   # ns


def _ev(start, dur, name, line=0, thread="main"):
    return Event(start * MS, dur * MS, name, line, thread)


def _prog(start, dur, name):
    return Span(start * MS, dur * MS, name)


def _view(extra=(), ops=((11, 14), (50.7, 53))):
    """Two paired searches on line 0, one unpaired on line 1."""
    events = [
        _ev(0, 100, "bench.window"),
        # search 1: its program runs 11..14, its wait ends at 15
        _ev(10, 2, "qpad.search"), _ev(10, 0.3, "qpad.search.prepare"),
        _ev(10.3, 1.2, "qpad.search.launch"), _ev(12, 3, "bench.block"),
        # search 2, a stream engine's: program 50.7..53, wait ends 53.5
        _ev(50, 1, "qpad.search"), _ev(50, 0.2, "qpad.search.prepare"),
        _ev(50.2, 0.7, "qpad.search.launch"), _ev(51, 2.5, "bench.block"),
        _ev(15, 35, "bench.wait"),
        # a search on another line with no launch: not paired
        _ev(70, 1, "qpad.search", line=1),
        # outside the window: left out
        _ev(150, 1, "qpad.search.prepare"), *extra]
    programs = [_prog(10.4, 0.05, "jit_dynamic_slice(3)"),   # not a search
                _prog(11, 3, "jit__engine_search_fn(1)"),
                _prog(50.7, 2.3, "jit__engine_stream_fn(2)")]
    return HostView(events, programs, [(s * MS, e * MS) for s, e in ops])


def test_each_search_pairs_with_its_program_and_its_wait():
    v = _view()
    assert [(p.program.name, p.block.end / MS) for p in v.pairs] == [
        ("jit__engine_search_fn(1)", 15), ("jit__engine_stream_fn(2)", 53.5)]
    assert v.prepare_ms() == pytest.approx(0.25)      # median of .3, .2
    assert v.launch_ms() == pytest.approx(0.6)        # .7 and .5
    assert v.complete_ms() == pytest.approx(0.75)     # 1.0 and .5


def test_host_idle_is_device_idle_while_a_search_is_outstanding():
    v = _view()
    # search 1: 10..15 with the device busy 11..14; search 2: 50..53.5,
    # busy 50.7..53
    assert v.host_idle_ms() == pytest.approx((2.0 + 1.2) / 2)
    assert v.idle_ns(0, 100 * MS) / MS == pytest.approx(100 - 3 - 2.3)


def test_a_program_without_spans_reads_nothing():
    bare = HostView([_ev(0, 100, "bench.window"), _ev(12, 3, "bench.block")],
                    [_prog(11, 3, "jit__engine_search_fn(1)")],
                    [(11 * MS, 14 * MS)])
    assert bare.pairs == []
    assert [bare.prepare_ms(), bare.launch_ms(), bare.complete_ms(),
            bare.host_idle_ms()] == [None] * 4


def test_gaps_are_cut_at_searches_and_named_by_the_innermost_event():
    events = [
        _ev(0, 100, "bench.window"),
        # search 1 waits 26 ms for its result; the runtime's completion
        # thread sits in one event for most of it
        _ev(10, 2, "qpad.search"), _ev(10.3, 1.2, "qpad.search.launch"),
        _ev(12, 28, "bench.block"),
        _ev(15, 24, "CompleteCallbacks", line=2, thread="runtime"),
        # search 2 stalls in its own prepare, right after search 1
        _ev(40.5, 11.5, "qpad.search"),
        _ev(40.5, 11, "qpad.search.prepare"),
        _ev(51.5, 0.4, "qpad.search.launch"), _ev(52, 2, "bench.block"),
        _ev(54, 46, "bench.wait")]
    programs = [_prog(11, 3, "jit__engine_search_fn(1)"),
                _prog(52, 1, "jit__engine_search_fn(1)")]
    ops = [(11, 14), (52, 53), (60, 61)]
    v = HostView(events, programs, [(s * MS, e * MS) for s, e in ops])
    gaps = [[g[0], g[1], round(g[2] * 1e3, 6), g[3]] for g in v.idle_gaps()]
    assert gaps[:5] == [
        ["bench.wait", "main", 39.0, False],           # 61..100
        ["CompleteCallbacks", "runtime", 26.0, True],  # 14..40, search 1
        ["qpad.search.prepare", "main", 11.5, True],   # 40.5..52, search 2
        ["no host span", "", 10.0, False],             # 0..10
        ["bench.wait", "main", 6.0, False]]            # 54..60
    assert [g[2] for g in gaps[5:]] == [1.0, 1.0, 0.5]
    # ties go to the program's span
    tie = _view(extra=[_ev(80, 10, "Runtime", line=2),
                       _ev(80, 10, "qpad.compact.fold", line=3)])
    assert tie.name_of(80 * MS, 90 * MS)[0] == "qpad.compact.fold"
    # under half covered by each: the one that covers most
    assert _view().name_of(60 * MS, 100 * MS)[0] == "qpad.search"


def test_a_trace_without_one_window_is_refused():
    with pytest.raises(ValueError):
        HostView([], [], [])


@pytest.mark.parametrize("name,value", [
    ("prepare_ms.open", 0.25), ("launch_ms.open", 0.6),
    ("complete_ms.open", 0.75), ("host_idle_ms.open", 1.6)])
def test_readers_read_the_host_view_and_nothing_without_it(name, value):
    read, what = _load_reader(BENCH_DIR / "metrics" / f"{name}.py")
    assert what
    assert read(Context(reqs=[], trace=None)) is None
    assert read(Context(reqs=[], trace=None, host=_view())) == \
        pytest.approx(value)


def test_program_and_runtime_events_leave_the_reduction_as_it_was(
        tmp_path):
    """A real profile of a served window on the CPU holds the program's
    qpad.* spans and the runtime's events next to the harness's bench.*
    ones; ``trace.load`` keeps only the latter, as before."""
    from repro.search import build_engine, jax_profile
    from repro.search.tracing import span
    rng = np.random.default_rng(0)
    eng = build_engine(rng.normal(size=(300, 16)).astype(np.float32),
                       "flat")
    q = rng.normal(size=(2, 16)).astype(np.float32)
    jax.block_until_ready(eng.search(q, 5))
    with jax_profile(str(tmp_path)):
        with span("bench.window"):
            for _ in range(3):
                with span("bench.search"):
                    out = eng.search(q, 5)
                with span("bench.block"):
                    jax.block_until_ready(out)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    from harness.host import load as load_host
    view = load_host(path)
    names = {e.name for e in view.events}
    assert {"qpad.search", "qpad.search.prepare",
            "qpad.search.launch"} <= names
    assert any(n.startswith("PjitFunction") for n in names)
    summary = load(path)
    assert {s.name for s in summary.spans} == {"bench.search", "bench.block"}
    bench_only = [Span(e.start, e.dur, e.name) for e in view.events
                  if e.name.startswith("bench.")]
    window = Span(view.lo, view.hi - view.lo, "bench.window")
    alone = Summary(summary.ops, summary.programs, bench_only + [window])
    assert summary.breakdown() == alone.breakdown()
    assert summary.busy_s == alone.busy_s


def test_hostview_rehearsal_prints_the_host_numbers():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    out = subprocess.run(
        [sys.executable, "bench/hostview.py", "--workload",
         "sift1m.single-open", "--seed", str(2 ** 33 + 11), "--seconds",
         "1", "--span-calls", "1000", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(line["metrics"]) == {"prepare_ms.open", "launch_ms.open",
                                    "complete_ms.open", "host_idle_ms.open"}
    # the CPU trace has no device plane: nothing pairs, but the program's
    # own prepare span is read
    assert line["metrics"]["prepare_ms.open"] > 0
    assert line["searches_paired"] == 0
    assert line["span_cost_us"]["off"] > 0 and line["span_cost_us"]["on"] > 0
    assert "idle gap" in out.stderr
