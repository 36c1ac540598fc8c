"""The reduction from trace events to per-layer numbers, on hand-made
events: busy union, per-scope time, program runs, idle gaps named by the
host span that overlaps them."""
import pytest

from harness.trace import Op, Span, Summary, _instr_name, _leaf_scope

MS = 1e6   # ns


def _op(start, dur, scope="", name="fusion.1", program="jit_p(1)", dev=0):
    return Op(dev, start * MS, dur * MS, name, program, scope)


def _window(start=0.0, dur=100.0):
    return Span(start * MS, dur * MS, "bench.window")


def test_busy_is_the_union_clipped_to_the_window():
    ops = [_op(10, 10), _op(15, 10),          # overlap: 10..25
           _op(50, 5),
           _op(95, 10),                        # runs past the window's end
           _op(150, 5)]                        # after the window: left out
    s = Summary(ops, [], [_window()])
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx((15 + 5 + 5) * 1e-3)


def test_busy_is_averaged_over_devices():
    ops = [_op(0, 40, dev=0), _op(0, 20, dev=1)]
    s = Summary(ops, [], [_window()])
    assert s.busy_s == pytest.approx(0.030)


def test_scope_time_matches_whole_path_segments():
    ops = [_op(0, 4, "jit(_engine_search_fn)/qpad.scan/gather"),
           _op(5, 2, "jit(_engine_search_fn)/qpad.scan/jit(take)/select_n"),
           _op(8, 3, "jit(_engine_stream_fn)/qpad.base_scan/add"),
           _op(12, 1, "jit(_engine_search_fn)/qpad.rerank/sort"),
           _op(14, 9, "jit(f)/qpad.scanner/add"),       # not qpad.scan
           _op(30, 1, "")]
    s = Summary(ops, [], [_window()])
    assert s.scope_s(["qpad.scan"]) == pytest.approx(6e-3)
    assert s.scope_s(["qpad.scan", "qpad.base_scan"]) == pytest.approx(9e-3)
    assert s.scope_s(["qpad.rerank"]) == pytest.approx(1e-3)
    assert s.scope_s(["qpad.merge"]) == 0.0


def test_program_time_and_runs():
    progs = [Span(1 * MS, 5 * MS, "jit__engine_compact(77)"),
             Span(20 * MS, 7 * MS, "jit__engine_compact(77)"),
             Span(30 * MS, 1 * MS, "jit__engine_upsert(5)"),
             Span(200 * MS, 9 * MS, "jit__engine_compact(77)")]   # outside
    s = Summary([], progs, [_window()])
    assert s.program("_engine_compact") == (pytest.approx(0.012), 2)
    assert s.program("_engine_upsert") == (pytest.approx(0.001), 1)
    assert s.program("_engine_delete") == (0.0, 0)


def test_idle_gaps_are_named_by_the_host_span_over_them():
    ops = [_op(10, 10), _op(60, 10)]
    spans = [_window(),
             Span(0, 10 * MS, "bench.wait"),          # gap 0..10
             Span(20 * MS, 35 * MS, "bench.upsert"),  # gap 20..60, mostly
             Span(55 * MS, 5 * MS, "bench.block"),
             Span(70 * MS, 30 * MS, "bench.wait")]    # gap 70..100
    s = Summary(ops, [], spans)
    gaps = s.idle_gaps()
    assert [g[0] for g in gaps] == ["bench.upsert", "bench.wait",
                                    "bench.wait"]
    assert [round(g[1], 6) for g in gaps] == [0.04, 0.03, 0.01]
    assert s.busy_s / s.window_s == pytest.approx(0.2)


def test_top_device_ops_and_breakdown_shape():
    ops = [_op(0, 5, "jit(f)/qpad.scan/gather", "fusion.3"),
           _op(6, 5, "jit(f)/qpad.scan/gather", "fusion.3"),
           _op(12, 2, "jit(f)/qpad.rerank/sort", "sort.1")]
    b = Summary(ops, [], [_window()]).breakdown()
    assert b["device_ops"][0] == ["qpad.scan:fusion.3", pytest.approx(0.01)]
    assert b["device_ops"][1][0] == "qpad.rerank:sort.1"
    assert len(b["idle_gaps"]) <= 10 and len(b["device_ops"]) <= 10


def test_a_trace_without_one_window_is_refused():
    with pytest.raises(ValueError):
        Summary([], [], [])


def test_names_from_instruction_text_and_scope_paths():
    assert _instr_name("%fusion.17 = f32[128]{0} fusion(f32[1,128] %q)"
                       ", kind=kLoop") == "fusion.17"
    assert _instr_name("%copy-start.3 = (f32[32]) copy-start(%x)") \
        == "copy-start.3"
    assert _leaf_scope("jit(_engine_search_fn)/qpad.scan/jit(take)/gather") \
        == "qpad.scan"
    assert _leaf_scope("") == ""


def test_a_listed_metric_that_reads_nothing_fails_the_run():
    """A scope renamed in the program leaves its reader nothing to read:
    the run fails and names the metric and what its reader reads."""
    from harness.cell import Context, RunFailure, _Req, read_metrics
    from harness.spec import load_cell
    from harness.traffic import SEARCH
    scan = [m for m in load_cell("sift1m.bulk256").per_layer
            if m.name == "scan_ms.bulk"]
    renamed = Summary([_op(0, 4, "jit(_engine_search_fn)/qpad.adc/gather")],
                      [], [_window()])
    ctx = Context(reqs=[_Req(SEARCH, 0.0)], trace=renamed)
    with pytest.raises(RunFailure, match=r"scan_ms\.bulk read nothing.*"
                                         r"qpad\.scan"):
        read_metrics(scan, ctx, strict=True)
    assert read_metrics(scan, ctx, strict=False) == {}
    ctx.trace = Summary([_op(0, 4, "jit(_engine_search_fn)/qpad.scan/gather")],
                        [], [_window()])
    got = read_metrics(scan, ctx, strict=True)
    assert got == {"scan_ms.bulk": {"value": pytest.approx(4.0),
                                    "unit": "ms"}}
