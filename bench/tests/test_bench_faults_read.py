"""The comparison that decides ``correct`` fails the control (the exact
search in bfloat16 in the program's place) and each fault a read cell can
have, planted under a whole rehearsed run; and a compile inside the
window fails the run."""
import pytest

from harness.cell import Options, run_cell
from harness.spec import load_cell

from harness.faults import AlteredAnswers, CompilesInWindow, HalfBatch


def _run(cell, **kw):
    return run_cell(load_cell(cell), Options(seed=11, seconds=0.5,
                                             rehearse=True, **kw))


def test_the_control_is_not_correct():
    res = _run("sift1m.bulk256", control=True)
    assert res["correct"] is False
    assert res["checks"]["dist_gap"]["value"] > 1e-3


@pytest.mark.parametrize("cell,fault", [
    ("sift1m.single-open", AlteredAnswers),
    ("sift1m.bulk256", HalfBatch),
])
def test_a_fault_under_the_timed_path_is_not_correct(cell, fault):
    res = _run(cell, server_wrap=fault)
    assert res["correct"] is False


def test_a_compile_inside_the_window_fails_the_run():
    with pytest.raises(SystemExit, match="inside the window"):
        _run("sift1m.single-open", server_wrap=CompilesInWindow)
