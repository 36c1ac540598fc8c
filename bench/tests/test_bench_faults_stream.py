"""The stream cell's comparison fails the control and each fault the
write path can have: an upsert or a delete that returns the store
unchanged, and an answer altered where it is produced."""
import pytest

from harness.cell import Options, run_cell
from harness.spec import load_cell

from harness.faults import AlteredAnswers, StaleDelete, StaleUpsert

STREAM_CELL = "turing1m-stream.slide"   # the cell of conftest's stream_root


@pytest.fixture(scope="module")
def cell(stream_root):
    return load_cell(STREAM_CELL, root=stream_root)


def _run(cell, **kw):
    return run_cell(cell, Options(seed=12, seconds=1.0, rehearse=True, **kw))


def test_the_control_is_not_correct(cell):
    res = _run(cell, control=True)
    assert res["correct"] is False
    assert res["checks"]["dist_gap"]["value"] > 1e-3


@pytest.mark.parametrize("fault,broken", [
    (StaleUpsert, "own_write_misses"),
    (StaleDelete, "bad_answers"),
    (AlteredAnswers, "dist_gap"),
])
def test_a_fault_under_the_timed_path_is_not_correct(cell, fault, broken):
    res = _run(cell, server_wrap=fault)
    assert res["correct"] is False
    c = res["checks"][broken]
    assert c["value"] > float(c["limit"].split()[-1])
