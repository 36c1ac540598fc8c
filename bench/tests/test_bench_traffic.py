"""The traffic generator: open-loop due times, the same work for every
seed, writes and own-write searches; and the statistics taken over every
request of a window, a stall included."""
import numpy as np
import pytest

from harness.cell import Context, _Req
from harness.spec import load_cell
from harness.stats import percentile, rate
from harness.traffic import DELETE, SEARCH, UPSERT, make_schedule

SLIDE = {"loop": "open", "rate_per_s": 200.0, "search_batch": 1,
         "query_order": "uniform", "pool": "queries", "write_share": 0.1,
         "write_cycle": ["upsert", "delete"], "write_rows": 256,
         "read_own_writes": True}


def test_open_loop_same_work_for_every_seed():
    a = make_schedule(SLIDE, 1, 10.0, 1000)
    b = make_schedule(SLIDE, 2 ** 40 + 1, 10.0, 1000)
    assert a.due.shape == b.due.shape == (2000,)
    gaps_a, gaps_b = np.diff(a.due, prepend=0.0), np.diff(b.due, prepend=0.0)
    assert np.allclose(np.sort(gaps_a), np.sort(gaps_b))
    assert not np.array_equal(a.due, b.due)
    assert np.all(gaps_a > 0)
    # about rate x seconds long, as a Poisson process of that rate is
    assert 9.0 < a.due[-1] < 10.0
    assert np.sum(a.kind != SEARCH) == np.sum(b.kind != SEARCH) == 200
    # the same questions, in another order
    qa, qb = a.queries[a.kind == SEARCH], b.queries[b.kind == SEARCH]
    assert np.array_equal(np.sort(qa, axis=None), np.sort(qb, axis=None))
    assert not np.array_equal(qa, qb)


def test_open_loop_is_reproducible_from_the_seed():
    a = make_schedule(SLIDE, 7, 5.0, 1000)
    b = make_schedule(SLIDE, 7, 5.0, 1000)
    for f in ("due", "kind", "queries", "own_write"):
        assert np.array_equal(getattr(a, f), getattr(b, f))


def test_writes_alternate_and_own_writes_follow_upserts():
    s = make_schedule(SLIDE, 3, 10.0, 1000)
    writes = s.kind[s.kind != SEARCH]
    assert np.all(writes[0::2] == UPSERT) and np.all(writes[1::2] == DELETE)
    assert np.all(s.queries[s.kind != SEARCH] == -1)
    assert np.all((s.queries[s.kind == SEARCH] >= 0)
                  & (s.queries[s.kind == SEARCH] < 1000))
    own = np.flatnonzero(s.own_write >= 0)
    assert 0.9 * s.n_upserts <= own.size <= s.n_upserts
    for i in own:
        # the first search since the last upsert
        last_up = np.flatnonzero(s.kind[:i] == UPSERT)[-1]
        assert not np.any(s.kind[last_up + 1:i] == SEARCH)
        assert s.kind[i] == SEARCH and 0 <= s.own_write[i] < 256


def test_closed_loop_walks_the_pool_in_turn():
    s = make_schedule({"loop": "closed", "search_batch": 256,
                       "query_order": "in_turn", "pool": "queries"},
                      5, 10.0, 1000)
    rows = np.concatenate([s.closed_queries(j) for j in range(4)])
    assert np.array_equal(rows, (s.start + np.arange(1024)) % 1000)


def test_rate_override_and_unknown_keys():
    s = make_schedule(SLIDE, 1, 2.0, 100, rate_per_s=50.0)
    assert s.due.shape == (100,)
    with pytest.raises(ValueError):
        make_schedule({**SLIDE, "burst": 3}, 1, 1.0, 10)


def _reqs(lat_due):
    """Search requests with (due, done) pairs, a query each."""
    out = []
    for due, done in lat_due:
        r = _Req(SEARCH, due, call=due, ret=due, done=done)
        r.ids = np.zeros((1, 10), np.int64)
        out.append(r)
    return out


def test_tail_counts_the_wait_a_stall_imposes():
    # 1000 requests due every 10 ms, each served in 1 ms, except that a
    # 1 s stall at 5 s delays every request due during it
    due = np.arange(1000) * 0.01
    done, free = [], 0.0
    for d in due:
        start = max(d, free)
        if 5.0 <= d < 6.0 and start < 6.0:
            start = 6.0
        free = start + 0.001
        done.append(free)
    ctx = Context(reqs=_reqs(zip(due, done)), window_s=max(done))
    p95 = next(m for m in load_cell("sift1m.single-open").per_layer
               if m.name == "search_p95_ms.open")
    lat = np.array(done) - due
    assert p95.read(ctx) == pytest.approx(1e3 * np.percentile(lat, 95))
    # the 100 requests due in the stall wait up to 1 s: the tail sees them
    assert p95.read(ctx) > 200.0
    assert np.median(lat) == pytest.approx(0.001)


def test_rate_is_over_the_whole_window():
    ctx = Context(reqs=_reqs([(0.0, 1.0), (1.0, 2.0), (2.0, 4.0)]),
                  window_s=4.0)
    qps = next(m for m in load_cell("sift1m.bulk256").end_to_end
               if m.name == "search_qps")
    assert qps.read(ctx) == pytest.approx(3 / 4.0)
    assert rate(3, 4.0) == 0.75
    with pytest.raises(ValueError):
        rate(1, 0.0)


def test_percentile_of_all_values():
    assert percentile([1, 2, 3, 4, 5], 50) == 3.0
    assert percentile(np.arange(101), 95) == 95.0
    with pytest.raises(ValueError):
        percentile([], 99)
