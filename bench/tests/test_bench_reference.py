"""The yardstick: the plain exact k-NN against brute-force numpy, its
bf16 control, recall and returned-distance arithmetic, the seeded data,
and the ADC work count (probed mass, not padded width)."""
import jax
import numpy as np
import pytest

from reference.adc_work import adc_work, probed_rows, rerank_work
from reference.data import make_data, seed_key
from reference.knn import exact_knn, recall_rows, true_dists


def _brute(q, x, k, lo, hi):
    d = np.sqrt(((q[:, None, :].astype(np.float64) - x[None]) ** 2).sum(-1))
    ids = np.arange(x.shape[0])
    out_d, out_i = [], []
    for r in range(q.shape[0]):
        m = (ids >= lo[r]) & (ids < hi[r])
        order = np.argsort(np.where(m, d[r], np.inf), kind="stable")[:k]
        out_d.append(d[r, order])
        out_i.append(order)
    return np.array(out_d), np.array(out_i)


@pytest.mark.parametrize("n,block", [(1000, 256), (777, 100), (64, 8192)])
def test_exact_knn_matches_brute_force_over_live_ranges(n, block):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 24)).astype(np.float32)
    q = rng.normal(size=(37, 24)).astype(np.float32)
    lo = rng.integers(0, n // 3, size=37)
    hi = rng.integers(n // 2, n + 1, size=37)
    d, i = exact_knn(q, x, 10, lo, hi, block=block)
    bd, bi = _brute(q, x, 10, lo, hi)
    assert np.array_equal(i, bi)
    assert np.allclose(d, bd, rtol=1e-5, atol=1e-5)


def test_bf16_control_returns_distances_far_from_exact():
    rng = np.random.default_rng(1)
    x = (rng.normal(size=(2000, 128)) + 3.0).astype(np.float32)
    q = (x[:50] + 0.1 * rng.normal(size=(50, 128))).astype(np.float32)
    d, i = exact_knn(q, x, 10, precision="bf16")
    truth = true_dists(q, x[i])
    gap = np.max(np.abs(d - truth) / np.maximum(truth, 1e-3))
    assert gap > 1e-2
    # the f32 search keeps its ids exact; its matmul-form distances lose
    # digits to cancellation, which is why returned distances are judged
    # against true_dists in float64 and not against the search's own
    d32, i32 = exact_knn(q, x, 10)
    gap32 = np.max(np.abs(d32 - true_dists(q, x[i32])) / true_dists(
        q, x[i32]))
    assert gap > 10 * gap32


def test_recall_rows_and_true_dists():
    found = np.array([[1, 2, 3], [4, 5, 6]])
    truth = np.array([[3, 2, 9], [-1, -1, -1]])
    assert recall_rows(found, truth).tolist() == [2 / 3, 0.0]
    q = np.zeros((1, 2))
    rows = np.array([[[3.0, 4.0], [0.0, 1.0]]])
    assert true_dists(q, rows).tolist() == [[5.0, 1.0]]


def test_data_is_seeded_and_64_bit_seeds_stay_apart():
    a = make_data(5, 300, 40, 20, 8, 64)
    b = make_data(5, 300, 40, 20, 8, 64)
    c = make_data(5 + 2 ** 33, 300, 40, 20, 8, 64)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))
    assert [x.shape for x in a] == [(300, 8), (40, 8), (20, 8)]
    key_data = jax.random.key_data
    assert not np.array_equal(key_data(seed_key(1)),
                              key_data(seed_key(1 + 2 ** 32)))
    with pytest.raises(ValueError):
        seed_key(-1)


def test_adc_work_counts_the_probed_mass_not_the_padded_width():
    rng = np.random.default_rng(2)
    cent = rng.normal(size=(16, 4))
    sizes = np.arange(16) * 10           # cell c holds 10c rows
    q = cent[[3, 7]] + 1e-3               # each query nearest its own cell
    per_q = probed_rows(q, cent, sizes, nprobe=1)
    assert per_q.tolist() == [30, 70]
    two = probed_rows(q, cent, sizes, nprobe=2)
    d2 = ((q[:, None] - cent[None]) ** 2).sum(-1)
    want = [sizes[np.argsort(r)[:2]].sum() for r in d2]
    assert two.tolist() == want
    w = adc_work(int(per_q.sum()), 2, m=16, kc=256, d_reduced=32)
    # 100 candidates x (16 code bytes + id + base) + 2 f32 tables
    assert w["bytes"] == 100 * 24 + 2 * 16 * 256 * 4
    assert w["flops"] == 100 * 16 + 2 * 2 * 32 * 16 * 256
    # the padded width (nprobe x max cell = 150 a query) never enters
    assert adc_work(300, 2, 16, 256, 32)["bytes"] > w["bytes"]


def test_rerank_work_counts_candidate_rows_and_ids():
    w = rerank_work(queries=3, candidates=64, dim=128)
    assert w["bytes"] == 3 * 64 * (128 * 4 + 4)
    assert w["flops"] == 3 * 64 * 3 * 128


def test_nearest_first_holds_to_float32_rounding():
    from harness.check import Answers, _guarantee_breaks
    d = np.array([[1.0, 4.850027084350586, 4.850026607513428],   # 1 ulp
                  [1.0, 2.0, 1.99],                               # swapped
                  [1.0, 1.0, 2.0]])                               # a tie
    ids = np.array([[1, 2, 3], [1, 2, 3], [1, 2, 3]])
    a = Answers(key=np.zeros(3), queries=np.zeros((3, 2)), ids=ids,
                dists=d, lo=np.zeros(3), hi=np.full(3, 10),
                own=np.full(3, -1))
    out = _guarantee_breaks(a, 3)["dist_out_of_order"]
    assert out.tolist() == [False, True, False]
