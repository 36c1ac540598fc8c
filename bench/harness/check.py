"""The comparison that decides ``correct``: what the timed path returned,
against the plain reference over the rows live when each search was due.

Numbers compared (each against the limit in the configuration's
``check``):

  recall_at_10      mean recall@k against the exact reference
  dist_gap          widest relative gap between a returned distance and
                    the float64 distance of the returned id
  bad_answers       answers that break the guarantee: an id that was not
                    live, a repeated id, fewer than k ids, a distance that
                    is not finite or out of order beyond float32 rounding
                    (limit 0)
  own_write_misses  searches for a row that the previous upsert wrote and
                    that did not return it first (limit 0)
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from reference.knn import exact_knn, recall_rows, true_dists

__all__ = ["Answers", "compare", "judge"]

# relative gaps are taken against max(true distance, DIST_FLOOR), so that a
# search for a row's own vector (true distance 0) is judged absolutely
DIST_FLOOR = 1e-3
# "nearest first" holds to float32 rounding: two returned distances may
# stand out of order by at most this share (16 float32 ulps); the program
# was read swapping two ties one ulp apart (a relative 9.8e-8)
ORDER_SLACK = 1e-6


@dataclasses.dataclass
class Answers:
    """Searches answered by the timed path, one row per query."""
    key: np.ndarray            # (A,) what was asked: a pool row, or
    #                            pool_size + row of an upserted vector
    queries: np.ndarray        # (A, D) the query vectors
    ids: np.ndarray            # (A, k) returned ids
    dists: np.ndarray          # (A, k) returned distances
    lo: np.ndarray             # (A,) live ids [lo, hi) when it was due
    hi: np.ndarray
    own: np.ndarray            # (A,) id the search must return first, or -1

    @classmethod
    def concat(cls, parts):
        return cls(*(np.concatenate([getattr(p, f.name) for p in parts])
                     for f in dataclasses.fields(cls)))

    def __len__(self):
        return self.key.shape[0]


def _guarantee_breaks(a: Answers, k: int) -> dict:
    """{which guarantee: (A,) bool} for every answer."""
    ids, d = a.ids, a.dists
    srt = np.sort(ids, axis=1)
    return {
        "id_not_live": ~((ids >= a.lo[:, None])
                         & (ids < a.hi[:, None])).all(axis=1),
        "id_repeated": (srt[:, 1:] == srt[:, :-1]).any(axis=1),
        "dist_not_finite": ~np.isfinite(d).all(axis=1),
        "dist_out_of_order": ~(np.diff(d, axis=1)
                               >= -ORDER_SLACK * d[:, :-1]).all(axis=1),
        "fewer_than_k": np.full(len(a), ids.shape[1] != k),
    }


def _examples(a: Answers, breaks: dict, n: int = 3) -> list:
    """The first ``n`` answers that break a guarantee, for the log."""
    out = []
    for why, rows in breaks.items():
        for r in np.flatnonzero(rows)[:n - len(out)]:
            out.append({"breaks": why, "asked": int(a.key[r]),
                        "live": [int(a.lo[r]), int(a.hi[r])],
                        "ids": a.ids[r].tolist(),
                        "dists": a.dists[r].tolist()})
    return out


def compare(a: Answers, rows, k: int) -> dict:
    """Readings of one set of answers. ``rows`` (device) holds every row
    by id; each distinct question is asked once of the reference."""
    breaks = _guarantee_breaks(a, k)
    bad = np.logical_or.reduce(list(breaks.values()))
    safe = np.clip(a.ids, 0, rows.shape[0] - 1)
    got = np.asarray(jnp.take(rows, jnp.asarray(safe.reshape(-1)), axis=0))
    d_true = true_dists(a.queries, got.reshape(*safe.shape, -1))
    gap = np.abs(a.dists - d_true) / np.maximum(d_true, DIST_FLOOR)
    gap = np.where(bad[:, None], 0.0, gap)
    ask = np.stack([a.key, a.lo, a.hi], axis=1)
    _, first, inv = np.unique(ask, axis=0, return_index=True,
                              return_inverse=True)
    _, truth = exact_knn(a.queries[first], rows, k, a.lo[first],
                         a.hi[first])
    rec = recall_rows(a.ids, truth[inv.reshape(-1)])
    own = a.own >= 0
    return {"recall_at_10": float(np.mean(rec)) if rec.size else None,
            "dist_gap": float(np.max(gap)) if gap.size else 0.0,
            "bad_answers": int(np.sum(bad)),
            "bad_examples": _examples(a, breaks),
            "own_write_misses": int(np.sum(a.ids[own, 0] != a.own[own]))}


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for every limited number; a
    number with a limit and no reading fails."""
    out, ok = {}, True
    for name, lim in limits.items():
        v = readings.get(name)
        if "min" in lim:
            passed = v is not None and v >= lim["min"]
            shown = f">= {lim['min']}"
        else:
            passed = v is not None and v <= lim["max"]
            shown = f"<= {lim['max']}"
        ok &= passed
        out[name] = {"value": v, "limit": shown}
    return ok, out
