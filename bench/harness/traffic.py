"""One general generator for every traffic mix: it reads the mix's data
file and a seed and gives the requests of a window.

Keys of a mix file (``traffic/<mix>.json``):

  loop           "open" (requests due on a schedule, whether or not the
                 last has finished) or "closed" (one client sends its next
                 request when the last returns)
  rate_per_s     open loop: offered requests per second; ``share_of_knee``
                 and ``knee_per_s`` record where that rate came from
  search_batch   queries per search request
  query_order    "uniform" (open loop: every pool row asked equally
                 often, rows taken in turn as many as the window asks, in
                 a seeded order) or "in_turn" (closed loop: the pool
                 walked in order from a seeded start)
  pool           which query pool searches draw from ("queries")
  write_share    share of requests that are writes (default 0)
  write_cycle    kinds the writes take in turn, of "upsert" and "delete"
  write_rows     rows per write request
  read_own_writes  the first search after each upsert queries one of the
                 rows that upsert wrote

Every seed gets the same work: an open loop of n = rate x seconds requests
whose gaps are the n midpoint quantiles of the exponential distribution
(a Poisson process's gaps), in a seeded order, and exactly
round(n x write_share) writes at seeded positions, and the same pool rows
asked. Seeds change the order, not the amount or the questions.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["SEARCH", "UPSERT", "DELETE", "Schedule", "make_schedule"]

SEARCH, UPSERT, DELETE = 0, 1, 2
_KINDS = {"upsert": UPSERT, "delete": DELETE}
_KEYS = {"loop", "rate_per_s", "share_of_knee", "knee_per_s", "knee_note",
         "search_batch", "query_order", "pool", "write_share", "write_cycle",
         "write_rows", "read_own_writes"}


@dataclasses.dataclass
class Schedule:
    """The requests of one window. ``due`` (seconds after the window opens)
    is empty for a closed loop, which has no fixed count; its searches
    come from ``closed_queries(j)``."""
    loop: str
    batch: int
    write_rows: int
    due: np.ndarray                # (R,) float64
    kind: np.ndarray               # (R,) int8: SEARCH | UPSERT | DELETE
    queries: np.ndarray            # (R, batch) pool rows; -1 for writes
    own_write: np.ndarray          # (R,) row of the last upsert to query
    pool_size: int
    start: int                     # closed loop: first pool row

    def closed_queries(self, j: int) -> np.ndarray:
        return (self.start + j * self.batch
                + np.arange(self.batch)) % self.pool_size

    @property
    def n_upserts(self) -> int:
        return int(np.sum(self.kind == UPSERT))


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(seed))


def make_schedule(mix: dict, seed: int, seconds: float, pool_size: int,
                  rate_per_s: float | None = None) -> Schedule:
    """The window's requests for ``mix`` under ``seed``; ``rate_per_s``
    overrides the mix's rate (a knee sweep)."""
    unknown = set(mix) - _KEYS
    if unknown:
        raise ValueError(f"unknown traffic keys {sorted(unknown)}")
    if mix.get("pool", "queries") != "queries":
        raise ValueError(f"unknown query pool {mix['pool']!r}")
    rng = _rng(seed)
    batch = int(mix["search_batch"])
    write_rows = int(mix.get("write_rows", 0))
    empty = np.zeros(0)
    if mix["loop"] == "closed":
        return Schedule("closed", batch, write_rows, empty,
                        np.zeros(0, np.int8), np.zeros((0, batch), np.int64),
                        np.zeros(0, np.int64), pool_size,
                        int(rng.integers(pool_size)))
    if mix["loop"] != "open":
        raise ValueError(f"loop must be 'open' or 'closed': {mix['loop']!r}")
    rate = float(rate_per_s or mix["rate_per_s"])
    n = max(1, round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = np.cumsum(rng.permutation(gaps))
    kind = np.zeros(n, np.int8)
    n_writes = round(n * float(mix.get("write_share", 0.0)))
    if n_writes:
        cycle = [_KINDS[k] for k in mix["write_cycle"]]
        at = np.sort(rng.choice(n, n_writes, replace=False))
        kind[at] = [cycle[i % len(cycle)] for i in range(n_writes)]
    if mix.get("query_order", "uniform") != "uniform":
        raise ValueError("an open loop asks its pool rows uniformly")
    # the pool's rows in turn, in a seeded order: every seed asks the same
    # questions, so recall and the work per window do not move with it
    searches = kind == SEARCH
    asked = np.arange(int(searches.sum()) * batch) % pool_size
    queries = np.full((n, batch), -1, np.int64)
    queries[searches] = rng.permutation(asked).reshape(-1, batch)
    own = np.full(n, -1, np.int64)
    if mix.get("read_own_writes"):
        pending = False
        for i in range(n):
            if kind[i] == UPSERT:
                pending = True
            elif kind[i] == SEARCH and pending:
                own[i] = rng.integers(write_rows)
                pending = False
    return Schedule("open", batch, write_rows, due, kind, queries, own,
                    pool_size, 0)
