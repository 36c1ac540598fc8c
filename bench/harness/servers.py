"""The system under test behind one small interface, and the control that
takes its place.

``EngineServer`` drives the program only through its public entry points:
``build_engine``, ``SearchEngine.search/upsert/delete/finish_compact``,
``streaming``, ``compile_count`` and ``metrics()``. ``ReferenceServer`` is
the plain exact search computed in bfloat16, put where the program was:
the control that the comparison deciding ``correct`` has to fail.
"""
from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from reference.knn import exact_knn

__all__ = ["EngineServer", "ReferenceServer"]

BLOCK = 8192        # rows per block of the reference's running top-k


class EngineServer:
    """One engine built from a configuration over ``corpus``; rows of the
    corpus have ids 0..N-1 and upserted rows the ids that follow."""

    def __init__(self, config: dict, corpus):
        from repro.search import StreamConfig, build_engine
        self.engine = build_engine(corpus, config["spec"], **config["engine"])
        self.streaming = "stream" in config
        if self.streaming:
            self.engine.streaming(StreamConfig(**config["stream"]))
        jax.block_until_ready(self.engine.store if self.streaming
                              else self.engine.state)
        self.k = int(config["shape"]["k"])

    def search(self, queries):
        return self.engine.search(queries, self.k)

    def upsert(self, ids, rows):
        self.engine.upsert(ids, rows)
        return self.engine.store

    def delete(self, ids):
        self.engine.delete(ids)
        return self.engine.store

    def settle(self):
        """Finish a pending background compaction (outside the window)."""
        if self.streaming:
            self.engine.finish_compact()
            jax.block_until_ready(self.engine.store)

    @property
    def compile_count(self) -> int:
        return self.engine.compile_count

    def counters(self) -> dict:
        m = self.engine.metrics()
        out = {"compile_count": m.engine.compile_count}
        if m.compact is not None:
            out["compactions"] = m.compact.compactions
        if m.stream is not None:
            out["grow_count"] = self.engine.grow_count
        return out

    def probe_inputs(self):
        """(reducer, centroids (nlist, d), live rows per cell, engine
        config) of a read-only ivfpq engine, for the ADC work count; None
        otherwise."""
        state = self.engine.state
        if state is None or state.index.kind != "ivfpq":
            return None
        ix = state.index.payload
        sizes = np.asarray(jnp.sum(ix.lists >= 0, axis=1))
        return (self.engine.reducer, np.asarray(ix.centroids), sizes,
                self.engine.config)

    def close(self):
        """Drop every array the engine holds (the caller's corpus stays)."""
        if self.streaming:
            self.engine.finish_compact()
        self.engine = None
        gc.collect()


class ReferenceServer:
    """Exact search in bfloat16 over the rows the harness holds live."""

    def __init__(self, config: dict, corpus, inserts):
        rows = [jnp.asarray(corpus), jnp.asarray(inserts)]
        n = corpus.shape[0] + inserts.shape[0]
        if n > BLOCK and n % BLOCK:      # pad once, not at every search
            rows.append(jnp.zeros((BLOCK - n % BLOCK, corpus.shape[1]),
                                  jnp.float32))
        self.rows = jnp.concatenate(rows, axis=0)
        self.lo, self.hi = 0, int(corpus.shape[0])
        self.k = int(config["shape"]["k"])
        self.streaming = "stream" in config
        self.compile_count = 0

    def search(self, queries):
        q = np.asarray(queries)
        n = q.shape[0]
        d, i = exact_knn(q, self.rows, self.k, np.full(n, self.lo),
                         np.full(n, self.hi), block=BLOCK,
                         precision="bf16")
        return jnp.asarray(d), jnp.asarray(i)

    def upsert(self, ids, rows):
        self.hi = max(self.hi, int(np.max(ids)) + 1)
        return None

    def delete(self, ids):
        self.lo = max(self.lo, int(np.max(ids)) + 1)
        return None

    def settle(self):
        pass

    def counters(self) -> dict:
        return {"compile_count": 0}

    def probe_inputs(self):
        return None

    def close(self):
        self.rows = None
        gc.collect()
