"""Servers with the timed path broken underneath, for the fault tests."""
import jax
import jax.numpy as jnp


class Wrapped:
    """Passes everything to the server it wraps."""

    def __init__(self, server):
        self.inner = server

    def __getattr__(self, name):
        return getattr(self.inner, name)


class AlteredAnswers(Wrapped):
    """Every returned id is moved to the next row where it is produced."""

    def search(self, queries):
        d, i = self.inner.search(queries)
        return d, jnp.where(i >= 0, i + 1, i)


class HalfBatch(Wrapped):
    """The second half of each batch is left out: its rows get the first
    half's answers."""

    def search(self, queries):
        d, i = self.inner.search(queries)
        h = d.shape[0] // 2
        return (jnp.concatenate([d[:d.shape[0] - h], d[:h]]),
                jnp.concatenate([i[:i.shape[0] - h], i[:h]]))


class StaleUpsert(Wrapped):
    """An upsert that returns the store unchanged, once warm-up has seen
    its compaction (a write path that never fills cannot warm up)."""

    def upsert(self, ids, rows):
        if self.inner.engine.metrics().compact.compactions == 0:
            return self.inner.upsert(ids, rows)
        return self.inner.engine.store


class StaleDelete(Wrapped):
    """A delete that returns the store unchanged."""

    def delete(self, ids):
        return self.inner.engine.store


class CompilesInWindow(Wrapped):
    """A search that compiles a new program every call."""

    def __init__(self, server):
        super().__init__(server)
        self.calls = 0

    def search(self, queries):
        self.calls += 1
        d, i = self.inner.search(queries)
        shift = jax.jit(lambda x: x + 0 * self.calls)
        return shift(d), i
