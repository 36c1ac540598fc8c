"""The host's side of a traced window: where a search spends the time the
chip does not.

``trace.load`` keeps only the harness's ``bench.*`` host spans. This reads
the same ``.xplane.pb`` again and keeps every host event, from every host
line (one a thread): the program's own spans (``qpad.search`` with its
``.prepare`` / ``.launch`` children, the write path's), the harness's
``bench.*`` spans and the runtime's events (``PjitFunction``, the
completion callbacks). Device 0's program runs and busy intervals come
along, all clipped to the ``bench.window`` annotation like ``Summary``.

Each ``qpad.search`` span is paired with the first run, after the start
of its ``qpad.search.launch``, of a program whose name holds ``_engine_``
and ``search`` or ``stream`` (the engine's search programs), and with
the first ``bench.block`` on its line after it ends (the harness's wait
for the result). From the pairs:

  prepare_ms   median ``qpad.search.prepare``
  launch_ms    median launch start -> its program's start on the device
  complete_ms  median its program's end -> the end of its bench.block
  host_idle_ms mean device-idle time while a search is outstanding
               (qpad.search start -> bench.block end)

A trace of a program without these spans gives no pairs, and every
number reads None.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
from pathlib import Path

import numpy as np

from harness.trace import WINDOW, Span, _union

__all__ = ["Event", "HostView", "load", "reduce_dir"]

SEARCH = "qpad.search"


@dataclasses.dataclass(frozen=True)
class Event:
    start: float                   # ns
    dur: float                     # ns
    name: str
    line: int                      # host line (thread) in the trace
    thread: str = ""               # the line's name

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass(frozen=True)
class Pair:
    """One search: its span, launch span, program run and harness wait."""
    search: Event
    launch: Event
    program: Span
    block: Event


def _is_search_program(name: str) -> bool:
    return "_engine_" in name and ("search" in name or "stream" in name)


class HostView:
    """Host events, device 0's program runs and its busy intervals, inside
    the window."""

    def __init__(self, events: list, programs: list, ops: list):
        win = [e for e in events if e.name == WINDOW]
        if len(win) != 1:
            raise ValueError(f"{len(win)} '{WINDOW}' spans in the trace")
        self.lo, self.hi = win[0].start, win[0].end
        inside = lambda e: self.lo <= e.start < self.hi   # noqa: E731
        self.events = sorted((e for e in events
                              if e.name != WINDOW and inside(e)),
                             key=lambda e: e.start)
        self.programs = sorted((p for p in programs if inside(p)),
                               key=lambda p: p.start)
        iv = np.array([[s, min(e, self.hi)] for s, e in ops
                       if self.lo <= s < self.hi]).reshape(-1, 2)
        self.busy = _union(iv)
        self._busy_starts = [s for s, _ in self.busy]
        self.pairs = self._pair()

    def named(self, name: str) -> list:
        return [e for e in self.events if e.name == name]

    def _by_line(self, name: str) -> dict:
        """{line: (events named ``name`` on it, their starts)}."""
        out = {}
        for e in self.named(name):
            out.setdefault(e.line, []).append(e)
        return {ln: (es, [e.start for e in es]) for ln, es in out.items()}

    def _pair(self) -> list:
        progs = [p for p in self.programs if _is_search_program(p.name)]
        prog_starts = [p.start for p in progs]
        launches = self._by_line(SEARCH + ".launch")
        blocks = self._by_line("bench.block")
        out = []
        for s in self.named(SEARCH):
            ls, l_starts = launches.get(s.line, ([], []))
            bs, b_starts = blocks.get(s.line, ([], []))
            i = bisect.bisect_left(l_starts, s.start)
            b = bisect.bisect_left(b_starts, s.end)
            if i == len(ls) or ls[i].start > s.end or b == len(bs):
                continue
            j = bisect.bisect_left(prog_starts, ls[i].start)
            if j < len(progs):
                out.append(Pair(s, ls[i], progs[j], bs[b]))
        return out

    # -- the numbers -------------------------------------------------------

    def idle_ns(self, a: float, b: float) -> float:
        """Device-0 idle time inside [a, b)."""
        busy = 0.0
        i = max(bisect.bisect_right(self._busy_starts, a) - 1, 0)
        for s, e in self.busy[i:]:
            if s >= b:
                break
            busy += max(0.0, min(e, b) - max(s, a))
        return (b - a) - busy

    def prepare_ms(self):
        d = [e.dur for e in self.named(SEARCH + ".prepare")]
        return 1e-6 * float(np.median(d)) if d else None

    def launch_ms(self):
        d = [p.program.start - p.launch.start for p in self.pairs]
        return 1e-6 * float(np.median(d)) if d else None

    def complete_ms(self):
        d = [p.block.end - (p.program.start + p.program.dur)
             for p in self.pairs]
        return 1e-6 * float(np.median(d)) if d else None

    def host_idle_ms(self):
        d = [self.idle_ns(p.search.start, p.block.end) for p in self.pairs]
        return 1e-6 * float(np.mean(d)) if d else None

    # -- idle gaps ---------------------------------------------------------

    def name_of(self, a: float, b: float) -> tuple:
        """(event name, its line's name) of the innermost host event over
        [a, b): the shortest event, on any line, that covers at least half
        of it, a ``qpad.*`` span first on ties; else the event that covers
        most of it; ("no host span", "") where none does."""
        over = [(min(b, e.end) - max(a, e.start), e) for e in self.events
                if e.start < b and e.end > a]
        if not over:
            return "no host span", ""
        half = [e for ov, e in over if 2 * ov >= b - a]
        if half:
            best = min(half, key=lambda e: (e.dur,
                                            not e.name.startswith("qpad.")))
        else:
            best = max(over, key=lambda oe: oe[0])[1]
        return best.name, best.thread

    def idle_gaps(self, top: int = 10) -> list:
        """The ``top`` longest stretches of the window with no op on device
        0, longest first, each cut where a paired search starts and where
        its wait ends, so that a stretch lies wholly inside one search or
        wholly between searches: [event name, its line's name, seconds,
        whether a search was outstanding]."""
        edges = [self.lo] + [x for iv in self.busy for x in iv] + [self.hi]
        cuts = sorted(x for p in self.pairs
                      for x in (p.search.start, p.block.end))
        stretches = []
        for s, e in zip(edges[::2], edges[1::2]):
            bounds = [s, *cuts[bisect.bisect_right(cuts, s):
                               bisect.bisect_left(cuts, e)], e]
            stretches += [(a, b) for a, b in zip(bounds, bounds[1:])
                          if b > a]
        stretches.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in stretches[:top]:
            name, thread = self.name_of(s, e)
            out.append([name, thread, float(e - s) * 1e-9,
                        self._outstanding(s, e)])
        return out

    def _outstanding(self, a: float, b: float) -> bool:
        """Whether [a, b) lies inside a paired search's start-to-wait."""
        return any(p.search.start <= a and b <= p.block.end
                   for p in self.pairs)


def load(path: str) -> HostView:
    """Read one ``.xplane.pb`` into a ``HostView``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    events, programs, ops = [], [], []
    line = 0
    for plane in pd.planes:
        if plane.name == "/device:TPU:0":
            for ln in plane.lines:
                if ln.name == "XLA Modules":
                    programs = [Span(e.start_ns, e.duration_ns, e.name)
                                for e in ln.events]
                elif ln.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns)
                           for e in ln.events]
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                events += [Event(e.start_ns, e.duration_ns, e.name, line,
                                 ln.name) for e in ln.events]
                line += 1
    return HostView(events, programs, ops)


def reduce_dir(trace_dir) -> HostView:
    """The ``HostView`` of the one trace under ``trace_dir``."""
    found = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} traces under {trace_dir}")
    return load(found[0])
