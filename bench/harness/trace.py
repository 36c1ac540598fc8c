"""From a profiler trace of the window to the numbers the per-layer
metrics read.

The trace (``jax.profiler``, the Python tracer off) holds, on each
``/device:TPU:<n>`` plane, an ``XLA Modules`` line (one event per program
run) and an ``XLA Ops`` line (one event per HLO instruction run, named by
its instruction text), and on the host plane the harness's own
``bench.*`` annotations. An op's named scope (``qpad.scan``,
``qpad.rerank``, ...) is not on its event: it is in the op_name metadata
of the instruction in the program's HLO, which xprof's ``hlo_stats`` tool
reads from the trace and lists per (program id, instruction).

Reduction, all clipped to the ``bench.window`` annotation:

  busy       union of the op intervals of each device, averaged over the
             devices; idle share is 1 - busy / window
  scope_s    op time whose scope path holds a given scope
  program    time and runs of the programs whose name holds a string
  idle gaps  stretches with no op on device 0, each named by the host
             annotation that overlaps it most
"""
from __future__ import annotations

import dataclasses
import glob
import json
import re
from pathlib import Path

import numpy as np

__all__ = ["Op", "Span", "Summary", "load", "reduce_dir"]

WINDOW = "bench.window"


@dataclasses.dataclass(frozen=True)
class Op:
    device: int
    start: float                   # ns
    dur: float                     # ns
    name: str                      # HLO instruction name, e.g. fusion.12
    program: str                   # program (module) name, e.g. jit_f(123)
    scope: str = ""                # op_name metadata, e.g. jit(f)/qpad.scan/..


@dataclasses.dataclass(frozen=True)
class Span:
    start: float
    dur: float
    name: str


def _union(iv: np.ndarray) -> list:
    """Merged [start, end) intervals of (n, 2) ``iv``."""
    out = []
    for s, e in iv[np.argsort(iv[:, 0])] if len(iv) else []:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Summary:
    """The reduced trace of one window."""

    def __init__(self, ops: list, programs: list, spans: list):
        win = [s for s in spans if s.name == WINDOW]
        if len(win) != 1:
            raise ValueError(f"{len(win)} '{WINDOW}' spans in the trace")
        self.lo, self.hi = win[0].start, win[0].start + win[0].dur
        inside = lambda e: self.lo <= e.start < self.hi   # noqa: E731
        self.ops = [o for o in ops if inside(o)]
        self.programs = [p for p in programs if inside(p)]
        self.spans = [s for s in spans if s.name != WINDOW and inside(s)]
        self.window_s = (self.hi - self.lo) * 1e-9
        self.devices = sorted({o.device for o in self.ops}) or [0]
        busy = []
        for d in self.devices:
            iv = np.array([[o.start, min(o.start + o.dur, self.hi)]
                           for o in self.ops if o.device == d]).reshape(-1, 2)
            merged = _union(iv)
            busy.append(sum(e - s for s, e in merged))
            if d == self.devices[0]:
                self._busy0 = merged
        self.busy_s = float(np.mean(busy)) * 1e-9

    def scope_s(self, scopes) -> float:
        """Device seconds of ops under any of ``scopes`` (a scope matches a
        whole segment of the op's scope path)."""
        want = set(scopes)
        return 1e-9 * sum(o.dur for o in self.ops
                          if want & set(o.scope.split("/")))

    def program(self, part: str) -> tuple:
        """(device seconds, runs) of the programs whose name holds
        ``part``."""
        hit = [p.dur for p in self.programs if part in p.name]
        return 1e-9 * sum(hit), len(hit)

    def idle_gaps(self, top: int = 10) -> list:
        """The ``top`` longest stretches of the window with no op on the
        first device, as [host activity, seconds]."""
        edges = [self.lo] + [x for iv in self._busy0 for x in iv] + [self.hi]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:top]:
            best, label = 0.0, "no host span"
            for sp in self.spans:
                ov = min(e, sp.start + sp.dur) - max(s, sp.start)
                if ov > best:
                    best, label = ov, sp.name
            out.append([label, float(e - s) * 1e-9])
        return out

    def device_ops(self, top: int = 10) -> list:
        """The ``top`` ops by device time, as [scope:instruction, seconds]."""
        tot = {}
        for o in self.ops:
            key = f"{_leaf_scope(o.scope)}:{o.name}"
            tot[key] = tot.get(key, 0.0) + o.dur
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v * 1e-9] for k, v in best]

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops(),
                "idle_gaps": self.idle_gaps()}


def _leaf_scope(scope: str) -> str:
    """The innermost qpad.* (or other dotted) scope of an op_name path."""
    parts = [p for p in scope.split("/") if "." in p and "(" not in p]
    return parts[-1] if parts else (scope.split("/")[0] if scope else "")


_INSTR = re.compile(r"^%?([\w.\-]+) = ")


def _instr_name(text: str) -> str:
    m = _INSTR.match(text)
    return m.group(1) if m else text.split(" ", 1)[0]


def _scopes(path: str) -> dict:
    """{(program, instruction): op_name} from xprof's hlo_stats tool; empty
    where the trace carries no HLO."""
    from xprof.convert import raw_to_tool_data
    data, _ = raw_to_tool_data.xspace_to_tool_data(
        [path], "hlo_stats", {"use_saved_result": False})
    table = json.loads(data)
    cols = [c["id"] for c in table["cols"]]
    out = {}
    for row in table.get("rows", []):
        v = dict(zip(cols, (c.get("v") for c in row["c"])))
        # "op_name:op_type"; the op_name is the scope path
        name = (v["tf_op_name"] or "").rsplit(":", 1)[0]
        out[(str(v["program_id"]), v["hlo_op_name"])] = name
    return out


_PROGRAM_ID = re.compile(r"\((-?\d+)\)$")


def load(path: str) -> Summary:
    """Read one ``.xplane.pb`` into a ``Summary``."""
    from jax.profiler import ProfileData
    scopes = _scopes(path)
    pd = ProfileData.from_file(path)
    ops, programs, spans = [], [], []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            dev = int(m.group(1))
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            progs = [Span(e.start_ns, e.duration_ns, e.name)
                     for e in lines.get("XLA Modules", [])]
            programs += progs
            starts = np.array([p.start for p in progs])
            for e in lines.get("XLA Ops", []):
                i = int(np.searchsorted(starts, e.start_ns, "right")) - 1
                prog = progs[i].name if i >= 0 else ""
                pid = _PROGRAM_ID.search(prog)
                name = _instr_name(e.name)
                scope = scopes.get((pid.group(1) if pid else "", name), "")
                ops.append(Op(dev, e.start_ns, e.duration_ns, name, prog,
                              scope))
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                spans += [Span(e.start_ns, e.duration_ns, e.name)
                          for e in ln.events if e.name.startswith("bench.")]
    return Summary(ops, programs, spans)


def reduce_dir(trace_dir) -> Summary:
    """The ``Summary`` of the one trace under ``trace_dir``."""
    found = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} traces under {trace_dir}")
    return load(found[0])
