"""From a profiler trace of the window to the numbers the readers report.

``load_record`` reads the newest ``*.xplane.pb`` under a trace directory
into a ``Record``: the device operations of each chip (the ``XLA Ops``
line of each ``/device:TPU:n`` plane) and the benchmark's own host spans
(``bench.window``, ``bench.dispatch``, ``bench.on_batch``, written by
``jax.profiler.TraceAnnotation`` in ``harness.py``), on one clock. All
arithmetic is on that record, so a recorded one (``testdata/``) checks it
without a chip.

Inside the window the host runs, per batch b of ``stream_simulate``:
``host_prep`` (generate, screen, pack and stage batch b: from the end of
the last benchmark span before the dispatch of b to that dispatch),
``dispatch`` (the program call), ``wait`` (blocking on the batch before)
and ``on_batch`` (its copy to the host).
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

Interval = Tuple[int, int]  # [start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Record:
    window: Interval
    #: chip -> [(op name, start_ns, end_ns)]
    ops: Dict[int, List[Tuple[str, int, int]]]
    #: benchmark host spans [(name, start_ns, end_ns)], by start
    spans: List[Tuple[str, int, int]]
    #: op name -> what it computes: the JAX source path of a program op,
    #: or the module name of an eager op ("" when unknown)
    kinds: Dict[str, str]
    events: int  # events completed in the window

    # -- device time ------------------------------------------------------

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self, chip: int) -> List[Interval]:
        """Union of the chip's op intervals, clipped to the window."""
        return union([(s, e) for _, s, e in self.ops.get(chip, ())],
                     self.window)

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips traced."""
        chips = sorted(self.ops) or [0]
        return sum(length(self.busy(c)) for c in chips) * 1e-9 / len(chips)

    def op_seconds(self, match) -> float:
        """Device seconds in which an op that ``match(name, kind)`` accepts
        ran (the union of their intervals, so a loop and the ops of its
        body count once), clipped to the window, averaged over the chips
        traced."""
        chips = sorted(self.ops) or [0]
        total = sum(length(union(
            [(s, e) for name, s, e in self.ops.get(c, ())
             if match(name, self.kinds.get(name, ""))], self.window))
            for c in chips)
        return total * 1e-9 / len(chips)

    # -- host -------------------------------------------------------------

    def host_prep(self) -> List[Interval]:
        """One interval per dispatch: from the end of the last benchmark
        span before it (or the window's start) to the dispatch."""
        lo, hi = self.window
        inside = [(n, s, e) for n, s, e in self.spans
                  if n != "bench.window" and s >= lo and e <= hi]
        out = []
        for name, s, _ in inside:
            if name != "bench.dispatch":
                continue
            before = [e2 for _, _, e2 in inside if e2 <= s]
            out.append((max(before) if before else lo, s))
        return out

    def regions(self) -> List[Tuple[str, int, int]]:
        """The window cut into labelled host regions: dispatch, on_batch,
        host_prep, and wait for what lies between them."""
        lo, hi = self.window
        marked = [(f"{n[len('bench.'):]}:{i}", s, e)
                  for n in ("bench.dispatch", "bench.on_batch")
                  for i, (s, e) in enumerate(
                      (s, e) for m, s, e in self.spans
                      if m == n and s >= lo and e <= hi)]
        marked += [(f"host_prep:{i}", s, e)
                   for i, (s, e) in enumerate(self.host_prep())]
        marked.sort(key=lambda r: r[1])
        out, t = [], lo
        for name, s, e in marked:
            if s > t:
                out.append(("wait", t, s))
            out.append((name, s, e))
            t = max(t, e)
        if t < hi:
            out.append(("wait", t, hi))
        return out

    def idle_gaps(self, chip: int = 0) -> List[Tuple[str, int, int]]:
        """Idle intervals of a chip in the window, longest first, each
        labelled by the host region that overlaps it most."""
        regions = self.regions()
        out = []
        for s, e in complement(self.busy(chip), self.window):
            best = max(regions, key=lambda r: overlap((r[1], r[2]), (s, e)),
                       default=("wait", s, e))
            out.append((best[0], s, e))
        out.sort(key=lambda g: g[1] - g[2])
        return out

    def breakdown(self, top: int = 10) -> dict:
        by_name: Dict[str, int] = {}
        lo, hi = self.window
        chips = sorted(self.ops) or [0]
        for c in chips:
            for name, s, e in self.ops.get(c, ()):
                by_name[name] = by_name.get(name, 0) + max(
                    0, min(e, hi) - max(s, lo))
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[f"{n} {self.kinds.get(n, '')}".strip()[:160],
                            d * 1e-9 / len(chips)] for n, d in ops],
            "idle_gaps": [[label, (e - s) * 1e-9]
                          for label, s, e in self.idle_gaps()[:top]],
        }


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals, window: Optional[Interval] = None) -> List[Interval]:
    """Sorted, merged intervals, clipped to ``window`` when given."""
    lo, hi = window if window is not None else (None, None)
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if window is not None:
            s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def complement(busy: List[Interval], window: Interval) -> List[Interval]:
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def overlap(a: Interval, b: Interval) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


# ---------------------------------------------------------------------------
# Reading the profiler's trace
# ---------------------------------------------------------------------------


MODULES_LINE = "XLA Modules"
INSTR = re.compile(r"^%?([\w.\-]+)")
HLO_LINE = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*?metadata=\{[^}]*?"
                      r"op_name=\"([^\"]*)\"")


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> the JAX source path (``op_name`` metadata) of a
    compiled program's HLO text, e.g. ``while.6`` ->
    ``jit(run)/vmap()/scatter-add``: what the trace's bare op names lack."""
    out = {}
    for line in hlo_text.splitlines():
        m = HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def load_record(trace_dir: Path, events: int,
                program: Optional[Tuple[str, Dict[str, str]]] = None
                ) -> Record:
    """The newest trace under ``trace_dir`` as a Record.

    ``program`` is (HLO module name, ``hlo_op_names`` of it): ops that run
    inside an execution of that module are named ``<module>/<instruction>``
    and get the instruction's JAX source path as their kind; ops of other
    modules (the generator's eager ops) get their module's name."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    main, op_names = program or ("", {})
    ops: Dict[int, List[Tuple[str, int, int]]] = {}
    kinds: Dict[str, str] = {}
    spans = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
            continue
        lines = {line.name: line for line in plane.lines}
        modules = sorted(
            (int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns),
             ev.name.split("(")[0])
            for ev in (lines[MODULES_LINE].events
                       if MODULES_LINE in lines else ()))
        starts = [mod[0] for mod in modules]
        chip = ops.setdefault(int(m.group(1)), [])
        for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
            s = int(ev.start_ns)
            i = bisect.bisect_right(starts, s) - 1
            module = modules[i][2] if i >= 0 and s < modules[i][1] else "?"
            instr = INSTR.match(ev.name)
            instr = instr.group(1) if instr else ev.name
            name = f"{module}/{instr}"
            if name not in kinds:
                kinds[name] = (op_names.get(instr, "") if module == main
                               else module)
            chip.append((name, s, s + int(ev.duration_ns)))
    spans.sort(key=lambda x: x[1])
    windows = [(s, e) for n, s, e in spans if n == "bench.window"]
    if len(windows) != 1:
        raise ValueError(f"expected one bench.window span, found {len(windows)}")
    return Record(window=windows[0], ops=ops, spans=spans, kinds=kinds,
                  events=events)
