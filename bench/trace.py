"""Reduction of a profiler trace to per-layer numbers.

A trace is reduced in two steps. ``record_from_xplane`` reads the
``.xplane.pb`` the JAX profiler wrote and keeps what the metrics need as
a plain record (JSON-able): per device, the ``XLA Ops`` events as
``[instruction, start_ns, duration_ns]``; the host events of the thread
that drew the window's annotations, and Python's function events, as
``[name, start_ns, duration_ns]``; and, from the compiled program's HLO
text, each instruction's ``op_name`` (its ``jax.named_scope`` path) and,
for kernel calls, the bytes their shapes read and write.
``Reduced(record)`` then works on that record only, so a small recorded
trace kept with the tests checks the same arithmetic the chip run uses.

Definitions:

* the window is the span of the benchmark's ``bench.round`` host
  annotations; device events are clipped to it;
* an event is top-level when no other event of its device's line
  encloses it; leaves enclose none;
* busy is the union of top-level events; idle share is 1 - busy/window;
* a phase's device time is the time of top-level events whose
  ``op_name`` holds ``round.<phase>``;
* a collective's exposed time is the part of it during which no leaf
  that is not a collective runs on that device.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_MARK = "bench.round"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PHASE = re.compile(r"(?:^|/)round\.(\w+)")
_COLLECTIVE = re.compile(r"(all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all)")


def instruction(event_name: str) -> str:
    """``%while.20 = (...) while(...)`` -> ``while.20``."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.strip().lstrip("%")


def hlo_index(hlo_texts: Sequence[str]) -> Tuple[Dict[str, str], Dict[str, int]]:
    """(instruction -> op_name, kernel instruction -> bytes) over the
    given compiled modules."""
    from bench.roofline import custom_call_bytes
    op_names: Dict[str, str] = {}
    kernel_bytes: Dict[str, int] = {}
    for text in hlo_texts:
        for line in text.splitlines():
            m = _INSTR.match(line)
            if not m:
                continue
            name = m.group(1)
            op = _OP_NAME.search(line)
            if op:
                op_names.setdefault(name, op.group(1))
            if 'custom_call_target="tpu_custom_call"' in line:
                kernel_bytes[name] = custom_call_bytes(line)
    return op_names, kernel_bytes


def record_from_xplane(trace_dir: str, hlo_texts: Sequence[str]) -> Dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [[instruction(e.name), float(e.start_ns),
                            float(e.duration_ns)] for e in line.events]
            devices.append([plane.name, ops])
        elif plane.name == "/host:CPU":
            lines = {line.name: [[e.name, float(e.start_ns), float(e.duration_ns)]
                                 for e in line.events] for line in plane.lines}
            # the thread that drew the window's annotations, then Python's
            # own function events, which name what that thread was doing
            marked = [evs for evs in lines.values()
                      if any(e[0] == WINDOW_MARK for e in evs)]
            if not marked:
                raise ValueError(f"no {WINDOW_MARK} annotation on any host "
                                 f"line: {[(n, len(e)) for n, e in lines.items()]}")
            host = marked[0] + [
                e for name, evs in lines.items() if name == "python"
                for e in evs if evs is not marked[0]]
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    op_names, kernel_bytes = hlo_index(hlo_texts)
    used = {op[0] for _, ops in devices for op in ops}
    return {"devices": [ops for _, ops in devices], "host": host,
            "op_names": {k: v for k, v in op_names.items() if k in used},
            "kernel_bytes": {k: v for k, v in kernel_bytes.items() if k in used}}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _minus(intervals, cover) -> float:
    """Length of ``intervals`` outside ``cover`` (a sorted union)."""
    starts = [c for c, _ in cover]
    total = 0.0
    for a, b in intervals:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(cover) and cover[i][0] < b:
            c, d = cover[i]
            covered += max(0.0, min(b, d) - max(a, c))
            i += 1
        total += (b - a) - covered
    return total


class Reduced:
    """Numbers of one traced window, from a record (see module doc)."""

    def __init__(self, record: Dict, rounds: int):
        self.rounds = rounds
        marks = [(s, s + d) for n, s, d in record["host"] if n == WINDOW_MARK]
        if not marks:
            raise ValueError("trace holds no bench.round annotation")
        self.t0 = min(a for a, _ in marks)
        self.t1 = max(b for _, b in marks)
        self.window_ns = self.t1 - self.t0
        self.op_names: Dict[str, str] = record["op_names"]
        self.kernel_bytes: Dict[str, int] = record["kernel_bytes"]
        self.host = [(n, s, s + d) for n, s, d in record["host"]
                     if s < self.t1 and s + d > self.t0]
        self.top: List[List[Tuple[str, float, float]]] = []
        self.leaves: List[List[Tuple[str, float, float]]] = []
        self.every: List[List[Tuple[str, float, float]]] = []
        for ops in record["devices"]:
            evs = sorted(((n, max(s, self.t0), min(s + d, self.t1))
                          for n, s, d in ops
                          if s < self.t1 and s + d > self.t0),
                         key=lambda e: (e[1], -e[2]))
            top, leaves, stack = [], [], []
            has_child = [False] * len(evs)
            for i, (n, a, b) in enumerate(evs):
                while stack and evs[stack[-1]][2] <= a:
                    stack.pop()
                if stack and b <= evs[stack[-1]][2]:
                    has_child[stack[-1]] = True
                else:
                    top.append((n, a, b))
                stack.append(i)
            leaves = [e for e, c in zip(evs, has_child) if not c]
            self.top.append(top)
            self.leaves.append(leaves)
            self.every.append(evs)

    # -- device-wide ---------------------------------------------------------
    @property
    def n_devices(self) -> int:
        return len(self.top)

    def busy_ns(self) -> float:
        """Busy time averaged over the devices."""
        if not self.top:
            return 0.0
        return sum(_length(_union([(a, b) for _, a, b in top]))
                   for top in self.top) / len(self.top)

    def op_name(self, instr: str) -> str:
        return self.op_names.get(instr, "")

    def phase(self, instr: str) -> str:
        m = _PHASE.search(self.op_name(instr))
        return m.group(1) if m else "other"

    def phase_ms_per_round(self) -> Dict[str, float]:
        """Device time of each ``round.<phase>`` scope, ms per round,
        averaged over the devices."""
        out: Dict[str, float] = defaultdict(float)
        for top in self.top:
            for n, a, b in top:
                out[self.phase(n)] += (b - a)
        return {k: v / 1e6 / self.rounds / max(1, self.n_devices)
                for k, v in out.items()}

    def matching_ms_per_round(self, pattern: str) -> Optional[float]:
        """Device time of top-level events whose op_name matches, ms per
        round averaged over devices; None when none ran."""
        rx = re.compile(pattern)
        hits = [(a, b) for top in self.top for n, a, b in top
                if rx.search(self.op_name(n))]
        if not hits:
            return None
        return _length(hits) / 1e6 / self.rounds / max(1, self.n_devices)

    def kernel_calls(self, pattern: str) -> Tuple[float, int]:
        """(seconds, bytes) summed over the kernel calls whose op_name
        matches, on every device."""
        rx = re.compile(pattern)
        secs, nbytes = 0.0, 0
        for evs in self.every:
            for n, a, b in evs:
                if n in self.kernel_bytes and rx.search(self.op_name(n)):
                    secs += (b - a) / 1e9
                    nbytes += self.kernel_bytes[n]
        return secs, nbytes

    def collective_exposed_ms_per_round(self) -> Optional[float]:
        """Collective time with no other leaf running, per device, ms per
        round, averaged over devices; None when no collective ran."""
        total, found = 0.0, False
        for evs, leaves in zip(self.every, self.leaves):
            coll = [(a, b) for n, a, b in evs if _COLLECTIVE.search(n)]
            if not coll:
                continue
            found = True
            compute = _union([(a, b) for n, a, b in leaves
                              if not _COLLECTIVE.search(n)])
            total += _minus(_union(coll), compute)
        if not found:
            return None
        return total / 1e6 / self.rounds / max(1, self.n_devices)

    # -- breakdown -----------------------------------------------------------
    def top_ops(self, k: int = 10) -> List[List]:
        """The k top-level device operations that took most time, in
        seconds summed over the window and averaged over devices."""
        agg: Dict[str, float] = defaultdict(float)
        for top in self.top:
            for n, a, b in top:
                agg[f"{n} [{self.phase(n)}]"] += (b - a) / 1e9
        rows = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
        return [[n, s / max(1, self.n_devices)] for n, s in rows]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The k longest gaps between busy intervals of device 0, each
        named by the innermost host event that spans its midpoint."""
        if not self.top:
            return []
        busy = _union([(a, b) for _, a, b in self.top[0]])
        edges = [self.t0] + [x for ab in busy for x in ab] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        rows = []
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
            mid = (a + b) / 2
            around = [(e - s, n) for n, s, e in self.host if s <= mid <= e]
            label = min(around)[1] if around else "no host event"
            rows.append([label, (b - a) / 1e9])
        return rows
