"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes and
returns, each event as ``(name, start_ns, end_ns)``: the device operations
(the "XLA Ops" line of each ``/device:TPU:N`` plane) and the host events
(every line of ``/host:CPU``). Device and host events share the
profiler's clock.

Device ops nest: a ``while`` op spans the fusions of its body. Only the
leaves count as work, each for its own time. ``summarize`` gives:

  busy_s       the union of leaf-op intervals inside the window,
               averaged over the devices;
  window_s     first ``bench.round`` annotation start to last end;
  idle_share   1 - busy_s / window_s;
  chunk_gap_ms the device-idle time at each round boundary, from the
               last op of one annotated round to the first op of the
               next, averaged over the boundaries (None with one round);
  top_ops      the leaf ops that took most time, summed by op name and
               output shape;
  idle_gaps    the longest device-idle gaps, each named by the shortest
               host event that covers its midpoint (what the host was
               doing then), "host idle" where none does.
"""
from __future__ import annotations

import bisect
import glob
from collections import defaultdict
from typing import Callable, Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, end_ns
ROUND = "bench.round"


def tpu_op_line(plane: str, line: str) -> bool:
    """Device operations of a TPU trace."""
    return plane.startswith("/device:TPU:") and line == "XLA Ops"


def load(trace_dir: str, device_line: Callable[[str, str], bool] = tpu_op_line):
    """``({device plane: ops}, host events)`` of the trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = defaultdict(list)
    host: List[Event] = []
    for plane in data.planes:
        for line in plane.lines:
            if device_line(plane.name, line.name):
                sink = devices[plane.name]
            elif plane.name == "/host:CPU":
                sink = host
            else:
                continue
            sink.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
    return dict(devices), host


def op_label(name: str) -> str:
    """"%fusion.12 = bf16[8,128]{1,0} fusion(...)" -> "fusion.12 bf16[8,128]"."""
    head, _, rest = name.partition(" = ")
    shape = rest.split("{")[0].split(" ")[0] if rest and not rest.startswith("(") else ""
    return f"{head.lstrip('%')} {shape}".strip()


def self_times(ops: Sequence[Event]) -> List[Tuple[str, float, float, float]]:
    """``(name, start, end, self time)`` of the leaf ops: those that hold
    no other op of the line inside them."""
    order = sorted(ops, key=lambda e: (e[1], -e[2]))
    parent = [False] * len(order)
    stack: List[int] = []
    for i, (_, s, e) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent[stack[-1]] = True
        stack.append(i)
    return [(n, s, e, e - s) for (n, s, e), p in zip(order, parent) if not p]


def union(intervals: Sequence[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Merged, clipped-to-[lo, hi] intervals, in order."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _covering(host: List[Event], t: float) -> str:
    best = None
    for ev in host:
        if ev[1] <= t <= ev[2] and (best is None or ev[2] - ev[1] < best[2] - best[1]):
            best = ev
    return best[0] if best is not None else "host idle"


def summarize(devices: Dict[str, List[Event]], host: List[Event],
              top: int = 10) -> Dict:
    """The numbers listed in the module docstring, times in seconds
    unless named otherwise. A trace with no device op gives {}."""
    rounds = sorted((s, e) for n, s, e in host if n == ROUND)
    leaves = {d: self_times(ops) for d, ops in devices.items() if ops}
    if not rounds or not leaves:
        return {}
    lo, hi = rounds[0][0], rounds[-1][1]
    n_dev = len(leaves)
    by_name: Dict[str, float] = defaultdict(float)
    busy_ns, chunk_gaps, idle = 0.0, [], []
    starts = [s for s, _ in rounds]
    for d, ops in leaves.items():
        merged = union([(s, e) for _, s, e, _ in ops], lo, hi)
        busy_ns += sum(e - s for s, e in merged) / n_dev
        for name, s, e, own in ops:
            if s >= lo and e <= hi:
                by_name[op_label(name)] += own / n_dev
        per_round: Dict[int, List[Tuple[float, float]]] = defaultdict(list)
        for s, e in merged:
            per_round[bisect.bisect_right(starts, s) - 1].append((s, e))
        chunk_gaps += [per_round[r + 1][0][0] - per_round[r][-1][1]
                       for r in range(len(rounds) - 1)
                       if per_round.get(r) and per_round.get(r + 1)]
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    idle.sort(key=lambda g: g[0] - g[1])
    window = hi - lo
    return {
        "busy_s": busy_ns * 1e-9,
        "window_s": window * 1e-9,
        "idle_share": 1.0 - busy_ns / window,
        "rounds": len(rounds),
        "chunk_gap_ms": (sum(chunk_gaps) / len(chunk_gaps) * 1e-6
                         if chunk_gaps else None),
        "top_ops": [[n, v * 1e-9] for n, v in
                    sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_covering(host, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in idle[:top]],
    }
