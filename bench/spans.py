#!/usr/bin/env python3
"""Reduction of a profiler trace to time per span of the round program.

    python bench/spans.py --workload <name> --seed <n> --seconds <s>

runs one cell's window under the profiler, as ``bench/run.py --trace 1``
does, and prints one JSON line: ``reduce``'s result, the per-layer
numbers ``layer_metrics`` makes of it, and the traced window's own
``round_s``.

Where an op's scope comes from: a device op event ("XLA Ops" of a TPU
plane) carries the HLO instruction's text, not its metadata. The trace's
``/host:metadata`` plane holds each program's ``HloProto`` under its
module name, ``<module>(<program id>)``, the name of the "XLA Modules"
events on the device plane. An op is the instruction of that name in the
module whose execution covers it, and its ``op_name`` metadata is the
``jax.named_scope`` path it was traced under. An instruction without one
(XLA's own copies, layout changes and buffers) takes its fused
computation's, else its nearest named producer's, else its nearest named
user's (``_op_names``). A path component is a scope of
``repro.core.spans`` with wrappers such as ``transpose(jvp(...))`` taken
off, so a scope's time is its forward plus its backward. On the CPU the
ops are host events that name their module and instruction in stats
(``hlo_module``, ``program_id``, ``hlo_op``).

``reduce`` takes the leaf ops (``trace_reduce.self_times``) inside the
window, first to last ``bench.round`` annotation, and gives:

  busy_s, window_s   as ``trace_reduce.summarize``;
  scopes             device seconds by innermost scope, plus
                     ``unscoped``; leaves do not overlap, so the buckets
                     sum to ``busy_s``;
  under              device seconds of the ops under each scope at any
                     depth;
  idle_s             device-idle seconds inside the window;
  idle_between       idle seconds by the innermost scopes of the ops on
                     either side of each gap, ``"<before> | <after>"``
                     (``window`` at its edges), the 20 largest;
  idle_under         idle seconds whose neighbouring ops both lie under
                     the scope;
  idle_in_host_span  idle seconds by the innermost program host span
                     over them: ``scaffold.dispatch``, ``scaffold.fetch``,
                     ``scaffold.chunk`` (its own time), or ``none``;
  chunks             per ``scaffold.chunk`` span in the window: the host
                     spans' milliseconds, how long after the dispatch
                     began its first device op started, how long before
                     the fetch ended its last op ended, and the fetch's
                     time after that op (a chunk's ops are those that
                     start after the previous chunk's fetch ended).

A trace without the program's spans, or with no device op, gives {}.
"""
from __future__ import annotations

import bisect
import glob
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

BENCH = Path(__file__).resolve().parent
for _p in (BENCH.parent / "src", BENCH):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import trace_reduce  # noqa: E402

try:
    from repro.core import spans as names
except ImportError:  # a program without spans: every reduction is {}
    names = None

METADATA_PLANE = "/host:metadata"
WRAPPED = re.compile(r"^(?:[\w-]+\()*([^()]*)\)*$")


# ------------------------------------------------ protobuf wire format

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, span: Optional[Tuple[int, int]] = None, want: int = 0):
    """``(field, value)`` of a protobuf message in ``buf[span]``, only
    field ``want`` if given; a length-delimited value as its span."""
    i, hi = span or (0, len(buf))
    while i < hi:
        key, i = _varint(buf, i)
        field, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind}")
        if not want or field == want:
            yield field, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode()


def _first(buf, span, want: int):
    return next((v for _, v in _fields(buf, span, want)), None)


def _ints(buf, value) -> List[int]:
    """A repeated integer field's value: packed (a span) or one varint."""
    if not isinstance(value, tuple):
        return [value]
    out, (i, hi) = [], value
    while i < hi:
        v, i = _varint(buf, i)
        out.append(v)
    return out


def _op_names(hlo_module: bytes) -> Dict[str, str]:
    """instruction name -> ``op_name`` of a serialized HloModuleProto
    (computations 3, id 5; instructions 2: name 1, metadata 7 with
    op_name 2, id 35, operand ids 36, called computations 38). An
    instruction with none, as XLA's own copies and buffers have, takes
    the last named instruction of a computation it calls (a fusion's
    fused computation), else its nearest named producer's, else its
    nearest named user's."""
    named, operands, by_id, named_in = {}, {}, {}, {}
    for _, comp in _fields(hlo_module, None, 3):
        last = ""
        for _, ins in _fields(hlo_module, comp, 2):
            name, op, called, ops, iid = "", "", [], [], None
            for field, v in _fields(hlo_module, ins):
                if field == 1:
                    name = _text(hlo_module, v)
                elif field == 7:
                    span = _first(hlo_module, v, 2)
                    op = _text(hlo_module, span) if span else ""
                elif field == 35:
                    iid = v
                elif field == 36:
                    ops += _ints(hlo_module, v)
                elif field == 38:
                    called += _ints(hlo_module, v)
            op = op if "/" in op else ""  # a traced path, not an argument's name
            # callees come before their callers in the proto
            named[name] = op or next((named_in[c] for c in called if named_in.get(c)), "")
            operands[name], by_id[iid] = ops, name
            last = named[name] or last
        named_in[_first(hlo_module, comp, 5)] = last
    producers = {n: [by_id[o] for o in ops if o in by_id] for n, ops in operands.items()}
    users = defaultdict(list)
    for n, ps in producers.items():
        for p in ps:
            users[p].append(n)
    return {n: op or _nearest(n, producers, named) or _nearest(n, users, named)
            for n, op in named.items()}


def _nearest(start: str, edges, named: Dict[str, str], steps: int = 6) -> str:
    """The op_name of the first named instruction breadth-first from
    ``start`` along ``edges``, else ""."""
    seen, frontier = {start}, [start]
    for _ in range(steps):
        frontier = [m for n in frontier for m in edges.get(n, ()) if m not in seen]
        seen.update(frontier)
        hit = next((named[m] for m in frontier if named[m]), "")
        if hit:
            return hit
    return ""


def program_op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """``{"<module>(<program id>)": {instruction: op_name}}`` from the
    ``HloProto`` stats of a serialized XSpace's metadata plane (XSpace
    planes 1; XPlane name 2, event metadata 4 as map entries of value 2;
    XEventMetadata name 2, stats 5; XStat bytes 6; HloProto module 1)."""
    out = {}
    for _, plane in _fields(xspace, None, 1):
        name = _first(xspace, plane, 2)
        if name is None or _text(xspace, name) != METADATA_PLANE:
            continue
        for _, entry in _fields(xspace, plane, 4):
            meta = _first(xspace, entry, 2)
            module = _text(xspace, _first(xspace, meta, 2))
            for _, stat in _fields(xspace, meta, 5):
                proto = _first(xspace, stat, 6)
                if proto is not None:
                    mod = _first(xspace, proto, 1)
                    out[module] = _op_names(xspace[mod[0]:mod[1]])
    return out


# --------------------------------------------------------------- load

def scope_chain(op_name: str, scopes: Sequence[str]) -> Tuple[str, ...]:
    """The known scopes of an ``op_name`` path, outermost first, each
    once."""
    chain: List[str] = []
    for part in op_name.split("/"):
        name = WRAPPED.match(part).group(1)
        if name in scopes and name not in chain:
            chain.append(name)
    return tuple(chain)


def _instruction(event_name: str) -> str:
    """"%fusion.2 = bf16[8]{0} fusion(...)" -> "fusion.2"."""
    return event_name.partition(" = ")[0].lstrip("%")


def load(trace_dir: str, device_line=trace_reduce.tpu_op_line):
    """``({device plane: [(op_name, start_ns, end_ns)]}, host events)``;
    host events as ``trace_reduce.load`` gives them."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    op_names = program_op_names(memoryview(Path(path).read_bytes()))
    data = ProfileData.from_file(path)  # puts device times on the host's clock
    devices: Dict[str, List] = defaultdict(list)
    host: List = []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in lines["XLA Modules"].events) if "XLA Modules" in lines else []
        for line in plane.lines:
            if device_line(plane.name, line.name):
                sink = devices[plane.name]
                for e in line.events:
                    s = e.start_ns
                    if modules:
                        module, op = _covering(modules, s), _instruction(e.name)
                    else:
                        stats = dict(e.stats)
                        if "hlo_op" not in stats:
                            continue  # the CPU's thread-pool events
                        module = f"{stats['hlo_module']}({stats['program_id']})"
                        op = stats["hlo_op"]
                    sink.append((op_names.get(module, {}).get(op, ""), s, s + e.duration_ns))
            elif plane.name == "/host:CPU":
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events)
    return dict(devices), host


def _covering(intervals, t: float) -> str:
    """Name of the last of the sorted ``(start, end, name)`` intervals
    starting at or before ``t`` that still covers it, else ""."""
    lo, hi = 0, len(intervals)
    while lo < hi:
        mid = (lo + hi) // 2
        if intervals[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    return intervals[lo - 1][2] if lo and intervals[lo - 1][1] >= t else ""


# ------------------------------------------------------------- reduce

def reduce(devices: Dict[str, List], host: List) -> Dict:
    """The numbers listed in the module docstring, in seconds unless
    named otherwise; device times are averaged over the devices."""
    if names is None:
        return {}
    bounds = sorted((s, e) for n, s, e in host if n == trace_reduce.ROUND)
    spans_of = {k: sorted((s, e) for n, s, e in host if n == k) for k in names.HOST_SPANS}
    if not bounds or not spans_of[names.CHUNK] or not any(devices.values()):
        return {}
    lo, hi = bounds[0][0], bounds[-1][1]
    chunks = [{"span": iv} for iv in spans_of[names.CHUNK] if lo <= iv[0] and iv[1] <= hi]
    for c in chunks:
        for k in (names.DISPATCH, names.FETCH):
            c[k] = next(iv for iv in spans_of[k] if c["span"][0] <= iv[0] <= c["span"][1])
    chain_of: Dict[str, Tuple[str, ...]] = {}
    own = defaultdict(float)   # chain -> busy ns
    gaps = defaultdict(float)  # (chain before, chain after) -> idle ns; None at an edge
    in_span = defaultdict(float)
    for ops in devices.values():
        leaves = []
        for op, s, e, _ in trace_reduce.self_times(ops):
            if e > lo and s < hi:
                if op not in chain_of:
                    chain_of[op] = scope_chain(op, names.DEVICE_SCOPES)
                leaves.append((max(s, lo), min(e, hi), chain_of[op]))
        idle: List[Tuple[float, float]] = []
        end, prev = lo, None
        for s, e, chain in leaves:
            if s > end:
                gaps[prev, chain] += s - end
                idle.append((end, s))
                end = s
            if e > end:
                own[chain] += e - end
                end, prev = e, chain
        if hi > end:
            gaps[prev, None] += hi - end
            idle.append((end, hi))
        for k, ivs in spans_of.items():
            in_span[k] += sum(_covered(idle, a, b) for a, b in ivs)
        starts = [s for s, _, _ in leaves]
        after = lo
        for c in chunks:
            a = bisect.bisect_left(starts, after)
            b = bisect.bisect_left(starts, c[names.FETCH][1])
            if a < b:
                c.setdefault("first", []).append(leaves[a][0])
                c.setdefault("last", []).append(max(e for _, e, _ in leaves[a:b]))
            after = c[names.FETCH][1]
    n = len(devices)
    busy, under = defaultdict(float), defaultdict(float)
    for chain, t in own.items():
        busy[chain[-1] if chain else "unscoped"] += t / n
        for sc in chain:
            under[sc] += t / n
    label = lambda c: "window" if c is None else c[-1] if c else "unscoped"  # noqa: E731
    between, idle_under = defaultdict(float), defaultdict(float)
    for (before, after), t in gaps.items():
        between[f"{label(before)} | {label(after)}"] += t / n
        for sc in set(before or ()) & set(after or ()):
            idle_under[sc] += t / n
    idle_ns = hi - lo - sum(busy.values())
    inner = in_span[names.CHUNK] / n
    host_split = {k: in_span[k] / n for k in (names.DISPATCH, names.FETCH)}
    host_split[names.CHUNK] = inner - sum(host_split.values())
    host_split["none"] = idle_ns - inner

    def seconds(d, top=None):
        return {k: v * 1e-9 for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]}

    return {
        "busy_s": sum(busy.values()) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "scopes": seconds(busy),
        "under": seconds(under),
        "idle_s": idle_ns * 1e-9,
        "idle_between": seconds(between, 20),
        "idle_under": seconds(idle_under),
        "idle_in_host_span": seconds(host_split),
        "chunks": [_chunk_row(c) for c in chunks],
    }


def _covered(idle: List[Tuple[float, float]], a: float, b: float) -> float:
    """Length of the sorted, disjoint ``idle`` intervals inside [a, b]."""
    total = 0.0
    for s, e in idle[max(0, bisect.bisect_right(idle, (a,)) - 1):]:
        if s >= b:
            break
        total += max(0.0, min(e, b) - max(s, a))
    return total


def _chunk_row(c: Dict) -> Dict:
    (c0, c1), (d0, d1), (f0, f1) = c["span"], c[names.DISPATCH], c[names.FETCH]
    row = {"chunk_ms": (c1 - c0) * 1e-6, "dispatch_ms": (d1 - d0) * 1e-6,
           "fetch_ms": (f1 - f0) * 1e-6,
           "self_ms": ((c1 - c0) - (d1 - d0) - (f1 - f0)) * 1e-6}
    if "first" in c:
        first, last = min(c["first"]), max(c["last"])
        row.update(first_op_after_dispatch_ms=(first - d0) * 1e-6,
                   last_op_before_fetch_end_ms=(f1 - last) * 1e-6,
                   fetch_after_last_op_ms=(f1 - max(f0, last)) * 1e-6)
    return row


def layer_metrics(red: Dict, rounds: int, steps: int) -> Dict[str, float]:
    """The per-layer numbers, in ms: device time per local step or per
    round under the scopes of each layer, idle time per local step
    between two ops of the local step, and host time per round in the
    dispatch and in the fetch after the chunk's last device op; device
    time per local step under ``model.attention`` only where an op
    carries that scope."""
    if not red or rounds <= 0 or steps <= 0:
        return {}
    under = red["under"]

    def per(count, *scopes):
        return 1e3 * sum(under.get(n, 0.0) for n in scopes) / count

    chunks = red["chunks"]
    out = {
        "local_step_dev_ms": per(steps, names.LOCAL_STEP),
        "step_idle_ms": 1e3 * red["idle_under"].get(names.LOCAL_STEP, 0.0) / steps,
        "merge_dev_ms": per(steps, names.APPLY),
        "project_dev_ms": per(steps, names.GRAD_PROJECT),
        "store_dev_ms": per(rounds, names.GATHER, names.SCATTER),
        "agg_dev_ms": per(rounds, names.CONTROL, names.AGGREGATE, names.SERVER),
        "data_dev_ms": per(rounds, names.SAMPLE, names.BATCHES),
        "dispatch_ms": sum(c["dispatch_ms"] for c in chunks) / rounds,
        "fetch_ms": sum(c.get("fetch_after_last_op_ms", 0.0) for c in chunks) / rounds,
    }
    if names.ATTENTION in under:
        out["attention_dev_ms"] = per(steps, names.ATTENTION)
    return out


def window_metric(ctx, name: str) -> Optional[float]:
    """``layer_metrics``' number ``name`` for a metric reader's context:
    the traced window's span reduction (``ctx.trace["spans"]``) over its
    rounds and their local steps; None where the trace has none."""
    steps = ctx.rounds * ctx.traffic["num_sampled"] * ctx.traffic["local_steps"]
    return layer_metrics(ctx.trace.get("spans", {}), ctx.rounds, steps).get(name)


# ---------------------------------------------------------------- run

def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import tempfile
    import time

    import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    wl, config, traffic, _ = harness.resolve(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("bench/spans.py: the cell needs a TPU", file=sys.stderr)
        return 1
    from repro.util import use_repo_compile_cache

    use_repo_compile_cache()
    cell = harness.build(config, traffic, args.seed)
    harness.first_round(cell, traffic, args.seed)
    out_dir = tempfile.mkdtemp(prefix="bench-spans-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        # as bench/run.py: a first short session starts the device tracer
        with jax.profiler.trace(out_dir, profiler_options=opts):
            jax.block_until_ready(jax.numpy.zeros(8) + 1)
        shutil.rmtree(out_dir)
        with jax.profiler.trace(out_dir, profiler_options=opts):
            win = harness.window(cell.trainer, args.seconds)
        t = time.perf_counter()
        red = reduce(*load(out_dir))
        reduce_s = time.perf_counter() - t
        summary = trace_reduce.summarize(*trace_reduce.load(out_dir))
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    steps = win["rounds"] * traffic["num_sampled"] * traffic["local_steps"]
    print(json.dumps({
        "workload": wl["name"], "seed": args.seed, "device": jax.devices()[0].device_kind,
        "traced_round_s": win["seconds"] / win["rounds"], "rounds": win["rounds"],
        "reduce_s": reduce_s, "metrics": layer_metrics(red, win["rounds"], steps),
        "trace": {k: summary.get(k) for k in ("busy_s", "window_s", "idle_share",
                                                "chunk_gap_ms")},
        "spans": red}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
