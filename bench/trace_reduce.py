"""From a profiler trace (``.xplane.pb``) to device busy time, idle share,
the device operations that took the most time, and idle gaps labelled by
the benchmark's host span that covers them.  An operation's time in the
breakdown is its self time: a loop's own time less that of the operations
of its body, which the trace lists inside it.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  Busy time is the union of their intervals
inside the window; the window is the host span ``bench.window`` (the
measured loop), or, where a trace has none, the extent of the device
operations.  Host spans are the events named ``bench.<name>`` on the
``/host:CPU`` plane; a gap takes the name of the span that overlaps it
most, or ``none``.  The trace may place the device's clock about a
millisecond before the host's (seen on a v5e): a thousandth of a window
of seconds, but enough to shift a gap's label where gaps are that short.
"""

from __future__ import annotations

from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _clip_named(ops, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ops
            if min(e, hi) > max(s, lo)]


def read_events(path):
    """({device plane: [(name, start_ns, end_ns)]}, [host spans])."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return devices, spans


def _self_times(ops):
    """(name, self ns) of each op: its duration less that of the ops
    nested inside it (a while loop holds the ops of its body)."""
    out, stack = [], []
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            out.append(stack.pop()[::3])
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([name, s, e, e - s])
    out.extend(x[::3] for x in stack)
    return out


def _op_name(name: str) -> str:
    """``%fusion.3 = bf16[...] ...`` -> ``fusion.3``."""
    head = name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def reduce_events(devices, spans, top: int = 10) -> dict:
    ops_all = [op for ops in devices.values() for op in ops]
    if not ops_all:
        return {}
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        lo = min(s for _, s, _ in ops_all)
        hi = max(e for _, _, e in ops_all)
    window_ns = hi - lo
    busy, gaps = [], []
    by_op: dict = {}
    for ops in devices.values():
        u = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(sum(e - s for s, e in u))
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        for name, d in _self_times(_clip_named(ops, lo, hi)):
            key = _op_name(name)
            by_op[key] = by_op.get(key, 0) + d
    host = [(n[len(SPAN_PREFIX):], s, e) for n, s, e in spans if n != WINDOW]

    def label(gs, ge):
        best, name = 0, "none"
        for n, s, e in host:
            ov = min(e, ge) - max(s, gs)
            if ov > best:
                best, name = ov, n
        return name

    gaps.sort(key=lambda g: g[0] - g[1])
    busy_s = sum(busy) / len(busy) / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window_ns / 1e9,
        "idle_share": 1.0 - busy_s / (window_ns / 1e9) if window_ns else None,
        "device_ops": [[n, ns / 1e9 / len(busy)] for n, ns in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label(s, e), (e - s) / 1e9] for s, e in gaps[:top]],
    }


def reduce_trace(trace_dir: Path) -> dict:
    """The reduction of the newest ``.xplane.pb`` under ``trace_dir``."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return {}
    return reduce_events(*read_events(files[-1]))
