"""Device time of the round's named stages, from a profiler trace and the
compiled round's HLO text.

The program names the stages of its round with ``jax.named_scope``
(``wssl.client``, ``wssl.server``, ``wssl.optimizer``, ``wssl.validate``,
``wssl.aggregate``; ``attn_core`` inside attention).  The names reach each
compiled instruction's ``op_name`` metadata, which the trace's ``XLA Ops``
events do not carry: an event names the instruction (``fusion.2913``), so
the join goes through the executable's own HLO text
(``Compiled.as_text()``), parsed by :func:`hlo_op_paths`.

An op's stage is the outermost ``wssl.<stage>`` token of its path,
wherever the transforms put it (``transpose(jvp(wssl.client))/...``).
``other`` is an op of the module with no stage, ``unmapped`` an op the
module text does not name; ``attn_core`` cuts across the stages: every op
whose path carries it.  Times are self times inside the window (see
``trace_reduce``), averaged over devices like ``busy_s``.
"""

from __future__ import annotations

import bisect
import re
import statistics

from trace_reduce import (SPAN_PREFIX, WINDOW, _clip_named, _op_name,
                          _self_times)

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=%]+)\s+=\s")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_STAGE = re.compile(r"\bwssl\.\w+")
ATTN = "attn_core"
OTHER = "other"
UNMAPPED = "unmapped"


def hlo_op_paths(text: str) -> dict:
    """{instruction name: its ``op_name``, '' where it has none} of every
    instruction of an HLO module's text (names are unique in a module)."""
    paths = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            p = _OP_NAME.search(line)
            paths[m.group(1)] = p.group(1) if p else ""
    return paths


def stage_of(name: str, op_paths: dict) -> str:
    """The stage of the trace's op ``name``: ``wssl.<stage>``, ``other``
    or ``unmapped``."""
    path = op_paths.get(_op_name(name))
    if path is None:
        return UNMAPPED
    m = _STAGE.search(path)
    return m.group(0) if m else OTHER


def _window(ops_all, spans):
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if windows:
        return min(s for s, _ in windows), max(e for _, e in windows)
    return min(s for _, s, _ in ops_all), max(e for _, _, e in ops_all)


def by_scope(devices, spans, op_paths: dict) -> dict:
    """Seconds of device self time inside the window per stage, plus
    ``attn_core`` across them; {} where the trace has no device op."""
    ops_all = [op for ops in devices.values() for op in ops]
    if not ops_all:
        return {}
    lo, hi = _window(ops_all, spans)
    out: dict = {}
    for ops in devices.values():
        for name, d in _self_times(_clip_named(ops, lo, hi)):
            stage = stage_of(name, op_paths)
            out[stage] = out.get(stage, 0) + d
            if ATTN in op_paths.get(_op_name(name), ""):
                out[ATTN] = out.get(ATTN, 0) + d
    return {k: ns / 1e9 / len(devices) for k, ns in out.items()}


def top_ops(devices, spans, op_paths: dict, stage: str,
            top: int = 10) -> list:
    """[name, seconds] of the ``top`` ops of ``stage`` with most self
    time inside the window, averaged over devices like :func:`by_scope`."""
    ops_all = [op for ops in devices.values() for op in ops]
    if not ops_all:
        return []
    lo, hi = _window(ops_all, spans)
    out: dict = {}
    for ops in devices.values():
        for name, d in _self_times(_clip_named(ops, lo, hi)):
            if stage_of(name, op_paths) == stage:
                key = _op_name(name)
                out[key] = out.get(key, 0) + d
    return [[n, ns / 1e9 / len(devices)] for n, ns in
            sorted(out.items(), key=lambda kv: -kv[1])[:top]]


def dispatch_offsets(devices, spans) -> list:
    """Seconds from the start of each ``bench.dispatch`` span to the first
    device op of its call, per device, in trace order.  A call's ops are
    those that start between the midpoints of the host's idle time around
    it: from halfway between the previous ``bench.block`` (or dispatch)
    span's end and this dispatch's start, to the same point before the
    next dispatch.  A negative offset puts the device's clock before the
    host's by at least that much."""
    disp = sorted((s, e) for n, s, e in spans
                  if n == SPAN_PREFIX + "dispatch")
    ends = sorted(e for n, s, e in spans
                  if n in (SPAN_PREFIX + "block", SPAN_PREFIX + "dispatch"))
    bounds = []
    for s, _ in disp:
        k = bisect.bisect_right(ends, s)
        bounds.append((ends[k - 1] + s) / 2 if k else float("-inf"))
    bounds.append(float("inf"))
    out = []
    for ops in devices.values():
        starts = sorted(s for _, s, _ in ops)
        for i, (s, _) in enumerate(disp):
            j = bisect.bisect_left(starts, bounds[i])
            if j < len(starts) and starts[j] < bounds[i + 1]:
                out.append((starts[j] - s) / 1e9)
    return out


def offset_summary(offsets: list) -> dict:
    """min and median of :func:`dispatch_offsets`, in milliseconds."""
    if not offsets:
        return {}
    return {"calls": len(offsets), "min_ms": 1e3 * min(offsets),
            "median_ms": 1e3 * statistics.median(offsets)}
