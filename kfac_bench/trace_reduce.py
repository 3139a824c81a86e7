"""From a profiler trace to numbers: busy time, time per scope, the
operations that took most time and the longest idle gaps.

Two halves. :func:`load` reads the ``.xplane.pb`` a ``jax.profiler``
session wrote (with nothing but JAX) into plain lists of events;
:func:`reduce` turns those lists into numbers. The second half is what
the test checks on a small recorded trace kept as JSON beside it.

An event is ``[name, start_ns, duration_ns, scope]``: ``scope`` is the
``jax.named_scope`` path the compiler kept for the operation (its HLO
instruction's ``op_name`` metadata), or '' where it kept none.
"""

from __future__ import annotations

import glob
import os

import bisect
import re

DEVICE_PLANE = '/device:TPU:'
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    if not found:
        raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
    return found[-1]


# -- the scopes ------------------------------------------------------------
#
# A v5e trace's device events carry no scope: an event's name is its HLO
# instruction's text and its stats are times (my chip run, PR 24). The
# scope is in the HLO: the trace's ``/host:metadata`` plane keeps every
# module's HloProto, and each instruction's ``metadata={op_name=...}``
# holds the ``jax.named_scope`` path. So: parse the plane (a dozen lines
# of protobuf wire format, XSpace's field numbers below), print each
# module with XLA's own printer, and map instruction name -> op_name. An
# event belongs to the module whose run (``XLA Modules`` line) holds it.

def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            byte = buf[i]
            i += 1
            key |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                break
        number, wire = key >> 3, key & 7
        if wire == 0:
            value = shift = 0
            while True:
                byte = buf[i]
                i += 1
                value |= (byte & 0x7F) << shift
                shift += 7
                if byte < 0x80:
                    break
        elif wire == 2:
            size = shift = 0
            while True:
                byte = buf[i]
                i += 1
                size |= (byte & 0x7F) << shift
                shift += 7
                if byte < 0x80:
                    break
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f'wire type {wire} in a trace')
        yield number, wire, value


def _first(buf: bytes, number: int):
    return next((v for n, _, v in _fields(buf) if n == number), None)


_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?(%?[\w.\-]+) = .*?op_name="([^"]*)"', re.M)


def module_scopes(path: str) -> dict[str, dict[str, str]]:
    """``{module name: {instruction name: op_name}}`` from the HLO the
    trace itself carries. XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4 (map: value = 2); XEventMetadata.name = 2,
    .stats = 5; XStat.bytes_value = 6; HloProto.hlo_module = 1."""
    from jax._src.lib import xla_client

    with open(path, 'rb') as f:
        space = f.read()
    out = {}
    for number, _, plane in _fields(space):
        if number != 1 or _first(plane, 2) != b'/host:metadata':
            continue
        for field, _, entry in _fields(plane):
            if field != 4:
                continue
            meta = _first(entry, 2)
            name = (_first(meta, 2) or b'').decode()
            for f2, _, stat in _fields(meta):
                proto = _first(stat, 6) if f2 == 5 else None
                if not proto:
                    continue
                module = xla_client._xla.HloModule \
                    .from_serialized_hlo_module_proto(_first(proto, 1))
                out[name] = {
                    m.group(1).lstrip('%'): m.group(2)
                    for m in _INSTRUCTION.finditer(module.to_string())}
    return out


def _instruction_of(event_name: str) -> str:
    return event_name.split(' = ', 1)[0].strip().lstrip('%')


def load(path: str, host_prefix: str = 'bench/') -> dict:
    """``{'device': {plane: [event, ...]}, 'host': [event, ...]}``.
    Device events are those of each TPU plane's ``XLA Ops`` line, each
    with its scope; host events are the spans whose name starts with
    ``host_prefix`` (the benchmark's own), from any host plane."""
    from jax.profiler import ProfileData

    scopes = module_scopes(path)
    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns),
                             ''] for e in line.events
                            if e.name.startswith(host_prefix))
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        runs = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                       scopes.get(e.name, {}))
                      for e in lines[MODULES_LINE].events
                      ) if MODULES_LINE in lines else []
        starts = [r[0] for r in runs]
        events = []
        for e in lines[OPS_LINE].events:
            start = int(e.start_ns)
            at = bisect.bisect_right(starts, start) - 1
            names = runs[at][2] if at >= 0 and start <= runs[at][1] else {}
            events.append([e.name[:160], start, int(e.duration_ns),
                           names.get(_instruction_of(e.name), '')])
        device[plane.name] = events
    return {'device': device, 'host': host}


def _union(intervals):
    """Merged ``[start, end]`` list of ``(start, end)`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def scope_seconds(loaded: dict, fragments: list[str]) -> float:
    """Seconds, averaged over the device planes, in which an operation
    whose scope holds one of ``fragments`` ran: the union of those
    events' intervals, since a ``while`` and the operations of its body
    are events of their own, one inside the other."""
    planes = [p for p in loaded['device'].values() if p]
    total = 0
    for events in planes:
        total += sum(e - s for s, e in _union(
            (start, start + dur) for _, start, dur, scope in events
            if any(f in scope for f in fragments)))
    return total / 1e9 / max(len(planes), 1)


def reduce(loaded: dict, top: int = 10) -> dict:
    """Numbers of one trace.

    ``busy_s``: seconds in which an operation ran on the device (union
    of its events' intervals), averaged over the device planes.
    ``span_s``: first start to last end of device work, likewise.
    ``device_ops``: the ``top`` operation
    names by summed time; ``idle_gaps``: the ``top`` longest gaps
    between device operations, each named by the host span that covers
    the gap's middle.
    """
    planes = [p for p in loaded['device'].values() if p]
    if not planes:
        return {'busy_s': 0.0, 'span_s': 0.0, 'device_ops': [],
                'idle_gaps': [], 'events': 0}
    n = len(planes)
    busy = span = 0.0
    by_op: dict[str, float] = {}
    gaps = []
    for events in planes:
        merged = _union((s, s + d) for _, s, d, _ in events)
        busy += sum(e - s for s, e in merged) / 1e9
        span += (merged[-1][1] - merged[0][0]) / 1e9
        gaps.extend((b[0] - a[1], a[1]) for a, b in zip(merged,
                                                        merged[1:]))
        for name, _, dur, _ in events:
            by_op[name] = by_op.get(name, 0.0) + dur / 1e9 / n
    host = sorted(loaded.get('host', []), key=lambda e: e[1])

    def doing(at_ns):
        inside = [e for e in host if e[1] <= at_ns <= e[1] + e[2]]
        return min(inside, key=lambda e: e[2])[0] if inside else \
            'host:unattributed'

    gaps.sort(reverse=True)
    by_gap: dict[str, float] = {}
    for dur, start in gaps[:200]:
        name = doing(start + dur // 2)
        by_gap[name] = by_gap.get(name, 0.0) + dur / 1e9 / n
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {'busy_s': busy / n, 'span_s': span / n,
            'device_ops': [[k, v] for k, v in rank(by_op)],
            'idle_gaps': [[k, v] for k, v in rank(by_gap)],
            'events': sum(len(p) for p in planes)}
