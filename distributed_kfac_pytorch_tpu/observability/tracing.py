"""The program's one recorder of host spans and counters.

``span(name, **attrs)`` is a context manager that records ``(id, parent
id, name, start_ns, end_ns, attrs)``: the parent is the span open on
this thread, attributes may be set while it is open (``s.set(...)``),
and the spans of one training step carry ``step=`` (the global step),
their shared identifier. It also enters
``jax.profiler.TraceAnnotation(name, **attrs)``, so inside a profiler
session the same span lies in the ``.xplane.pb`` on the profiler's own
clock, beside the device's operations. In-memory timestamps are
``time.time_ns()``. ``count(name, n)`` and ``gauge(name, value)`` keep
plain numbers by name.

Always on, in memory, bounded: a ring of the newest :data:`RING_SPANS`
spans, plus per-name aggregates (``count``, ``total_ms``, ``self_ms``,
``max_ms``) that are never dropped. A span's self time is its duration
minus what its child spans cover. Nothing is written on the step path:
``spans()``, ``counters()`` and ``snapshot_trace()`` read the recorder,
and the JSONL sink (:mod:`observability.sink`) embeds the last two in
each epoch record so ``observability.report`` prints the breakdown
offline.

The reference's ``utils.py`` trace table (``trace`` / ``get_trace`` /
``print_trace`` / ``clear_trace``, re-exported from
``distributed_kfac_pytorch_tpu.utils``) and ``record`` are thin forms
over the same recorder. ``trace(sync=True)`` calls
``jax.block_until_ready`` on the result (the XLA analogue of the
reference's pre/post ``backend.barrier()`` — without it, timings
measure async dispatch only). Reference bugs fixed (SURVEY.md §8):
``clear_trace`` actually clears (utils.py:11-12 rebinds a local) and
``get_trace`` has no undefined variable (utils.py:18-19 ``max_times``).

Stages *inside* the jitted step are attributed by the profiler scopes
in :mod:`observability.profiling` instead. The span and counter names
the program itself records (``kfac/host/*``, ``kfac/build/*``,
``kfac/state_bytes/*``, ``kfac/factors/*``, ``kfac/inverses/*``) are
listed in README.md's observability section with what each is for.
"""

from __future__ import annotations

import collections
import functools
import itertools
import threading
import time
from typing import Callable, NamedTuple

import jax

RING_SPANS = 65536


class SpanRecord(NamedTuple):
    """One closed span. ``parent`` is 0 for a root."""
    id: int
    parent: int
    name: str
    start_ns: int
    end_ns: int
    attrs: dict


class Span:
    """An open span; what ``with span(...) as s`` binds."""

    __slots__ = ('_recorder', '_stack', '_annotation', '_child_ns',
                 '_cancelled', 'id', 'parent', 'name', 'attrs',
                 'start_ns', 'end_ns')

    def __init__(self, recorder: 'Recorder', name: str, attrs: dict):
        self._recorder = recorder
        self._child_ns = 0
        self._cancelled = False
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> None:
        """Set attributes of the in-memory record while the span is
        open (the profiler's annotation keeps those given at entry)."""
        self.attrs.update(attrs)

    def cancel(self) -> None:
        """Leave no record of this span (its children keep theirs)."""
        self._cancelled = True

    @property
    def duration_ms(self) -> float:
        """Of a span that has closed."""
        return (self.end_ns - self.start_ns) / 1e6

    def __enter__(self) -> 'Span':
        recorder = self._recorder
        stack = self._stack = recorder._stack()
        self.parent = stack[-1].id if stack else 0
        self.id = next(recorder._ids)
        stack.append(self)
        self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                        **self.attrs)
        self._annotation.__enter__()
        self.start_ns = recorder._clock()
        return self

    def __exit__(self, *exc) -> bool:
        recorder = self._recorder
        end_ns = self.end_ns = recorder._clock()
        self._annotation.__exit__(*exc)
        stack = self._stack
        while stack and stack.pop() is not self:
            pass
        if self._cancelled:
            return False
        duration = end_ns - self.start_ns
        if stack:
            stack[-1]._child_ns += duration
        recorder._closed((self.id, self.parent, self.name, self.start_ns,
                          end_ns, self.attrs), duration,
                         duration - self._child_ns)
        return False


class Recorder:
    """Spans, their per-name aggregates, and numbers by name.

    The program uses the one module-level instance through the
    functions below; a test may make its own (``ring`` and ``clock``
    exist for that)."""

    def __init__(self, ring: int = RING_SPANS,
                 clock: Callable[[], int] = time.time_ns):
        self._clock = clock
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)
        self._ring: collections.deque = collections.deque(maxlen=ring)
        # name -> [count, total_ns, self_ns, max_ns]
        self._aggregates: dict[str, list[int]] = {}
        self._numbers: dict[str, float] = {}

    def _stack(self) -> list[Span]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _closed(self, record: tuple, duration: int, self_ns: int
                ) -> None:
        """``record``: a :class:`SpanRecord`'s fields, kept as a plain
        tuple on the step path and wrapped where it is read."""
        with self._lock:
            self._ring.append(record)
            agg = self._aggregates.get(record[2])
            if agg is None:
                agg = self._aggregates[record[2]] = [0, 0, 0, 0]
            agg[0] += 1
            agg[1] += duration
            agg[2] += self_ns
            if duration > agg[3]:
                agg[3] = duration

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def current(self) -> Span | None:
        """The innermost span open on this thread."""
        stack = self._stack()
        return stack[-1] if stack else None

    def record(self, name: str, seconds: float) -> None:
        """A closed root span of ``seconds`` that ends now, for a
        caller that already holds a timing."""
        end_ns = self._clock()
        duration = int(seconds * 1e9)
        self._closed((next(self._ids), 0, name, end_ns - duration,
                      end_ns, {}), duration, duration)

    def count(self, name: str, n: float = 1) -> None:
        with self._lock:
            self._numbers[name] = self._numbers.get(name, 0) + n

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._numbers[name] = value

    def spans(self, name: str | None = None) -> list[SpanRecord]:
        """The ring's spans, oldest first (in the order they closed)."""
        with self._lock:
            held = list(self._ring)
        return [SpanRecord._make(s) for s in held
                if name is None or s[2] == name]

    def counters(self) -> dict[str, float]:
        with self._lock:
            return dict(self._numbers)

    def snapshot(self) -> dict[str, dict[str, float]]:
        with self._lock:
            aggregates = {k: tuple(v)
                          for k, v in self._aggregates.items()}
        return {name: {'mean_ms': total / n / 1e6,
                       'total_ms': total / 1e6, 'count': n,
                       'self_ms': own / 1e6, 'max_ms': longest / 1e6}
                for name, (n, total, own, longest) in aggregates.items()}

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._aggregates.clear()
            self._numbers.clear()


_RECORDER = Recorder()

span = _RECORDER.span
current = _RECORDER.current
count = _RECORDER.count
gauge = _RECORDER.gauge
spans = _RECORDER.spans
counters = _RECORDER.counters
record = _RECORDER.record


def snapshot_trace() -> dict[str, dict[str, float]]:
    """``{name: {'mean_ms', 'total_ms', 'count', 'self_ms',
    'max_ms'}}`` over every span the process closed (the aggregates
    outlive the ring). The sink embeds this into epoch records so the
    report CLI can reconstruct the per-stage breakdown offline."""
    return _RECORDER.snapshot()


def clear_trace() -> None:
    """Forget every span, aggregate, counter and gauge."""
    _RECORDER.clear()


def trace(sync: bool = False, name: str | None = None) -> Callable:
    """Decorator recording each call as a span.

    Args:
      sync: block on the result (and on the array arguments before
        starting) so the measurement covers device execution, not just
        dispatch.
      name: span name (defaults to the function's __name__).
    """
    def decorator(fn):
        key = name or fn.__name__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if sync:
                jax.block_until_ready(
                    [a for a in args if isinstance(a, jax.Array)])
            with span(key):
                out = fn(*args, **kwargs)
                if sync:
                    jax.block_until_ready(out)
            return out

        return wrapper

    return decorator


def get_trace(average: bool = True, max_history: int | None = None
              ) -> dict[str, float]:
    """Per-name mean (or total) duration in seconds.

    ``max_history`` restricts to the most recent N spans of each name
    (of those the ring still holds).
    """
    if not max_history:
        key = 'mean_ms' if average else 'total_ms'
        return {name: row[key] / 1e3
                for name, row in snapshot_trace().items()}
    by_name: dict[str, list[int]] = {}
    for s in spans():
        by_name.setdefault(s.name, []).append(s.end_ns - s.start_ns)
    out = {}
    for key, times in by_name.items():
        window = times[-max_history:]
        total = sum(window) / 1e9
        out[key] = total / len(window) if average else total
    return out


def print_trace(average: bool = True, max_history: int | None = None
                ) -> None:
    for key, val in sorted(get_trace(average, max_history).items()):
        print(f'{key}: {val * 1000:.3f} ms')
