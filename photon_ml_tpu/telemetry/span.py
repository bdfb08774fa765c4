"""Hierarchical span tracer.

One process-global :class:`Tracer` collects :class:`SpanRecord`\\ s from
``with span("cd/outer_iter", outer=3):`` blocks.  Nesting is tracked with a
:mod:`contextvars` variable, so spans opened on different threads (or in
different asyncio tasks) chain to the right parent without any locking on
the hot path — the only lock is taken once per span, on close, to append
the finished record.

The disabled path is near-free: :func:`span` returns a singleton no-op
context manager (no allocation, no clock read), so instrumentation can stay
on hot loops unconditionally.  Spans that exit via an exception are kept
and tagged ``failed=True`` with the exception type name.

Optionally a span can request *device-sync* timing: the enter/exit clock
reads are preceded by a barrier that drains the async XLA dispatch queue,
so the measured wall time covers device work issued inside the block
instead of just the Python time spent enqueueing it.

While the tracer is on, a live span also sits inside a
``jax.profiler.TraceAnnotation`` named by its path: when a profiler session
is active the program's spans lie in the trace's host plane, on the device
operations' timeline. Finished intervals measured elsewhere (JAX's compile
phases, ``telemetry/compile_spans.py``) enter through
:meth:`Tracer.add_interval`.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import itertools
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = [
    "SpanRecord",
    "Tracer",
    "get_tracer",
    "span",
    "timed_span",
    "union_seconds",
    "upload",
    "enable_tracing",
    "disable_tracing",
    "barrier_over",
]


@dataclasses.dataclass
class SpanRecord:
    """A finished span. ``start_s`` is seconds since the tracer's origin
    (``Tracer.origin_unix`` converts it to wall-clock time)."""

    span_id: int
    parent_id: Optional[int]
    name: str
    path: str
    depth: int
    start_s: float
    duration_s: float
    thread_id: int
    thread_name: str
    failed: bool = False
    error: Optional[str] = None
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)


class _NoopSpan:
    """Singleton returned when tracing is disabled. Accepts the same calls
    as a live span so call sites never branch."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_attrs(self, **attrs) -> "_NoopSpan":
        return self


NOOP_SPAN = _NoopSpan()

# The innermost live span for the current thread/task (None at top level).
_CURRENT: contextvars.ContextVar[Optional["_LiveSpan"]] = contextvars.ContextVar(
    "photon_ml_tpu_current_span", default=None
)


# The devices a ``device_sync`` span waits for: () is the default device
# alone. Code that spreads work over a mesh widens it with ``barrier_over``
# for as long as that work lasts.
_BARRIER_DEVICES: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "photon_ml_tpu_barrier_devices", default=()
)


@contextlib.contextmanager
def barrier_over(devices):
    """While open (in this context), every ``device_sync`` span waits for
    all of ``devices`` (an iterable of ``jax.Device``), and for the default
    device alone again once it has closed. The barrier's program for them
    is compiled on the way in when the tracer is on, so that no
    ``device_sync`` span ever holds its compile."""
    devices = tuple(devices)
    if _TRACER.enabled and _TRACER.device_sync:
        _barrier_program(devices)
    token = _BARRIER_DEVICES.set(devices)
    try:
        yield
    finally:
        _BARRIER_DEVICES.reset(token)


@functools.lru_cache(maxsize=4)
def _barrier_program(devices: tuple = ()):
    """(a trivial jitted program, an operand of it on the default device or
    on each of ``devices``), run once on each here so that no span ever
    holds its compile. One single-device program a device, not one program
    over them all: the CPU backend does not order a multi-device program
    after a single-device one on the same device (the barrier returned 7 ms
    after a 500 ms program was dispatched to a grid's last device), and
    single-device programs on one device it runs in order, as the chip does."""
    import jax
    import jax.numpy as jnp

    program = jax.jit(lambda x: x + 1)
    zero = jnp.zeros((), jnp.float32)
    operands = tuple(jax.device_put(zero, d) for d in devices) or (zero,)
    jax.block_until_ready([program(operand) for operand in operands])
    return program, operands


def _device_barrier() -> None:
    """Block until the work dispatched so far has retired on every device
    that holds work: the default device, or the devices of the innermost
    open ``barrier_over``. A device's compute stream runs programs in
    order, so a trivial program dispatched now to each of them ends after
    what was dispatched before. (A host-to-device copy does not wait for the
    compute stream: ``device_put(0.0)`` returned 1-6 ms after a 496 ms
    program was dispatched, this barrier after 495.9 ms; chip, PR 26, log 1.
    ``chip_smoke.py`` repeats the check in its engine phase and, on the
    grid's last device, in its multichip phase.)"""
    import jax

    program, operands = _barrier_program(_BARRIER_DEVICES.get())
    jax.block_until_ready([program(operand) for operand in operands])


class _LiveSpan:
    __slots__ = (
        "_tracer",
        "name",
        "attrs",
        "span_id",
        "parent_id",
        "path",
        "depth",
        "duration_s",
        "failed",
        "error",
        "_token",
        "_start",
        "_sync",
        "_annotation",
    )

    def __init__(self, tracer: "Tracer", name: str, sync: bool, attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self._sync = sync
        self.span_id = next(tracer._ids)
        self.parent_id: Optional[int] = None
        self.path = name
        self.depth = 1
        self.duration_s = 0.0
        self.failed = False
        self.error: Optional[str] = None
        self._token: Optional[contextvars.Token] = None
        self._start = 0.0
        self._annotation = None

    def set_attrs(self, **attrs) -> "_LiveSpan":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "_LiveSpan":
        parent = _CURRENT.get()
        if parent is not None:
            self.parent_id = parent.span_id
            self.path = f"{parent.path}/{self.name}"
            self.depth = parent.depth + 1
        self._token = _CURRENT.set(self)
        if self._tracer.enabled:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation(self.path)
            self._annotation.__enter__()
        if self._sync:
            _device_barrier()
        self._start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._sync:
            _device_barrier()
        end = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if self._token is not None:
            _CURRENT.reset(self._token)
        self.duration_s = end - self._start
        self.failed = exc_type is not None
        self.error = exc_type.__name__ if exc_type is not None else None
        # timed_span measures even with tracing off; only record when on
        if self._tracer.enabled:
            thread = threading.current_thread()
            self._tracer._record(
                SpanRecord(
                    span_id=self.span_id,
                    parent_id=self.parent_id,
                    name=self.name,
                    path=self.path,
                    depth=self.depth,
                    start_s=self._start - self._tracer.origin_perf,
                    duration_s=self.duration_s,
                    thread_id=thread.ident or 0,
                    thread_name=thread.name,
                    failed=self.failed,
                    error=self.error,
                    attrs=self.attrs,
                )
            )
        return False  # never swallow exceptions


def union_seconds(intervals) -> float:
    """Seconds covered by ``(start, end)`` pairs that may nest and overlap:
    the time they hold, which their sum is not."""
    total, reach = 0.0, None
    for a, b in sorted(intervals):
        if reach is None or a > reach:
            total, reach = total + (b - a), b
        elif b > reach:
            total, reach = total + (b - reach), b
    return total


class Tracer:
    """Thread-safe collector of finished spans.

    ``enabled`` gates collection: when False, :meth:`span` hands back the
    shared no-op singleton. ``device_sync`` master-switches per-span barrier
    requests (so a run can ask for wall-only timing even at instrumented
    call sites that request a sync).
    """

    def __init__(self, enabled: bool = False, device_sync: bool = True):
        self.enabled = enabled
        self.device_sync = device_sync
        self._lock = threading.Lock()
        # by span id, in the order recorded: add_interval drops by id
        self._spans: Dict[int, SpanRecord] = {}
        # (thread id, name) -> that thread's kept absorbing intervals of that
        # name, by start: what a later, enclosing one of the same name drops
        self._kept: Dict[tuple, List[SpanRecord]] = {}
        self._ids = itertools.count(1)
        # Anchor for converting perf_counter readings to wall-clock time.
        self.origin_perf = time.perf_counter()
        self.origin_unix = time.time()

    # ------------------------------------------------------------- control
    def span(self, name: str, device_sync: bool = False, **attrs):
        if not self.enabled:
            return NOOP_SPAN
        return _LiveSpan(self, name, device_sync and self.device_sync, attrs)

    def add_interval(
        self, name: str, start: float, end: float, absorb_nested: bool = False, **attrs
    ) -> None:
        """Record a finished interval measured elsewhere (``start``/``end``
        are ``perf_counter`` readings) as a child of the calling thread's
        live span. ``absorb_nested`` drops the same thread's earlier
        absorbing intervals *of the same name* that lie wholly inside this
        one. It is flood control and no more: JAX reports a trace of every
        function traced inside another, and those leave one span. Intervals
        of different names still nest (a kernel body traced inside a
        lowering, a compile inside a trace) and threads overlap, so whoever
        wants a time takes :func:`union_seconds` of what it reads."""
        if not self.enabled:
            return
        parent = _CURRENT.get()
        thread = threading.current_thread()
        record = SpanRecord(
            span_id=next(self._ids),
            parent_id=None if parent is None else parent.span_id,
            name=name,
            path=name if parent is None else f"{parent.path}/{name}",
            depth=1 if parent is None else parent.depth + 1,
            start_s=start - self.origin_perf,
            duration_s=end - start,
            thread_id=thread.ident or 0,
            thread_name=thread.name,
            attrs=attrs,
        )
        with self._lock:
            if absorb_nested:
                kept = self._kept.setdefault((record.thread_id, name), [])
                while kept and kept[-1].start_s >= record.start_s:
                    self._spans.pop(kept.pop().span_id, None)
                kept.append(record)
            self._spans[record.span_id] = record

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._kept.clear()

    # ------------------------------------------------------------- access
    def _record(self, record: SpanRecord) -> None:
        with self._lock:
            self._spans[record.span_id] = record

    def spans(self, seal: bool = False) -> List[SpanRecord]:
        """The spans recorded so far. ``seal`` makes them final: no later
        interval drops one of them (for a sink that writes them where they
        cannot be taken back)."""
        with self._lock:
            if seal:
                self._kept.clear()
            return list(self._spans.values())

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


_TRACER = Tracer(enabled=False)


def get_tracer() -> Tracer:
    """The process-global tracer used by :func:`span`."""
    return _TRACER


def span(name: str, device_sync: bool = False, **attrs):
    """Open a span on the global tracer. Near-free when tracing is off:
    a single attribute check then the shared no-op context manager."""
    t = _TRACER
    if not t.enabled:
        return NOOP_SPAN
    return _LiveSpan(t, name, device_sync and t.device_sync, attrs)


def current_span():
    """The innermost span open in this thread or task, for attrs that are
    known only deep inside its block; the no-op span where none is open or
    the tracer is off."""
    live = _CURRENT.get()
    return live if live is not None and _TRACER.enabled else NOOP_SPAN


def upload(what: str, put):
    """``put()``, which hands host arrays to a device and returns what it
    made there, inside a span ``data/upload`` (``what``: ``features``,
    ``plan``, ``kron``, ``re_bucket``, ``rows``, ``tile``) while the tracer
    is on. The span then waits for those arrays with
    ``jax.block_until_ready``, not with the span barrier: a host-to-device
    copy does not follow the compute stream the barrier orders. Its ``bytes`` are what
    landed on the devices, every replica counted. With the tracer off this
    is ``put()``, and nothing waits that did not wait before."""
    if not _TRACER.enabled:
        return put()
    import jax

    with span("data/upload", what=what) as uploading:
        out = put()
        arrays = [a for a in jax.tree.leaves(out) if isinstance(a, jax.Array)]
        jax.block_until_ready(arrays)
        uploading.set_attrs(bytes=sum(
            shard.data.nbytes for a in arrays for shard in a.addressable_shards
        ))
    return out


def timed_span(name: str, **attrs) -> _LiveSpan:
    """An ALWAYS-measuring span: times the block whether or not tracing is
    on, exposing ``duration_s``/``failed``/``error`` afterwards, and lands
    in the tracer only when it is enabled. This is the single timing path
    behind ``utils.timer.Timer``/``Timed``."""
    return _LiveSpan(_TRACER, name, False, attrs)


def enable_tracing(device_sync: bool = True, clear: bool = True) -> Tracer:
    """Turn on the global tracer (optionally clearing prior spans). The
    first call also registers the listeners that turn JAX's compile phases
    into spans (``compile_spans``) and the one that counts the bytes
    ``fetch_global`` brings to the host (``mesh.fetch_bytes``)."""
    from photon_ml_tpu.telemetry import compile_spans, metrics

    compile_spans.register()
    metrics.count_mesh_fetches()
    if device_sync:
        _barrier_program(_BARRIER_DEVICES.get())
    if clear:
        _TRACER.clear()
    _TRACER.device_sync = device_sync
    _TRACER.enabled = True
    return _TRACER


def disable_tracing() -> None:
    _TRACER.enabled = False
