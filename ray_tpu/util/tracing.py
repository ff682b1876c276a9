"""Distributed tracing: spans around task/actor submit + execute, inside
the decode engine and the serve plane, with W3C trace context propagated
in the TaskSpec.

Role-equivalent of ray: python/ray/util/tracing/tracing_helper.py:34
(_OpenTelemetryProxy + the submit/execute span wrappers, context carried
in TaskOptions["_ray_trace_ctx"]).  Design differences, TPU-image
reality: the OpenTelemetry *API* is available but no SDK is baked in, so
spans are recorded by a built-in recorder and BRIDGED to OpenTelemetry
when an application has installed a real TracerProvider before the
operator's switch turned tracing on — `pip install opentelemetry-sdk` +
set_tracer_provider, then ``tracing.enable()``.  The bridge is resolved
there, once: importing the OpenTelemetry API scans the installed
packages (200 ms in a decode replica), which no span may pay.

One switch, turned on two ways: the operator says so
(``ray_tpu.util.tracing.enable()`` in the driver, ``RT_TRACING_ENABLED=1``
cluster-wide; workers inherit the env), or a jax profiler session is
running in this process (``jax.profiler.start_trace`` .. ``stop_trace``).
In the second case every span is also a ``jax.profiler.TraceAnnotation``
of the same name, so it shows above the device's operations in xprof /
Perfetto on the profiler's own clock.  Off, a span site costs the
``enabled()`` check and nothing else.

Beside the switch, ``startup()`` starts a span that is recorded whatever
the switch says: the start-up time line of a process (``rt.start.*``,
``serve.start.*``, ``train.start.*``, ``llm.start.*``) and its XLA
builds (``xla.trace`` / ``xla.lower`` / ``xla.compile``,
util/compile_cache.py).  Start-up happens before any profiler session;
these number a dozen a process and three a compiled program (about 170
in a 7B replica's start), and none sits on a per-task, per-step or
per-token path.  ``record()`` does the same for a span whose two ends
are already known: ``rt.stall``, one for each stop of a process's io
loop from 20 ms (core/stall.py; at most 64 a push interval a process).

The recorder: ``start_ns`` / ``end_ns`` are integer nanoseconds of
``time.time_ns()`` (the processes of one host share that clock); a
finished span is one tuple ``(name, trace_id, span_id, parent_id,
start_ns, end_ns, attrs)`` in a bounded in-process ring.  The runtime's
push loop drains the ring into the push it already sends the GCS every
``metrics_push_interval_s`` (core/runtime.py), the GCS keeps the newest
spans of the whole cluster in a table of their own, and ``collect()``
reads them back, also after the process that recorded them is gone.
"""

from __future__ import annotations

import contextvars
import itertools
import os
import secrets
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

RING_SPANS = 65536  # spans a process keeps; what a push can carry at most

_enabled: Optional[bool] = None  # the operator's switch; None = read env on first use
_SPANS: deque = deque(maxlen=RING_SPANS)  # newest-last ring of finished spans
_LOCK = threading.Lock()
_undrained = 0  # spans finished since the last drain()

#: current span context: (trace_id_hex32, span_id_hex16) or None
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "rt_trace_ctx", default=None
)

CARRIER_KEY = "traceparent"  # W3C trace context header
#: a spawned process finds its spawner's context here (core/node.py,
#: core/raylet.py set it in the child's environment, never in their own)
START_ENV = "RT_TRACEPARENT"

#: entered and not yet left, oldest first (what a stalled process names)
_OPEN: Dict[int, str] = {}

_otel: Any = None  # the bridge's handles, resolved when the switch turns on
_annotation: Any = None  # jax.profiler.TraceAnnotation, once jax is imported


def _reseed() -> None:
    """Ids are a per-process random prefix plus a counter."""
    global _TRACE_PREFIX, _SPAN_PREFIX, _ids, _undrained
    _TRACE_PREFIX = secrets.token_hex(12)
    _SPAN_PREFIX = secrets.token_hex(4)
    _ids = itertools.count(1)
    _SPANS.clear()  # a forked child must not export its parent's spans
    _undrained = 0


_reseed()
os.register_at_fork(after_in_child=_reseed)


def _next_id() -> str:
    return f"{next(_ids) & 0xFFFFFFFF:08x}"


def enable() -> None:
    global _enabled, _otel
    _enabled = True
    _otel = _resolve_otel()
    os.environ["RT_TRACING_ENABLED"] = "1"  # workers spawned later inherit


def disable() -> None:
    global _enabled, _otel
    _enabled = False
    _otel = None
    # mirror enable(): workers spawned from now on must not inherit a
    # stale flag and keep recording spans forever
    os.environ.pop("RT_TRACING_ENABLED", None)


def profiling() -> bool:
    """True while a jax profiler session runs in this process.  Asks jax
    only where jax is already imported: the GCS, the raylet and a driver
    that keeps off the chip must not import it for this."""
    global _annotation
    ann = _annotation
    if ann is None:
        mod = sys.modules.get("jax.profiler")
        ann = getattr(mod, "TraceAnnotation", None)  # None while it imports
        if ann is None:
            return False
        _annotation = ann
    return ann.is_enabled()


def enabled() -> bool:
    global _enabled
    if _enabled is None:
        _enabled = False
        if os.environ.get("RT_TRACING_ENABLED", "") in ("1", "true", "True"):
            enable()
    return _enabled or profiling()


# -- context propagation (W3C traceparent) ---------------------------------


def traceparent(trace_id: str, span_id: str) -> str:
    """The W3C header that names a span as a parent."""
    return f"00-{trace_id}-{span_id}-01"


def inject() -> Optional[Dict[str, str]]:
    """Carrier dict for the current trace context, to ride a TaskSpec.
    Outside any span it names this process's start-up (``startup``), and
    where there was none starts a fresh trace (every task belongs to
    some trace once tracing is on)."""
    cur = _CURRENT.get() or _start
    if cur is None:
        cur = (_TRACE_PREFIX + _next_id(), _SPAN_PREFIX + _next_id())
    return {CARRIER_KEY: traceparent(*cur)}


def _extract(carrier: Optional[Dict[str, str]]):
    if not carrier:
        return None
    try:
        _ver, trace_id, span_id, _flags = carrier[CARRIER_KEY].split("-")
        return (trace_id, span_id)
    except (KeyError, ValueError):
        return None


#: what start-up spans hang under where no span is open and no carrier is
#: given: the spawner's context, then this process's own root
#: (``rt.start.cluster`` in a driver, ``rt.start.boot`` in a worker)
_spawner = _extract({CARRIER_KEY: os.environ.get(START_ENV, "")})
_start = _spawner


def current() -> Optional[Tuple[str, str]]:
    """(trace_id, span_id) of the span this code runs under, or None."""
    return _CURRENT.get()


# -- spans -----------------------------------------------------------------


class Span:
    """One span.  The clock starts when it is made.  As a context
    manager it is also the ambient parent of spans started inside it,
    an OpenTelemetry current span and, while a profiler session runs, a
    TraceAnnotation.  Where a ``with`` block cannot hold it (across the
    ``yield`` of an async generator, whose caller's context a
    context-variable write would leak into), keep the object and call
    ``finish()``."""

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "start_ns", "end_ns",
        "attrs", "_token", "_otel_span", "_otel_token", "_ann",
    )

    def __init__(self, name: str, parent, attrs: Dict[str, Any]):
        """``parent``: (trace_id or None, parent span id or None), or
        None for the root of a fresh trace."""
        self.name = name
        self.trace_id = (parent and parent[0]) or _TRACE_PREFIX + _next_id()
        self.span_id = _SPAN_PREFIX + _next_id()
        self.parent_id = parent[1] if parent else None
        self.attrs = attrs
        self.end_ns = None
        self._token = self._otel_token = self._ann = None
        self._otel_span = _otel_start(self) if _otel is not None else None
        self.start_ns = time.time_ns()

    def __enter__(self):
        self._token = _CURRENT.set((self.trace_id, self.span_id))
        _OPEN[id(self)] = self.name
        if self._otel_span is not None:
            self._otel_token = _otel[1].attach(
                _otel[2].set_span_in_context(self._otel_span)
            )
        if profiling():
            self._ann = _annotation(
                self.name, trace_id=self.trace_id, span_id=self.span_id,
                parent_id=self.parent_id or "",
            )
            self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        _CURRENT.reset(self._token)
        _OPEN.pop(id(self), None)
        if self._otel_token is not None:
            _otel[1].detach(self._otel_token)
        self.finish(exc)
        return False

    def finish(self, exc: Optional[BaseException] = None,
               end_ns: Optional[int] = None) -> None:
        """``end_ns``: the end someone else's clock took (JAX's compile
        events carry both of theirs); now otherwise."""
        global _undrained
        self.end_ns = end_ns or time.time_ns()
        if exc is not None:
            self.attrs["error"] = type(exc).__name__
        if self._otel_span is not None:
            if exc is not None:
                self._otel_span.record_exception(exc)
            self._otel_span.end()
        row = (self.name, self.trace_id, self.span_id, self.parent_id,
               self.start_ns, self.end_ns, self.attrs)
        with _LOCK:
            _SPANS.append(row)
            _undrained += 1


def _resolve_otel():
    """(tracer, context module, trace module, propagator) IFF the app
    installed a real provider (the API's default ProxyTracerProvider is a
    no-op — bridging to it would just burn cycles), else None."""
    try:
        from opentelemetry import context as otel_ctx
        from opentelemetry import trace as otel_trace
        from opentelemetry.trace.propagation.tracecontext import (
            TraceContextTextMapPropagator,
        )
    except ImportError:
        return None
    provider = otel_trace.get_tracer_provider()
    if type(provider).__name__ in (
        "ProxyTracerProvider", "NoOpTracerProvider",
    ):
        return None
    return (otel_trace.get_tracer("ray_tpu"), otel_ctx, otel_trace,
            TraceContextTextMapPropagator())


def _otel_start(s: Span):
    parent_ctx = None
    if s.parent_id:
        parent_ctx = _otel[3].extract({
            CARRIER_KEY: traceparent(s.trace_id, s.parent_id),
        })
    return _otel[0].start_span(s.name, context=parent_ctx, attributes=s.attrs)


def span(name: str, carrier: Optional[Dict[str, str]] = None,
         **attrs) -> Span:
    """Start a span.  ``carrier``: remote parent context (a TaskSpec's
    trace_ctx); otherwise the ambient context is the parent."""
    parent = _extract(carrier) if carrier is not None else _CURRENT.get()
    return Span(name, parent, attrs)


def root(name: str, **attrs) -> Span:
    """Start a span that is the root of a fresh trace whatever the
    ambient context (a loop that serves many requests)."""
    return Span(name, None, attrs)


def startup(name: str, carrier: Optional[Dict[str, str]] = None,
            root: bool = False, **attrs) -> Span:
    """Start a span of a process's start-up: like ``span``, but the
    caller does not ask ``enabled()`` first, so it is always recorded.
    Outside any open span and without a carrier it hangs under this
    process's start-up root.  ``root``: this span is that root from now
    on, and itself hangs under the spawner's context (``START_ENV``),
    where there is one."""
    global _start
    if carrier is not None:
        parent = _extract(carrier)
    elif root:
        parent = _spawner
    else:
        parent = _CURRENT.get() or _start
    s = Span(name, parent, attrs)
    if root:
        _start = (s.trace_id, s.span_id)
    return s


def record(name: str, start_ns: int, end_ns: int, **attrs) -> None:
    """Record a span whose two ends someone else's clock reads already
    gave (``time.time_ns()`` both), whatever the switch says, as
    ``startup`` spans are: the runtime's stall witness, which knows of a
    stop only once it is over.  It hangs under this process's start-up
    root.  While a profiler session runs it is also a TraceAnnotation,
    which the profiler can place only at the moment it is made: the
    stop's end."""
    s = Span(name, _start, attrs)
    s.start_ns = start_ns
    if profiling():
        with _annotation(name, start_ns=start_ns, end_ns=end_ns,
                         **{k: str(v) for k, v in attrs.items()}):
            pass
    s.finish(end_ns=end_ns)


def as_dict(row: tuple, pid: Optional[int] = None) -> dict:
    """A recorded span as callers read it (built on read, not at span
    exit)."""
    name, trace_id, span_id, parent_id, start_ns, end_ns, attrs = row
    return {
        "name": name,
        "trace_id": trace_id,
        "span_id": span_id,
        "parent_id": parent_id,
        "start_ns": start_ns,
        "end_ns": end_ns,
        "duration_ms": (end_ns - start_ns) / 1e6,
        "attributes": dict(attrs),
        "pid": os.getpid() if pid is None else pid,
    }


def spans(trace_id: Optional[str] = None) -> List[dict]:
    """Finished spans recorded in THIS process (newest last)."""
    with _LOCK:
        rows = list(_SPANS)
    return [as_dict(r) for r in rows if not trace_id or r[1] == trace_id]


def drain() -> List[tuple]:
    """The spans finished since the last drain, each exactly once, as
    recorded: what the runtime's next push to the GCS carries.  The ring
    keeps them for ``spans()``."""
    global _undrained
    with _LOCK:
        n = min(_undrained, len(_SPANS))
        _undrained = 0
        return list(itertools.islice(_SPANS, len(_SPANS) - n, None))


def open_span() -> Optional[str]:
    """Name of the span entered last and not left yet, in any task or
    thread of this process."""
    try:
        return next(reversed(_OPEN.values()), None)
    except RuntimeError:  # another thread entered or left a span meanwhile
        return None


def clear() -> None:
    global _undrained
    with _LOCK:
        _SPANS.clear()
        _undrained = 0


def collect(trace_id: Optional[str] = None, since_ns: Optional[int] = None,
            until_ns: Optional[int] = None,
            name_prefix: Optional[str] = None) -> List[dict]:
    """Spans of the whole cluster from the GCS's span table, oldest
    first: every process's spans reach it with that process's next push
    (at most ``metrics_push_interval_s`` later, and when a worker exits
    gracefully).  This process's own pending spans are pushed first."""
    from ray_tpu.core.runtime import get_runtime

    rt = get_runtime()
    rt._run(rt.push_telemetry())
    return rt._run(rt.gcs.call("list_spans", {
        "trace_id": trace_id, "since_ns": since_ns, "until_ns": until_ns,
        "name_prefix": name_prefix,
    }))
