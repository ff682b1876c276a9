"""Where compiled XLA programs are kept between processes.

Every process that compiles for the chip — a worker that binds a TPU
lease, the GPT-2 bench — calls :func:`configure` once.  The directory
can be placed from outside: if ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing is set in code.  Otherwise the cache is
``<checkout>/.jax_cache``, computed from this package's own location:
the path is part of what a cache entry is found by, so it must be the
same for every process and every run of one checkout — never under
``/tmp``, a session directory, a pid or a temporary name.

:func:`configure` also installs this process's one listener on JAX's
monitoring events.  It turns every program's trace, lowering and
backend compile into a start-up span (``xla.trace`` / ``xla.lower`` /
``xla.compile`` with JAX's own two ends and its ``fun_name``;
util/tracing.py) and keeps the totals every :class:`CompileLog` reads.
JAX reports a trace for every jitted function called inside another's
trace and for every call that finds its jaxpr kept (thousands in a
replica's start): the span is the outermost trace of ``f`` that the
lowering of ``jit(f)`` follows on its thread, the program's own.
"""

from __future__ import annotations

import os
import threading

from ray_tpu.util import tracing

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    """``<checkout>/.jax_cache`` (the directory that holds ``ray_tpu/``)."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def configure() -> str:
    """Point JAX's persistent compilation cache at its directory, start
    listening to JAX's compile events, and return that directory.  Safe
    to call again; must run before the first compile it is meant to
    catch."""
    _listen()
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    path = default_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "xla.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "xla.lower",
    "/jax/core/compile/backend_compile_duration": "xla.compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

_listening = False
_lock = threading.Lock()
#: of this process since ``_listen``; a CompileLog reports what was
#: added since it was made
_totals = {"count": 0, "seconds": 0.0, "cache_hits": 0, "cache_misses": 0,
           "trace_lower_seconds": 0.0}
_last_compiled = None  # fun_name of the newest compile no cache answered
#: of this thread: what the cache said inside the backend compile it is
#: in (the hit and its load time are sent before the compile's own
#: event), how many traces it is inside, and its outermost traces since
#: its last lowering by ``fun_name``
_answer = threading.local()


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _answer.hit = True


def _on_scalar(event: str, _value, **_kw) -> None:
    # JAX sends a stage's start as a scalar when it enters it
    if _STAGES.get(event) == "xla.trace":
        _answer.traces = getattr(_answer, "traces", 0) + 1


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == _CACHE_LOAD:
        _answer.load_s = seconds


def _on_time_span(event: str, start: float, end: float,
                  fun_name: str = "", **_kw) -> None:
    global _last_compiled
    name = _STAGES.get(event)
    if name is None:
        return
    if name == "xla.trace":
        _answer.traces = inside = max(getattr(_answer, "traces", 1) - 1, 0)
        if not inside:  # else the outer trace holds this one
            _answer.__dict__.setdefault("traced", {})[fun_name] = (start, end)
        return
    attrs, traced = {"fun_name": fun_name}, None
    with _lock:
        if name == "xla.compile":
            hit = attrs["cache_hit"] = getattr(_answer, "hit", False)
            if hit:
                attrs["load_s"] = getattr(_answer, "load_s", 0.0)
            else:
                _last_compiled = fun_name
            _answer.hit, _answer.load_s = False, 0.0
            _totals["count"] += 1
            _totals["seconds"] += end - start
            _totals["cache_hits" if hit else "cache_misses"] += 1
        else:
            # the trace of ``f`` that ``jit(f)``'s lowering follows is the
            # program's: JAX also reports one for every call that finds
            # its jaxpr kept (an eager ``a + b``), and those become no span
            held = _answer.__dict__.setdefault("traced", {})
            traced_name = fun_name[fun_name.find("(") + 1:-1]
            traced = held.get(traced_name)
            held.clear()
            if traced:
                _totals["trace_lower_seconds"] += traced[1] - traced[0]
            _totals["trace_lower_seconds"] += end - start
    if traced:
        _record("xla.trace", *traced, {"fun_name": traced_name})
    _record(name, start, end, attrs)


def _record(name: str, start: float, end: float, attrs: dict) -> None:
    s = tracing.startup(name, **attrs)
    s.start_ns = int(start * 1e9)
    s.finish(end_ns=int(end * 1e9))


def _listen() -> None:
    """One set of listeners a process, however many ask."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_scalar_listener(_on_scalar)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_time_span_listener(_on_time_span)


class CompileLog:
    """Counts the XLA backend compiles of this process from the moment
    it is made: how many, how long they took together (a program found
    in the persistent cache counts with the time it took to load), how
    many of them the cache answered and how many it did not, the time
    spent tracing and lowering, and the program compiled last
    (``last_compiled``: the ``fun_name`` of the newest backend compile
    the cache did not answer, None while there was none)."""

    def __init__(self):
        _listen()
        with _lock:
            self._since = dict(_totals)

    def snapshot(self) -> dict:
        with _lock:
            out = {k: v - self._since[k] for k, v in _totals.items()}
            last = _last_compiled if out["cache_misses"] else None
        out["seconds"] = round(out["seconds"], 3)
        out["trace_lower_seconds"] = round(out["trace_lower_seconds"], 3)
        out["last_compiled"] = last
        return out
