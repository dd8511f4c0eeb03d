"""The span primitive and the Recorder, the one object the engines talk to.

:class:`span` is the program's only timing primitive.  ``with span(name,
stats):`` does two things:

  * it opens a ``jax.profiler.TraceAnnotation(name)``, so when a profiler
    session is running the span lands in the ``.xplane.pb`` host plane on
    the same clock as the device's ``XLA Ops``;
  * on exit it adds the elapsed ``perf_counter`` seconds and one call to
    two plain counters of ``stats``: ``<phase>_s`` and ``<phase>_calls``,
    where the phase is the name after its last dot (``serve.decode`` ->
    ``decode_s``, ``decode_calls``).

It never fences (``block_until_ready``): a span around a dispatch times
the host's work, and the device's time comes from the profiler.  So
attaching a recorder changes neither dispatch nor what a span measures.

Two recorders share one duck type:

  * :data:`NULL_RECORDER` (a :class:`NullRecorder`) — the default.  Its
    ``span()`` is the bare primitive; every other hook is a no-op.
  * :class:`Recorder` — its ``span()`` is the same primitive with the
    recorder as sink: each closed span also observes a
    ``<name>_seconds`` histogram and becomes a Perfetto slice of the
    same name; lifecycle hooks feed the :class:`~repro.obs.spans.SpanLog`;
    ``instant()`` marks point events on the trace.
"""
from __future__ import annotations

import time
from typing import Optional

from jax.profiler import TraceAnnotation

from .metrics import MetricsRegistry
from .spans import SpanLog
from .trace import TraceBuffer

__all__ = ["span", "Recorder", "NullRecorder", "NULL_RECORDER"]

_KEYS: dict[str, tuple[str, str]] = {}


def _keys(name: str) -> tuple[str, str]:
    keys = _KEYS.get(name)
    if keys is None:
        phase = name.rpartition(".")[2]
        keys = _KEYS[name] = (f"{phase}_s", f"{phase}_calls")
    return keys


class span:
    """Context manager: a profiler annotation plus two counters in
    ``stats`` (see the module docstring).  ``stats=None`` keeps the
    annotation only; ``sink`` (a :class:`Recorder`) gets
    ``on_span(name, t0, t1)`` at exit.  After exit ``elapsed`` holds the
    span's seconds."""

    __slots__ = ("name", "_stats", "_sink", "_ann", "t0", "elapsed")

    def __init__(self, name: str, stats=None, sink=None):
        self.name = name
        self._stats = stats
        self._sink = sink
        self._ann = TraceAnnotation(name)

    def __enter__(self):
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self.elapsed = t1 - self.t0
        st = self._stats
        if st is not None:
            ks, kn = _keys(self.name)
            st[ks] = st.get(ks, 0.0) + self.elapsed
            st[kn] = st.get(kn, 0) + 1
        if self._sink is not None:
            self._sink.on_span(self.name, self.t0, t1)
        return False


class NullRecorder:
    """Do-nothing recorder; the engines' default.  Stateless singleton."""

    enabled = False
    registry: Optional[MetricsRegistry] = None
    spans: Optional[SpanLog] = None
    trace: Optional[TraceBuffer] = None

    def span(self, name, stats=None):
        return span(name, stats)

    def instant(self, name, track="events", **args):
        pass

    def on_submit(self, req, step):
        pass

    def on_transition(self, req, frm, to, step):
        pass

    def on_token(self, req, step):
        pass

    def annotate(self, rid, **kw):
        pass


NULL_RECORDER = NullRecorder()


class Recorder:
    """Live recorder: registry + request spans + Perfetto trace.

    Any of the three sinks can be switched off at construction
    (``spans=False`` / ``trace=False``); pre-built instances can also be
    passed in (e.g. a SpanLog with an injected test clock).  Every hook
    is host-side bookkeeping; nothing here waits for the device.
    """

    enabled = True

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 spans=True, trace=True):
        self.registry = registry if registry is not None else MetricsRegistry()
        if spans is True:
            spans = SpanLog()
        self.spans = spans or None
        if trace is True:
            trace = TraceBuffer()
        self.trace = trace or None
        # slices are stamped relative to the trace buffer's start
        self._t0 = self.trace.t0 if self.trace is not None else 0.0

    def span(self, name, stats=None):
        return span(name, stats, self)

    def on_span(self, name: str, t0: float, t1: float) -> None:
        """A closed span: its histogram and its Perfetto slice."""
        self.registry.histogram(
            f"{name}_seconds", help=f"host wall-clock of {name} spans",
        ).observe(t1 - t0)
        if self.trace is not None:
            self.trace.slice(name, t0 - self._t0, t1 - self._t0)

    def instant(self, name, track="events", **args):
        if self.trace is not None:
            self.trace.instant(name, track=track, **args)
        self.registry.counter(
            f"event_{name}_total", labels=()).inc()

    def on_submit(self, req, step):
        if self.spans is not None:
            self.spans.on_submit(req, step)

    def on_transition(self, req, frm, to, step):
        if self.spans is not None:
            self.spans.on_transition(req, frm, to, step)
        if self.trace is not None and to in ("FINISHED", "CANCELLED",
                                             "EXPIRED", "FAILED"):
            self.trace.instant(f"request_{to.lower()}", track="lifecycle",
                               rid=req.rid, step=step)

    def on_token(self, req, step):
        if self.spans is not None:
            self.spans.on_token(req, step)

    def annotate(self, rid, **kw):
        if self.spans is not None:
            self.spans.annotate(rid, **kw)
