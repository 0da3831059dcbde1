"""Program spans on the profiler's clock.

``span(name, tracer, **args)`` marks one interval of host work. It always
opens a ``jax.profiler.TraceAnnotation``, so while a profiler trace is
being taken the span sits in the host plane of the same ``.xplane.pb`` as
the device's operations, on the same clock: an idle stretch of the device
can be read against whichever span was open on the host. With a
``TrajectoryTracer`` passed as ``tracer``, the same interval is also
recorded as a tracer activity under the same name and arguments, so the
Chrome export and the profiler trace carry one set of names.

With no trace being taken and no tracer, a span costs one annotation
enter and exit (the annotation encodes its name and arguments only while
a trace is active). Spans go around whole loops, never inside a
per-token or per-trajectory one.

Names are dotted by layer (``engine.step``, ``decode.sync``,
``trainer.dispatch``; ``docs/architecture.md`` "Program spans" lists
them). Arguments carry an instance id and a row or job count.
"""
from __future__ import annotations

from typing import TYPE_CHECKING, Optional

from jax.profiler import TraceAnnotation

if TYPE_CHECKING:
    from repro.obs.tracer import TrajectoryTracer


class span:
    """Context manager: one program span (see the module docstring)."""

    __slots__ = ("_name", "_tracer", "_args", "_annotation", "_t0")

    def __init__(
        self, name: str, tracer: Optional["TrajectoryTracer"] = None, **args
    ):
        self._name = name
        self._tracer = tracer
        self._args = args

    def __enter__(self) -> "span":
        self._annotation = TraceAnnotation(self._name, **self._args)
        self._annotation.__enter__()
        if self._tracer is not None:
            self._t0 = self._tracer.now()
        return self

    def __exit__(self, *exc) -> None:
        if self._tracer is not None:
            self._tracer.activity(
                self._name, self._t0, self._tracer.now(),
                args=self._args or None,
            )
        self._annotation.__exit__(*exc)
