"""Serving layer: the event-loop engine + dispatch/latency metrics.

``metrics`` is imported eagerly — it is dependency-free and the core
overlay layers record into its :class:`Histogram` on the dispatch path.
``Request`` and ``EventLoopEngine`` are exposed lazily (PEP 562):
``engine`` imports ``repro.core``, which imports ``repro.serving.metrics``,
so an eager import here would close an import cycle.
"""

from repro.serving.metrics import Histogram

__all__ = ["Histogram", "Request", "EventLoopEngine"]


def __getattr__(name: str):
    if name in ("Request", "EventLoopEngine"):
        from repro.serving import engine
        return getattr(engine, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
