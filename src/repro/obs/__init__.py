"""Observability: span tracing, bubble attribution, export, metrics.

The package decomposes a run's idle time ("bubbles", the paper's Eq. 5-6
objective) into *causes*.  ``trace`` records per-task / per-resource
spans emitted by both the arithmetic simulator (``repro.core.sim``) and
the async executor (``repro.serving.async_engine``) behind a
zero-cost-when-disabled sink hook; ``bubbles`` classifies every idle gap
on every resource into a closed cause set under a conservation identity
(``busy + sum(bubbles) == horizon`` per resource); ``export`` renders
Chrome/Perfetto ``trace_event`` JSON and text tables; ``metrics`` is the
counters/gauges/histograms registry the engines populate.  ``served``
records the served request path's host spans on the profiler's real
clock while a ``jax.profiler`` session runs.
"""

from repro.obs import bubbles, export, metrics, served, trace  # noqa: F401

__all__ = ["trace", "bubbles", "export", "metrics", "served"]
