"""Host spans on the device trace's clock.

Every span is a ``jax.profiler.TraceAnnotation`` named
``omni.<stage>.<part>``.  While a profiler session runs (``jax.profiler
.trace(dir)``, or TensorBoard's capture), the profiler writes each span
into the same ``.xplane.pb`` as the device's programs and ops, on the
same clock: one host line per OS thread, so a device-idle gap can be
read against what each thread was doing.  With no session a span costs
under a microsecond, so spans are always on; there is no flag.

The stage name is part of the span name because the trace keys host
lines by thread id and does not carry Python thread names.  Names carry
no per-call metadata: counts live in counters that callers snapshot
(``AREngine.prefix_stats``, ``AREngine.sched_stats``, ``Connector.stats``).
README "Running" lists the spans and what each covers.
"""
from __future__ import annotations

import jax


def span(stage: str, part: str) -> jax.profiler.TraceAnnotation:
    """A context manager that marks ``omni.<stage>.<part>`` on the
    calling thread's host line of the profiler trace."""
    return jax.profiler.TraceAnnotation(f"omni.{stage}.{part}")
