"""Host spans of the serving loop, on the profiler's own clock.

``span(name, **stats)`` is ``jax.profiler.TraceAnnotation``: a ``TraceMe``
on the host plane of the same ``.xplane.pb`` that holds the device's
operations, so a span and the device work it enqueued share one clock.
Each keyword becomes a stat of the event (``rid``, ``slot``, ...), and
``set_metadata`` on the entered span adds stats found out inside it.

Spans record whenever a profiler is recording (``jax.profiler.trace`` or
``start_trace``) and at no other time; there is no switch.  Unrecorded, a
span costs under a microsecond on the host.  ``recording()`` says whether
one is recording: stats that cost work to compute are computed only then.
"""
from jax.profiler import TraceAnnotation

span = TraceAnnotation
recording = TraceAnnotation.is_enabled
