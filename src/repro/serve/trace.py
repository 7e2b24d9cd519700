"""Host spans of the served path.

Each span is a ``jax.profiler.TraceAnnotation`` named ``pir.<name>``. It
writes into the profiler's own trace while one is being recorded, on the
host clock that the trace's device planes are aligned to, and costs one or
two microseconds when none is. Its stats are Python values already at hand
(ids, counts, shapes): never a device value, so a span never syncs.

Where each span opens:

* ``pir.admit``: ``AsyncFrontend``'s ingest worker, one admission chunk
  under the frontend's lock (``items``);
* ``pir.wait.arrivals``: the flush worker asleep until a cut is due
  (``queued``); ``pir.wait.inflight``: the flush worker blocked on the
  batch in flight (``batch``);
* ``pir.idle.<kind>``: one idle-slot job (ingest, compact, prefill,
  autotune);
* ``pir.plan`` (``batch``, ``requests``, ``misses``, ``bucket``) with
  ``pir.query_gen`` (``bucket``) inside it: ``ServingPipeline``'s plan
  phase and the router's query generation;
* ``pir.execute`` (``batch``): the execute phase, holding ``pir.answer``
  (``servers``, ``bucket``, ``n``, ``words``, ``kind``: the backend's
  answer loop, with ``pir.answer.server`` (``server``) for each server's
  dispatch) and
  ``pir.finalize`` (``batch``: responses to host bytes, with
  ``pir.reconstruct``, ``pir.unpack`` and ``pir.cache_insert`` inside).

``batch`` is the pipeline's id of a planned batch: it links the plan on
the flush worker to the execute it caused on another thread.
"""

from __future__ import annotations

import jax

__all__ = ["SPANS", "span"]

#: every span the served path opens
SPANS = (
    "pir.admit",
    "pir.wait.arrivals",
    "pir.wait.inflight",
    "pir.idle.ingest",
    "pir.idle.compact",
    "pir.idle.prefill",
    "pir.idle.autotune",
    "pir.plan",
    "pir.query_gen",
    "pir.execute",
    "pir.answer",
    "pir.answer.server",
    "pir.finalize",
    "pir.reconstruct",
    "pir.unpack",
    "pir.cache_insert",
)


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """The host span ``pir.<name>`` carrying ``stats``; use it as a
    context manager."""
    return jax.profiler.TraceAnnotation("pir." + name, **stats)
