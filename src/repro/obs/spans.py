"""Host spans on the scan path, by name.

Every span is a plain ``jax.profiler.TraceAnnotation`` (:data:`span`).
With no profiler running it costs about a microsecond and records
nothing; under ``jax.profiler.start_trace`` it lands on the trace's host
plane, on the clock of the device events, and its keyword arguments
arrive as the event's stats. There is no flag to turn them off. Spans
open at query, call or batch granularity, never inside a per-row,
per-column or per-segment loop; a span inside a generator opens and
closes within one ``next()``.

=====================  ==================================================  ====================
span                   opens around                                        args (counts)
=====================  ==================================================  ====================
``thallus.scan``       ``ThallusClient.run_query``: the whole query        --
``thallus.init_scan``  ``ThallusServer.init_scan``                         ``start_batch``
``thallus.iterate``    ``ThallusServer.iterate``: the whole walk           --
``thallus.expose``     in ``iterate``, per batch: ``expose_batch``,        ``rows``, ``segments``
                       ``size_vectors`` and the control RPC
``thallus.pull``       ``core.transport.rdma_pull_batch``: allocation,     ``rows``, ``bytes``,
                       placement copy, assembly                            ``segments``
``thallus.sink``       ``ThallusClient.do_rdma``: the call to the sink     ``rows``
                       (the consumer's code)
``thallus.finalize``   ``ThallusServer.finalize``                          --
``thallus.land``       ``core.device_transport.batch_to_device``, after    ``rows``, ``columns``,
                       the choice of path: validation, then the region's   ``transfers``,
                       one transfer and split, or a put per column         ``bytes``
``engine.plan``        ``Engine.execute``: parse and catalog lookup        --
``engine.filter``      ``filter_mask`` of one batch (scan or aggregate)    ``rows`` (scanned)
``engine.take``        the gather or projection of one batch's kept rows   ``rows`` (kept)
=====================  ==================================================  ====================

In ``thallus.land``, ``columns`` counts the batch's columns and
``transfers`` its host-to-HBM transfers: 1 where the batch lands from its
receive region, one per column otherwise.

Nesting on the query's thread: ``scan`` holds ``init_scan`` (which holds
``engine.plan``), ``iterate`` and ``finalize``; ``iterate`` holds, per
batch, the engine's ``filter`` and ``take``, then ``expose``, ``pull``
and ``sink``; a sink that lands through ``batch_to_device`` holds
``land``.

The self time of :data:`PROTOCOL`'s spans (each span's time less the
spans nested in it) is the control plane's own work: the reader map,
the batch walk, exposing and the bookkeeping around the data plane.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation as span  # noqa: F401

SCAN = "thallus.scan"
INIT_SCAN = "thallus.init_scan"
ITERATE = "thallus.iterate"
EXPOSE = "thallus.expose"
PULL = "thallus.pull"
SINK = "thallus.sink"
FINALIZE = "thallus.finalize"
LAND = "thallus.land"
ENGINE_PLAN = "engine.plan"
ENGINE_FILTER = "engine.filter"
ENGINE_TAKE = "engine.take"

PREFIXES = ("thallus.", "engine.")
PROTOCOL = (SCAN, INIT_SCAN, ITERATE, EXPOSE, FINALIZE)
