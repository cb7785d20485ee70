"""The profiler spans of a FedEntropy round, and its one readback helper.

A round opens these ``jax.profiler.TraceAnnotation`` spans, nested under
``fl.round``. They record only while a profiler session is active (for
example inside ``jax.profiler.trace(log_dir)``), on the same clock as the
device's programs in that trace. Otherwise they record nothing and cost
0.4-0.6 us each on a TPU v5e host (timed with ``timeit``, JAX 0.9.0)::

    fl.round      one round() of an engine; keyword ``round`` = its number
      fl.select   the selector's draw (and the clustered assignment)
      fl.stage    the cohort gathered off the data plane, the strategy's
                  per-client inputs
      fl.clients  the client program's dispatch
      fl.fetch    one device-to-host readback (``fetch``), once each;
                  a device selector's or judge's own readbacks are
                  ``fl.fetch`` spans inside ``fl.select`` or ``fl.judge``
      fl.judge    the judge's verdict and the admission mask
      fl.aggregate  the aggregator
      fl.feedback   strategy state, selector pools, uplink bytes, history

Every device-to-host readback of a round goes through :func:`fetch`, so
the number of ``fl.fetch`` spans in one ``fl.round`` is the number of
times that round waited on the device.

Every engine runs the round's tail through ``Server``'s stages
(readback, verdict, merge), so every engine opens these spans. A
speculative pipelined round first opens ``fl.judge`` (the device verdict
on its own cohort), ``fl.aggregate`` (the speculative aggregate),
``fl.feedback`` (the strategy-state fold), all dispatched before any
readback, and ``fl.judge`` again (that verdict read back), then the
sequence above: the next cohort's draw and dispatch, and this
cohort's readbacks, oracle, aggregate and feedback; when the oracle
confirms the guess, that ``fl.aggregate`` only adopts the speculative
aggregate. An async round (one flush) opens ``fl.select`` per dispatched
cohort and ``fl.judge`` per screened arrival batch. A scan block's
folded rounds have no ``fl.round``: the block's readbacks are
``fl.fetch`` spans and each confirmed round's oracle an ``fl.judge``.

The mesh engine's step opens one counter span, after its readback, where
the model has held-expert layers (:func:`route_counter`)::

    moe.route     keywords ``rows`` (assignments the held experts computed,
                  summed over the MoE layers), ``max_rows`` and
                  ``min_rows`` (the busiest and idlest held expert's, over
                  every layer)
"""
from __future__ import annotations

import numpy as np
from jax.profiler import TraceAnnotation

ROUND = "fl.round"
SELECT = "fl.select"
STAGE = "fl.stage"
CLIENTS = "fl.clients"
FETCH = "fl.fetch"
JUDGE = "fl.judge"
AGGREGATE = "fl.aggregate"
FEEDBACK = "fl.feedback"
MOE_ROUTE = "moe.route"
NAMES = (ROUND, SELECT, STAGE, CLIENTS, FETCH, JUDGE, AGGREGATE, FEEDBACK,
         MOE_ROUTE)

__all__ = ["AGGREGATE", "CLIENTS", "FEEDBACK", "FETCH", "JUDGE",
           "MOE_ROUTE", "NAMES", "ROUND", "SELECT", "STAGE", "fetch",
           "route_counter"]


def fetch(x, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)`` under an ``fl.fetch`` span: the host waits
    here for the device's answer."""
    with TraceAnnotation(FETCH):
        return np.asarray(x, dtype)


def route_counter(expert_rows) -> None:
    """The ``moe.route`` counter span of one step, from its host copy of
    the step's ``expert_rows`` (MoE layers, experts held)."""
    rows = np.asarray(expert_rows)
    with TraceAnnotation(MOE_ROUTE, rows=int(rows.sum()),
                         max_rows=int(rows.max()), min_rows=int(rows.min())):
        pass
