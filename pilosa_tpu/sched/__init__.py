"""Query admission control & QoS scheduling.

This package sits between the HTTP layer (server/handler.py, server/api.py)
and the executor (exec/): every query is *admitted* before it may dispatch.
Admission is weighted by the query's estimated device footprint (cost.py,
derived from the same accounting exec/plan.py's BudgetExceeded uses), and
bounded three ways (admission.py):

- a concurrent-query semaphore (`max-concurrent-queries`),
- a bounded, deadline- and priority-aware queue (`admission-queue-depth`,
  classes interactive / batch / internal with weighted-fair dequeue), and
- an in-flight device-byte budget coordinated with core/devcache.py's
  HBM residency budget (`admission-byte-budget`).

When the queue saturates — or a query's deadline can no longer be met —
the query is *shed* with HTTP 429 + Retry-After instead of queueing
unboundedly; server/faults.py already classifies 429 as retryable, so
internode load shedding composes with the fan-out's failover retries.
The controller also feeds observed load into exec/batcher.py's
CountBatcher so batch size grows under load (the fixed per-sweep cost
amortizes over the batch).
"""

from pilosa_tpu.sched.admission import (  # noqa: F401
    AdmissionController,
    CLASS_BATCH,
    CLASS_INTERACTIVE,
    CLASS_INTERNAL,
    CLASS_WEIGHTS,
    DEADLINE_HEADER,
    PRIORITY_HEADER,
    ShedError,
    Ticket,
)
from pilosa_tpu.sched.cost import QueryCost, ZERO_COST, estimate  # noqa: F401
