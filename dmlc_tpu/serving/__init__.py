"""dmlc_tpu.serving: the request-serving plane.

The training substrate pointed at users: a continuous-batching
inference server over the flagship transformer, built from the pieces
the repo already trusts —

  * ``kv_cache``   paged (block-granular) KV storage with a free-list
                   allocator; the bytes are device pools that the
                   prefill and decode programs write in place
  * ``scheduler``  Orca-style iteration-level admit/evict with
                   preemption-by-recompute under memory pressure
  * ``engine``     the prefill/decode loop: jitted model programs,
                   greedy sampling, BufferPool admission backpressure,
                   and one StepLedger step per decode iteration (p50/
                   p99 step time, goodput, decode MFU on /metrics)
  * ``server``     POST /generate + /metrics /healthz /requests /slo
                   /trace HTTP surface (TelemetryHTTPServer pattern;
                   429 on a full queue, per-status-code counters)
  * ``loadgen``    N-stream closed-loop load + BENCH_serving.json
                   (joined with the server-side request ledger)
  * ``router``     fleet front door: health-checked least-loaded
                   routing over N replicas with idempotent retry,
                   tail-latency hedging, and zero-downtime failover
                   (``bin/dmlc-router``; CI: scripts/fleet_smoke.py)

Request-scoped observability rides telemetry.requests (per-request
lifecycle ledger: TTFT ≡ queue + prefill, TBT, preempt/resume
episodes, per-request /trace rows) and telemetry.slo (DMLC_SLO_*
burn-rate objectives; violations flow into the anomaly surface).

Launch with ``bin/dmlc-serve``; knobs are the ``DMLC_SERVE_*`` family
(README "Serving"); the CI smoke is ``scripts/serving_smoke.py``.
"""

from .engine import (  # noqa: F401
    AdmissionFull,
    EngineDraining,
    InferenceEngine,
    RequestTooLarge,
)
from .kv_cache import BlockAllocator, PagedKVCache  # noqa: F401
from .loadgen import LoadGenerator  # noqa: F401
from .router import Router, RouterHTTPServer  # noqa: F401
from .scheduler import ContinuousBatchScheduler, Request  # noqa: F401
from .server import ServingHTTPServer  # noqa: F401

__all__ = [
    "AdmissionFull",
    "BlockAllocator",
    "ContinuousBatchScheduler",
    "EngineDraining",
    "InferenceEngine",
    "LoadGenerator",
    "PagedKVCache",
    "Request",
    "RequestTooLarge",
    "Router",
    "RouterHTTPServer",
    "ServingHTTPServer",
]
