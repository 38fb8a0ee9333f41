"""Serving layer: the open-loop traffic and admission layer over the LSM
engines.

``traffic`` materializes multi-tenant :class:`TrafficSpec` scenarios
into simulated-time-ordered op streams and drives either engine;
``admission`` is the deterministic pre-pass controller (token buckets,
priority-aware shedding) in front of each shard's foreground queue.
"""

from .admission import (ADMIT, SHED, THROTTLE, AdmissionConfig,
                        TokenBucket, admit)
from .traffic import (ServeResult, TenantSpec, TrafficSpec, TrafficStream,
                      bursty_arrivals, deterministic_arrivals, materialize,
                      poisson_arrivals, serve, serve_grid)

__all__ = [
    "ADMIT", "AdmissionConfig", "SHED", "THROTTLE", "ServeResult",
    "TenantSpec", "TokenBucket", "TrafficSpec", "TrafficStream", "admit",
    "bursty_arrivals", "deterministic_arrivals", "materialize",
    "poisson_arrivals", "serve", "serve_grid",
]
