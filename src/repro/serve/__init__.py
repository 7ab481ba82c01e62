"""Async batched surrogate serving: queue, coalescing batcher, stats.

The paper's speedups come from replacing accurate regions with surrogate
inference; at scale the surrogate is a *service*, not a function call.
This package turns ``MLRegion`` invocations into queued requests that
coalesce into mesh-wide padded mega-batches (see README.md).  The
multi-tenant control plane (:mod:`repro.serve.tenancy`) adds per-tenant
admission, QoS tiers and weighted fair share on top; the residency
manager (:mod:`repro.serve.residency`) meters loaded bundles against an
HBM byte budget.
"""
from repro.serve.batcher import Batcher
from repro.serve.queue import (Backpressure, FlushPolicy, ServeFuture,
                               ServeQueue)
from repro.serve.residency import RESIDENCY, ResidencyManager
from repro.serve.scratch import ScratchPool
from repro.serve.stats import ServeStats
from repro.serve.tenancy import (DeficitRoundRobin, TenantBoard, TenantSpec,
                                 TenantThrottled, TokenBucket)

__all__ = ["Backpressure", "Batcher", "DeficitRoundRobin", "FlushPolicy",
           "RESIDENCY", "ResidencyManager", "ScratchPool", "ServeFuture",
           "ServeQueue", "ServeStats", "TenantBoard", "TenantSpec",
           "TenantThrottled", "TokenBucket"]
