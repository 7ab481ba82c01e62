"""repro.dist: the distributed-execution substrate.

Three modules:

``buckets``
    The batch-bucket policy (``bucket_size``, ``bucket_for``): the
    padded row count of every served batch, below every layer that
    sizes one.

``sharding``
    Logical-axis sharding contexts.  Model/runtime code names *logical*
    axes ("batch", "seq", "ffn", ...); an active :class:`ShardCtx`
    (installed by :func:`use_mesh`) resolves them to the physical mesh
    axes ("pod", "data", "model") with per-dimension divisibility
    fallback, so the same traced program runs on 1 CPU device, a local
    test mesh, or a 512-chip dry-run mesh without edits.

``hlo_analysis``
    Post-compile analysis: a parser extracting collective-communication
    counts/bytes from compiled HLO, and a three-term (compute / HBM /
    interconnect) :class:`Roofline` estimator.

See ``README.md`` in this directory for the axis model.
"""
from repro.dist.hlo_analysis import CollectiveStats, Roofline, collective_stats
from repro.dist.sharding import (ShardCtx, cache_spec_tree, constrain,
                                 current_ctx, param_spec_tree, use_mesh)

__all__ = [
    "CollectiveStats", "Roofline", "collective_stats",
    "ShardCtx", "cache_spec_tree", "constrain", "current_ctx",
    "param_spec_tree", "use_mesh",
]
