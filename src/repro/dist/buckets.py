"""The batch-bucket policy: the padded row count of every served batch.

Power-of-two buckets bound the jit cache to log2(max batch) shapes per
sharding context, and key every tune-cache entry.  The engine, the
batcher, the kernels' tune lookups and the flush controller all size
batches here.  A leaf module: it imports nothing from ``repro``.
"""
from __future__ import annotations


def bucket_size(n: int, min_bucket: int = 8) -> int:
    """Smallest power-of-two >= max(n, min_bucket)."""
    b = max(int(min_bucket), 1)
    while b < n:
        b <<= 1
    return b


def bucket_for(n: int, min_bucket: int, n_shards: int = 1) -> int:
    """Dispatch bucket: power-of-two floor, rounded up to a multiple of
    the data-shard count — a bucket smaller than (or not dividing) the
    shard count would make `spec_for` drop the data axis and silently
    replicate the whole batch on every device.
    """
    b = bucket_size(n, max(min_bucket, n_shards))
    if n_shards > 1 and b % n_shards:
        b += -b % n_shards
    return b
