"""Hash-partitioned exchange: the all-to-all shuffle kernel.

Reference data plane: PartitionedOutputOperator hash-routes each row to an output partition
(operator/output/PagePartitioner.java:134) into per-partition buffers
(execution/buffer/PartitionedOutputBuffer.java:42) pulled over HTTP by the consumer's
ExchangeOperator (operator/ExchangeOperator.java:50, HttpPageBufferClient.java:100).

TPU re-design (runs *inside* shard_map, SURVEY.md §2.8 mapping):
- partition id = hash(keys) mod n_workers (same hash family as the reference's
  partitioned exchange);
- rows are bucketed into a fixed [n_workers, bucket] send tensor (stable sort by partition
  + within-partition offsets — a compaction, not a gather per partition, so one XLA sort
  covers all partitions);
- ``jax.lax.all_to_all`` over the worker axis swaps buckets so worker w receives every
  row whose key hashes to w — the ICI replacement for the HTTP long-poll;
- fixed bucket capacity keeps shapes static; overflowing rows are dropped AND reported (the
  rows bound for each partition come back beside the send layout: more than ``bucket`` of
  them is an overflow) so the driver can re-run the batch with a bigger bucket (the moral
  equivalent of exchange backpressure, OutputBuffer#isFull).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .hashing import hash_columns

__all__ = ["partition_ids", "bucketize", "exchange_all_to_all"]


def partition_ids(key_cols, n_partitions: int) -> jnp.ndarray:
    """Row -> partition id in [0, n_partitions)."""
    h = hash_columns(key_cols)
    return (jnp.abs(h) % n_partitions).astype(jnp.int32)


def bucketize(cols, valid, pid, n_partitions: int, bucket: int):
    """Pack rows into a [n_partitions * bucket] send layout.

    Returns (packed_cols, packed_valid, counts): row r of partition p lands at
    p * bucket + rank_of_r_within_p; slots beyond a partition's row count are invalid.
    ``counts`` [n_partitions] is the rows BOUND for each partition, before the bucket
    cuts them: a count over ``bucket`` is an overflow (rows dropped), their largest is
    the bucket the batch needed and their sum the rows it routed.

    Round-13 backend split: with `use_pallas()` the partitioned pack runs as
    ``n_partitions`` sequential masked compactions (ops/arrays.compact_rows —
    the block prefix-sum scatter kernel), one per destination bucket, instead
    of one global stable sort; byte-identical layout (stable sort preserves
    within-partition order, and so does each compaction).  Runs inside
    shard_map on the distributed path — the python loop is trace-time static.
    """
    from .arrays import compact_rows
    from .pallas_kernels import compact_enabled

    n = pid.shape[0]
    if compact_enabled(n, bucket, cols):
        packed_p, counts = [], []
        for p in range(n_partitions):
            sel = valid & (pid == p)
            pp, cnt = compact_rows(tuple(cols), sel, bucket)
            packed_p.append(pp)
            counts.append(cnt)
        packed = tuple(
            jnp.concatenate([pp[i] for pp in packed_p])
            for i in range(len(cols)))
        counts = jnp.stack(counts)
        out_valid = (jnp.arange(bucket)[None, :]
                     < jnp.minimum(counts, bucket)[:, None]).reshape(-1)
        return packed, out_valid, counts
    sort_key = jnp.where(valid, pid, n_partitions)  # invalid rows sort to the end
    order = jnp.argsort(sort_key, stable=True)
    sorted_pid = sort_key[order]
    starts = jnp.searchsorted(sorted_pid, jnp.arange(n_partitions + 1))
    counts = starts[1:] - starts[:-1]
    # slot p * bucket + r takes the r-th row of partition p in sorted order.
    # Written as a GATHER per column over the send layout, not a scatter of
    # the rows into it: on the TPU a scattered lane costs 74-290 ns and a
    # gathered one 14.5 (PERF.md, PR 26), and this runs once a batch inside
    # every probe exchange and every group-by merge of the mesh executor
    slot = jnp.arange(n_partitions * bucket, dtype=jnp.int32)
    p, r = slot // bucket, slot % bucket
    out_valid = r < jnp.minimum(counts, bucket)[p]
    src = order[jnp.clip(starts[p] + r, 0, n - 1)]
    packed = tuple(jnp.where(out_valid, c[src], jnp.zeros((), c.dtype))
                   for c in cols)
    return packed, out_valid, counts


def exchange_all_to_all(packed_cols, packed_valid, axis_name: str, n_partitions: int):
    """Swap partition buckets across the mesh axis (must run inside shard_map).

    Input/output layout: [n_partitions * bucket] rows; after the exchange, this worker
    holds the rows every peer routed to it.
    """

    def a2a(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0, tiled=True)

    return tuple(a2a(c) for c in packed_cols), a2a(packed_valid)
