"""``PartitionedArray`` — the dataflow core's partitioned-collection type.

The RDD analog (SURVEY.md L3 ``partitionBy``): ONE logical global array
plus the layout bookkeeping that maps it onto devices — padded length,
a global-id → padded-slot relabeling, and the mesh sharding the device
value carries.  Callers program against the logical view; the
padding/relabeling round-trip lives here once instead of inside each
runner (the owned sharded layout's :class:`OwnedArray` backs
``_ShardedExec.put_ranks``).

The host→device direction pads and places; the device→host direction is
a *guarded* pull (resilience executor: retry / sync deadline /
degradation ladder) returning the logical array — so every workload that
states its results through a ``PartitionedArray`` inherits the repo's
host-sync discipline for free.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from page_rank_and_tfidf_using_apache_spark_tpu import obs
from page_rank_and_tfidf_using_apache_spark_tpu.resilience import executor as rx


@dataclasses.dataclass(frozen=True)
class PartitionedArray:
    """A logical [n] array laid out as a padded (optionally sharded)
    device value of length ``n_pad``, with ``node_map[global_id] ->
    padded slot``.  ``sharding=None`` is the single-chip identity layout
    (n_pad == n, map == arange)."""

    n: int
    n_pad: int
    node_map: np.ndarray  # int64 [n]: global id -> padded slot
    value: Any = None  # device array [n_pad] (None until .put)
    sharding: Any = None  # jax.sharding.NamedSharding | None

    @classmethod
    def identity(cls, n: int) -> "PartitionedArray":
        """Single-chip layout: no padding, no relabeling."""
        return cls(n=n, n_pad=n, node_map=np.arange(n, dtype=np.int64))

    @classmethod
    def from_plan(cls, n: int, n_pad: int, node_map: np.ndarray,
                  sharding: Any = None) -> "PartitionedArray":
        """Layout from a partition plan's bookkeeping: logical and padded
        lengths, the global-id -> padded-slot map and the state
        sharding."""
        return cls(n=n, n_pad=n_pad, node_map=node_map, sharding=sharding)

    def put(self, global_np: np.ndarray, dtype=None) -> "PartitionedArray":
        """Pad + relabel + device_put a logical [n] host array; returns a
        new PartitionedArray holding the device value."""
        import jax

        dtype = dtype or global_np.dtype
        if self.n_pad == self.n and self.node_map.shape[0] == self.n and (
            self.node_map == np.arange(self.n)
        ).all():
            padded = np.asarray(global_np, dtype)
        else:
            padded = np.zeros(self.n_pad, dtype)
            padded[self.node_map] = global_np
        dev = (jax.device_put(padded, self.sharding)
               if self.sharding is not None else jax.device_put(padded))
        return dataclasses.replace(self, value=dev)

    def with_value(self, value: Any) -> "PartitionedArray":
        """The same layout around a new device value (a fixpoint's output
        carry keeps the input's partition plan)."""
        return dataclasses.replace(self, value=value)

    def pull(self, *, site: str = "partitioned_pull", metrics=None,
             checkpoint_dir: str | None = None) -> np.ndarray:
        """Guarded device→host pull of the LOGICAL array: one batched
        transfer through the resilience executor, then the node_map
        inverse on host."""
        if self.value is None:
            raise ValueError("PartitionedArray holds no device value")
        with obs.span("dataflow.pull", site=site, n=self.n):
            padded = rx.device_get(
                self.value, site=site, metrics=metrics,
                checkpoint_dir=checkpoint_dir,
            )
        return padded[self.node_map]


@dataclasses.dataclass(frozen=True)
class OwnedArray:
    """The owned-slice partitioned-collection layout (ISSUE 15): ONE
    logical [n] array split into a device-SHARDED padded tail (each shard
    holds only its owned block — nothing is replicated O(n)) plus a small
    REPLICATED hub-head mini-vector, mirroring
    ``ops.boundary.OwnedShard``'s node split.

    Same contract as :class:`PartitionedArray` — callers program against
    the logical view; host→device pads/places both components, and the
    device→host direction is one *guarded* batched pull (retry/deadline/
    ladder via the resilience executor) — so sharded PageRank, HITS and
    connected components on owned slices all inherit the host-sync
    discipline from this one class."""

    n: int
    n_pad: int  # d * block (tail layout width)
    h: int  # real head size
    h_pad: int
    tail_map: np.ndarray  # int64 [n]: global id -> padded tail slot; -1 head
    head_ids: np.ndarray  # int64 [H] ascending global ids
    tail: Any = None  # device array [n_pad], sharded along the mesh axis
    head: Any = None  # device array [h_pad], replicated
    tail_sharding: Any = None
    head_sharding: Any = None

    @classmethod
    def from_shard(cls, shard, *, tail_sharding: Any = None,
                   head_sharding: Any = None) -> "OwnedArray":
        """Layout view over a materialized ``ops.boundary.OwnedShard``."""
        return cls(
            n=shard.n, n_pad=shard.n_pad, h=shard.h, h_pad=shard.h_pad,
            tail_map=shard.tail_map, head_ids=shard.head_ids,
            tail_sharding=tail_sharding, head_sharding=head_sharding,
        )

    def put(self, global_np: np.ndarray, dtype=None) -> "OwnedArray":
        """Pad + split + device_put a logical [n] host array into the
        (sharded tail, replicated head) pair.  The split itself is
        ``ops.boundary.split_global`` — ONE implementation of the
        tail_map/head reassembly serves host planning and this layer."""
        import jax

        from page_rank_and_tfidf_using_apache_spark_tpu.ops import (
            boundary as ob,
        )

        dtype = dtype or global_np.dtype
        tail_np, head_np = ob.split_global(self, global_np, dtype)
        tail = (jax.device_put(tail_np, self.tail_sharding)
                if self.tail_sharding is not None
                else jax.device_put(tail_np))
        head = (jax.device_put(head_np, self.head_sharding)
                if self.head_sharding is not None
                else jax.device_put(head_np))
        return dataclasses.replace(self, tail=tail, head=head)

    def with_value(self, tail: Any, head: Any) -> "OwnedArray":
        """The same layout around a fixpoint's output carry components."""
        return dataclasses.replace(self, tail=tail, head=head)

    def pull(self, *, site: str = "partitioned_pull", metrics=None,
             checkpoint_dir: str | None = None) -> np.ndarray:
        """Guarded boundary-aware pull: ONE batched transfer for both
        components, then the tail_map/head reassembly on host."""
        from page_rank_and_tfidf_using_apache_spark_tpu.ops import (
            boundary as ob,
        )

        if self.tail is None or self.head is None:
            raise ValueError("OwnedArray holds no device value")
        with obs.span("dataflow.pull", site=site, n=self.n, owned=True):
            tail_np, head_np = rx.device_get(
                (self.tail, self.head), site=site, metrics=metrics,
                checkpoint_dir=checkpoint_dir,
            )
        return ob.merge_global(self, tail_np, head_np)
