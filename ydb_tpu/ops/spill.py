"""Aggregation-state spill: HBM → host-DRAM partitioned merge.

The analog of the reference WideCombiner's state machine
(`ydb/library/yql/minikql/comp_nodes/mkql_wide_combine.cpp:338-600`,
InMemory → Spilling → ProcessSpilled): when the partial group-by states
of a query exceed the device merge budget, each partial block is
hash-partitioned BY GROUP KEY on the device (one sort dispatch), read
out to host DRAM, and the merge group-by then runs per partition —
partitions hold disjoint key sets, so per-partition merges compose into
the global result without ever holding all states in HBM at once.

TPU shape of the idea: the reference spills hash-table buckets to disk
and re-reads them; here the "bucket" is a key-hash partition of a
padded columnar block, the spill medium is host DRAM (125GB vs 16GB
HBM on this platform), and the partition step is a single fused
sort-by-partition dispatch instead of per-row bucket appends.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ydb_tpu.core.block import ColumnData, HostBlock
from ydb_tpu.ops.xla_exec import sort_total
from ydb_tpu.utils.hashing import hash_combine, splitmix64

# fixed hash slot for NULL keys: every all-NULL key lands in one partition
_NULL_SENTINEL = -0x61C8864680B583EB


@partial(jax.jit, static_argnames=("names", "key_names", "nparts"))
def _partition_sort(arrays, valids, length, names: tuple, key_names: tuple,
                    nparts: int):
    """Sort a block's rows by key-hash partition id; returns the sorted
    columns plus per-partition row counts (one dispatch, one transfer
    when the caller fetches). Float keys hash on their int truncation —
    partitioning only needs same-key → same-partition, not injectivity."""
    cap = arrays[names[0]].shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    active = iota < length
    h = None
    for k in key_names:
        enc = arrays[k].astype(jnp.int64)
        v = valids.get(k)
        if v is not None:
            enc = jnp.where(v, enc, jnp.int64(_NULL_SENTINEL))
        x = splitmix64(jnp, enc)
        h = x if h is None else hash_combine(jnp, h, x)
    part = (h % jnp.uint64(nparts)).astype(jnp.int32)
    pkey = jnp.where(active, part, jnp.int32(nparts))
    # iota as the second key → stable order, and the output IS the
    # permutation (no carried operands — wide sorts explode compile time)
    _, perm = sort_total([pkey], iota)
    counts = jnp.sum((pkey[:, None]
                      == jnp.arange(nparts, dtype=jnp.int32)[None, :]),
                     axis=0, dtype=jnp.int32)
    out_arrays = {n: a[perm] for n, a in arrays.items()}
    out_valids = {n: v[perm] for n, v in valids.items()}
    return out_arrays, out_valids, counts


class PartitionStore:
    """Host-DRAM store of key-hash partitions of partial-agg blocks.

    feed() spills one device block; partition(p) returns the
    host-concatenated rows of partition p across every fed block."""

    def __init__(self, schema, key_names: list, nparts: int,
                 dictionaries: dict | None = None):
        self.schema = schema
        self.key_names = tuple(key_names)
        self.nparts = nparts
        self.dictionaries = dict(dictionaries or {})
        # partition -> list of {name: np array}, {name: np bool array}
        self._parts: list = [[] for _ in range(nparts)]
        self.spilled_rows = 0
        self.spilled_bytes = 0

    def feed(self, dblock) -> None:
        names = tuple(dblock.schema.names)
        arrays, valids, counts = _partition_sort(
            dblock.arrays, dblock.valids, dblock.length, names,
            self.key_names, self.nparts)
        h_arrays, h_valids, h_counts = jax.device_get(
            (arrays, valids, counts))
        self.dictionaries.update(dblock.dictionaries)
        bounds = np.cumsum(h_counts)
        total = int(bounds[-1])
        self.spilled_rows += total
        lo = 0
        for p in range(self.nparts):
            hi = int(bounds[p])
            if hi > lo:
                piece_a = {n: a[lo:hi] for n, a in h_arrays.items()}
                piece_v = {n: v[lo:hi] for n, v in h_valids.items()}
                self._parts[p].append((piece_a, piece_v))
                self.spilled_bytes += sum(a.nbytes for a in piece_a.values())
                self.spilled_bytes += sum(v.nbytes for v in piece_v.values())
            lo = hi

    def partition(self, p: int) -> HostBlock:
        pieces = self._parts[p]
        cols = {}
        if not pieces:
            for c in self.schema.columns:
                cols[c.name] = ColumnData(np.zeros(0, dtype=c.dtype.np),
                                          None, self.dictionaries.get(c.name))
            return HostBlock(self.schema, cols, 0)
        n = sum(len(next(iter(a.values()))) for (a, _v) in pieces)
        for c in self.schema.columns:
            data = np.concatenate([a[c.name] for (a, _v) in pieces])
            valid = None
            if any(c.name in v for (_a, v) in pieces):
                valid = np.concatenate(
                    [v.get(c.name, np.ones(len(next(iter(a.values()))),
                                           np.bool_))
                     for (a, v) in pieces])
            cols[c.name] = ColumnData(data, valid,
                                      self.dictionaries.get(c.name))
        self._parts[p] = []          # release as soon as merged
        return HostBlock(self.schema, cols, n)


def host_sort_limit(block: HostBlock, sort: list, limit, offset,
                    dictionaries: dict | None = None) -> HostBlock:
    """Host-side ORDER BY + LIMIT/OFFSET over a merged result (the spill
    path's final pass — per-partition results are each sorted on device
    or small enough that a host lexsort is cheap). String keys order by
    dictionary value rank; NULLs honor nulls_first."""
    dicts = dict(dictionaries or {})
    if sort:
        keys = []
        for sk in reversed(sort):       # lexsort: last key is primary
            cd = block.columns[sk.name]
            data = cd.data
            dic = dicts.get(sk.name) or cd.dictionary
            if dic is not None and block.schema.dtype(sk.name).is_string:
                ranks = dic.sort_ranks().astype(np.int64)
                safe = np.clip(data.astype(np.int64), 0, len(ranks) - 1)
                data = ranks[safe]
            k = data.astype(np.float64) \
                if np.issubdtype(data.dtype, np.floating) \
                else data.astype(np.int64)
            if not sk.ascending:
                k = -k.astype(np.float64) if k.dtype == np.float64 else -k
            if cd.valid is not None:
                nullk = np.where(cd.valid, 0, -1 if sk.nulls_first else 1)
                keys.append(k)
                keys.append(nullk)       # appended after → higher priority
            else:
                keys.append(k)
        order = np.lexsort(tuple(keys))
        block = block.take(order)
    lo = offset or 0
    hi = block.length if limit is None else min(lo + limit, block.length)
    if lo or hi < block.length:
        block = block.slice(lo, hi)
    return block
