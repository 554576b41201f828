"""Device-resident (HBM) column cache.

The TPU counterpart of the reference's shared page cache for tablet data
(`ydb/core/tablet_flat` shared cache / `columnshard` blob cache
`blobs_reader/`): immutable portion columns are uploaded to device memory
once and reused across queries, so repeated scans stream from HBM instead
of re-crossing the host↔device link every query. LRU-evicted under a byte
budget. Portions are immutable (compaction replaces them with new ids), so
entries never go stale — eviction of dropped portions happens lazily.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ydb_tpu.core.block import HostBlock
from ydb_tpu.ops.device import DeviceBlock, bucket_capacity
from ydb_tpu.storage.portion import Portion
from ydb_tpu.utils.metrics import GLOBAL, Timer

import os as _os

# bytes of HBM for cached columns (v5e: 16GB total; leave headroom for
# sort/groupby working sets)
DEFAULT_BUDGET = int(_os.environ.get("YDB_TPU_HBM_BUDGET", 10 << 30))


def enumerate_scan_sources(table, snapshot, prune):
    """Every visible scan source of a table: (HostBlocks, source ids).
    Source ids key superblock cache entries (write id, not list position:
    two snapshots seeing different insert subsets must not collide).
    Portions with MVCC delete marks visible at the snapshot contribute
    their filtered view under an id that carries the visible mark set."""
    sources, src_ids = [], []
    for shard in table.shards:
        portions, insert_entries = shard.scan_sources(snapshot, prune)
        for p in portions:
            sig = p.delete_sig(snapshot) if p.deletes else ()
            if sig:
                sources.append(p.visible_block(snapshot))
                src_ids.append(("pv", p.id, sig))
            else:
                sources.append(p.block)
                src_ids.append(("p", p.id))
        for e in insert_entries:
            sources.append(e.block)
            src_ids.append(("i", shard.shard_id, e.write_id))
    return sources, src_ids


def _device_source(b):
    """The still-on-device view of a stage-spine scan source (a landed
    `DeviceStageBlock` channel table), or None for plain host blocks.
    Reading it instead of `.columns` keeps the admission estimate and
    the superblock stack from forcing the block's host readback."""
    return getattr(b, "device", None)


def _source_cap(b) -> int:
    dev = _device_source(b)
    return dev.capacity if dev is not None \
        else bucket_capacity(max(b.length, 1))


def _source_has_valid(b, s: str) -> bool:
    dev = _device_source(b)
    return (s in dev.valids) if dev is not None \
        else (b.columns[s].valid is not None)


def estimate_scan_bytes(sources, storage_names: list,
                        pad_to: int = 0) -> int:
    """Superblock HBM footprint of a scan: K stacked sources at the max
    capacity bucket, per column data + validity — the fused-path
    admission estimate (no upload happens to find out it didn't fit).
    `pad_to`: the shape-bucketed row count (padded rows allocate real
    HBM, so the estimate must charge them). Device-resident sources
    answer from shape metadata — no readback."""
    if not sources:
        return 0
    K = max(len(sources), pad_to)
    CAP = max(_source_cap(b) for b in sources)
    total = 0
    for s in storage_names:
        b0 = sources[0]
        itemsize = int(np.dtype(b0.schema.dtype(s).np).itemsize) \
            if _device_source(b0) is not None \
            else b0.columns[s].data.itemsize
        total += K * CAP * itemsize
        if any(_source_has_valid(b, s) for b in sources):
            total += K * CAP
    return total


def scan_capacity(sources, pad_to: int = 0) -> int:
    """Row slots of the superblock `estimate_scan_bytes` prices: K
    stacked sources (padded to the shape bucket) at the max capacity."""
    if not sources:
        return 0
    return max(len(sources), pad_to) * max(_source_cap(b) for b in sources)


class DeviceColumnCache:
    def __init__(self, budget_bytes: int = DEFAULT_BUDGET):
        import threading
        self.budget = budget_bytes
        self._entries: OrderedDict = OrderedDict()  # (pid, col) -> (data, valid, nbytes)
        self.bytes = 0
        # device bytes held by OTHER long-lived caches sharing this HBM
        # budget (the cross-query BuildCache registers here): column
        # eviction makes room for them so the two pools never sum past
        # the device budget
        self.foreign_bytes = 0
        self.hits = 0
        self.misses = 0
        # concurrent readers share the cache; the lock covers the
        # LRU bookkeeping (uploads serialize on the device link anyway)
        self._mu = threading.RLock()

    def _evict(self, room: int = 0):
        """Drop LRU entries until `room` more bytes fit the budget (called
        under `_mu`); what goes is counted."""
        while self.bytes + self.foreign_bytes + room > self.budget \
                and self._entries:
            _key, (_d, _v, nbytes) = self._entries.popitem(last=False)
            self.bytes -= nbytes
            GLOBAL.inc("devcache/evictions")
            GLOBAL.inc("devcache/evicted_bytes", nbytes)

    def acquire_foreign(self, nbytes: int) -> None:
        """Register device bytes owned by another long-lived cache
        against this budget, evicting columns to make room."""
        with self._mu:
            self.foreign_bytes += nbytes
            self._evict()

    def release_foreign(self, nbytes: int) -> None:
        with self._mu:
            self.foreign_bytes = max(0, self.foreign_bytes - nbytes)

    def reserve(self, nbytes: int) -> None:
        """Evict LRU entries until `nbytes` of HBM fits beside the cached
        set — for paths that allocate device memory the cache doesn't
        track (tiled scan stacks, spill partials)."""
        with self._mu:
            self._evict(nbytes)

    def _lookup(self, key):
        with self._mu:
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
                self.hits += 1
            else:
                self.misses += 1
            return hit

    def _insert(self, key, data, valid, nbytes,
                built: Optional[Timer] = None):
        """Insert a freshly built entry; a concurrent builder of the same
        key may have won the race — keep the existing entry (dropping the
        duplicate upload) so bytes accounting stays exact. `built`: the
        timer started before a HOST-built entry's stack and transfer —
        given, the entry counts as an upload (bytes that crossed the
        link, whether or not it won the race)."""
        if built is not None:
            GLOBAL.inc("devcache/uploads")
            GLOBAL.inc("devcache/upload_bytes", nbytes)
            GLOBAL.inc("devcache/upload_ms", built.ms())
        with self._mu:
            hit = self._entries.get(key)
            if hit is not None:
                return hit[0], hit[1]
            self._entries[key] = (data, valid, nbytes)
            self.bytes += nbytes
            self._evict()
            return data, valid

    def column(self, portion: Portion, col: str, device=None):
        """(device data, device valid | None), padded to the portion's
        capacity bucket; committed to `device` when given (mesh placement).

        The stack/upload work runs OUTSIDE the cache mutex — holding it
        across device transfers would serialize every concurrent SELECT's
        data prep on one lock."""
        import jax

        key = (portion.id, col, None if device is None else device.id)
        hit = self._lookup(key)
        if hit is not None:
            # the query's working set includes cache-resident columns —
            # the ledger accounts residency per query, not per upload
            from ydb_tpu.utils import memledger
            memledger.record_padded_buffers(
                "portion_column", "scan_columns", portion.num_rows,
                hit[0].shape[0], hit[0], hit[1])
            return hit[0], hit[1]
        put = (lambda x: jax.device_put(x, device)) if device is not None \
            else jnp.asarray
        built = Timer()
        cd = portion.block.columns[col]
        cap = bucket_capacity(max(portion.num_rows, 1))
        pad = cap - portion.num_rows
        data = put(np.pad(cd.data, (0, pad)) if pad else cd.data)
        valid = None
        nbytes = data.nbytes
        if cd.valid is not None:
            valid = put(np.pad(cd.valid, (0, pad)) if pad else cd.valid)
            nbytes += valid.nbytes
        from ydb_tpu.utils import memledger
        memledger.record_padded_buffers(
            "portion_column", "scan_columns", portion.num_rows, cap,
            data, valid)
        return self._insert(key, data, valid, nbytes, built)

    def superblock(self, table, storage_names: list, rename: dict,
                   snapshot, prune, sources=None, src_ids=None,
                   pad_to: int = 0, info: Optional[dict] = None):
        """Stacked (K, CAP) device arrays covering every visible scan source
        of `table` — the input of the whole-query fused program
        (`ydb_tpu/ops/fused.py`), one upload per column per data version.

        `sources`/`src_ids`: pass a pre-enumerated source list (the
        executor's admission estimate already walked the shards once).

        `pad_to`: quantize the row count up to a shape bucket
        (`progstore/buckets.bucket_sources`) — rows beyond the real K
        are zero-filled with length 0, which the fused kernels mask out
        exactly like a short real source, so a growing table reuses the
        bucket's compiled program instead of minting a shape per count.
        The EFFECTIVE row count rides the cache key (an exact-K stack
        and its padded sibling are different device arrays).

        `info`: given a dict, it is told what this call cost: `bytes`
        stacked on the host and uploaded now (0 on a resident table) and
        `hit`, whether every entry it asked for was resident.

        Returns (arrays {internal: (K,CAP)}, valids {internal: (K,CAP)},
        lengths jnp (K,), K, CAP, dicts) or None when the table has no
        visible sources."""
        if sources is None:
            sources, src_ids = enumerate_scan_sources(table, snapshot, prune)
        if not sources:
            return None
        K = max(len(sources), pad_to)
        CAP = max(_source_cap(b) for b in sources)
        # no snapshot component: src_ids already reflect exactly which
        # sources the snapshot sees (portions are immutable), and
        # data_version covers commits — a snapshot in the key would make
        # every write to ANY table re-stack and re-upload this one
        src_key = (table.uid, table.data_version, tuple(src_ids), CAP, K)

        lengths_np = np.zeros(K, np.int32)
        lengths_np[:len(sources)] = [b.length for b in sources]
        arrays, valids, dicts = {}, {}, {}
        uploaded, all_hit = 0, True
        for s in storage_names:
            out = rename.get(s, s)
            key = ("sbc", src_key, s)
            hit = self._lookup(key)
            all_hit &= hit is not None
            if hit is not None:
                arrays[out] = hit[0]
                if hit[1] is not None:
                    valids[out] = hit[1]
            elif all(_device_source(b) is not None for b in sources):
                # device-resident sources (stage-spine channel
                # landings): stack BY REFERENCE on device — no host
                # readback, no re-upload. Pad regions zero and validity
                # is length-clipped, so the stack is bit-identical to
                # what the host path would have built.
                iota = jnp.arange(CAP, dtype=jnp.int32)
                has_valid = any(_source_has_valid(b, s) for b in sources)
                rows_d, rows_v = [], []
                for b in sources:
                    dv = _device_source(b)
                    act = iota < jnp.int32(b.length)
                    a = dv.arrays[s]
                    if a.shape[0] > CAP:
                        a = a[:CAP]
                    elif a.shape[0] < CAP:
                        a = jnp.concatenate(
                            [a, jnp.zeros(CAP - a.shape[0], a.dtype)])
                    rows_d.append(jnp.where(act, a, 0))
                    if has_valid:
                        va = dv.valids.get(s)
                        if va is not None:
                            if va.shape[0] > CAP:
                                va = va[:CAP]
                            elif va.shape[0] < CAP:
                                va = jnp.concatenate(
                                    [va, jnp.zeros(CAP - va.shape[0],
                                                   jnp.bool_)])
                            va = va & act
                        else:
                            va = act
                        rows_v.append(va)
                for _ in range(K - len(sources)):
                    rows_d.append(jnp.zeros(CAP, rows_d[0].dtype))
                    if has_valid:
                        rows_v.append(jnp.zeros(CAP, jnp.bool_))
                d = jnp.stack(rows_d)
                v = jnp.stack(rows_v) if has_valid else None
                nbytes = d.nbytes + (v.nbytes if v is not None else 0)
                d, v = self._insert(key, d, v, nbytes)
                arrays[out] = d
                if v is not None:
                    valids[out] = v
            else:
                # stack + upload OUTSIDE the mutex (see column())
                built = Timer()
                dtype = sources[0].columns[s].data.dtype
                stack = np.zeros((K, CAP), dtype=dtype)
                has_valid = any(b.columns[s].valid is not None
                                for b in sources)
                vstack = np.zeros((K, CAP), np.bool_) if has_valid else None
                for k, b in enumerate(sources):
                    cd = b.columns[s]
                    stack[k, :b.length] = cd.data
                    if vstack is not None:
                        vstack[k, :b.length] = (cd.valid if cd.valid is not None
                                                else True)
                d = jnp.asarray(stack)
                v = jnp.asarray(vstack) if vstack is not None else None
                nbytes = d.nbytes + (v.nbytes if v is not None else 0)
                uploaded += nbytes
                d, v = self._insert(key, d, v, nbytes, built)
                arrays[out] = d
                if v is not None:
                    valids[out] = v
            dv0 = _device_source(sources[0])
            dic = dv0.dictionaries.get(s) if dv0 is not None \
                else sources[0].columns[s].dictionary
            if dic is not None:
                dicts[out] = dic

        lkey = ("sbl", src_key)
        lhit = self._lookup(lkey)
        all_hit &= lhit is not None
        if lhit is None:
            built = Timer()
            lengths = jnp.asarray(lengths_np)
            uploaded += lengths.nbytes
            lengths, _ = self._insert(lkey, lengths, None, lengths.nbytes,
                                      built)
        else:
            lengths = lhit[0]
        if info is not None:
            info.update(bytes=uploaded, hit=all_hit)
        return arrays, valids, lengths, K, CAP, dicts

    def device_block(self, portion: Portion, columns: list,
                     rename: Optional[dict] = None,
                     device=None) -> DeviceBlock:
        """Assemble a DeviceBlock for a portion from cached columns."""
        import jax

        rename = rename or {}
        from ydb_tpu.core.schema import Column, Schema
        cap = bucket_capacity(max(portion.num_rows, 1))
        arrays, valids, dicts = {}, {}, {}
        cols = []
        for name in columns:
            out = rename.get(name, name)
            d, v = self.column(portion, name, device)
            arrays[out] = d
            if v is not None:
                valids[out] = v
            cd = portion.block.columns[name]
            if cd.dictionary is not None:
                dicts[out] = cd.dictionary
            cols.append(Column(out, portion.block.schema.dtype(name)))
        length = jax.device_put(np.int32(portion.num_rows), device) \
            if device is not None else jnp.int32(portion.num_rows)
        return DeviceBlock(Schema(cols), arrays, valids, length, cap, dicts)
