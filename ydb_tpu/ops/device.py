"""Device-resident blocks.

A ``DeviceBlock`` keeps a block's columns on the accelerator between
operators of the same stage — the analog of MiniKQL block values flowing
between Block* computation nodes without leaving the engine
(`mkql_computation_node_holders.h:577` TArrowBlock). Host round-trips happen
only at channel boundaries (serialization) or result egress.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ydb_tpu.core.block import ColumnData, HostBlock
from ydb_tpu.core.dictionary import Dictionary
from ydb_tpu.core.schema import Schema


def bucket_capacity(n: int, minimum: int = 8192) -> int:
    cap = minimum
    while cap < n:
        cap *= 2
    return cap


@dataclass
class DeviceBlock:
    schema: Schema
    arrays: dict                      # name -> jnp array (len = capacity)
    valids: dict                      # name -> jnp bool array (subset of names)
    length: object                    # traced/concrete scalar int32
    capacity: int
    dictionaries: dict = field(default_factory=dict)  # name -> Dictionary

    def sig(self) -> tuple:
        return tuple((c.name, c.dtype.kind.value, c.name in self.valids)
                     for c in self.schema)


def to_device(block: HostBlock, capacity: Optional[int] = None,
              device=None) -> DeviceBlock:
    """Upload a host block, optionally committed to a specific device
    (row-partition placement on a mesh: jit'd programs follow committed
    inputs, so per-portion work lands on the portion's device)."""
    import jax

    cap = capacity or bucket_capacity(max(block.length, 1))
    put = (lambda x: jax.device_put(x, device)) if device is not None \
        else jnp.asarray
    arrays, valids, dicts = {}, {}, {}
    pad = cap - block.length
    for c in block.schema:
        cd = block.columns[c.name]
        data = np.pad(cd.data, (0, pad)) if pad else cd.data
        arrays[c.name] = put(data)
        if cd.valid is not None:
            v = np.pad(cd.valid, (0, pad)) if pad else cd.valid
            valids[c.name] = put(v)
        if cd.dictionary is not None:
            dicts[c.name] = cd.dictionary
    length = put(np.int32(block.length)) if device is not None \
        else jnp.int32(block.length)
    # resource ledger: the upload's padded bytes (capacity bucket) vs the
    # block's live rows — shape arithmetic only, never a sync
    from ydb_tpu.utils import memledger
    memledger.record_padded_buffers("device_block", "upload",
                                    block.length, cap, arrays, valids)
    return DeviceBlock(block.schema, arrays, valids, length, cap, dicts)


def host_column(data, valid, dtype, dictionary) -> ColumnData:
    """Host materialization convention shared by every device→host path
    (`to_host`, the fused unpack): restore the schema dtype, collapse
    all-valid masks to None, reattach the dictionary."""
    # lint: transfer-ok(inputs already landed by the caller's batched device_get)
    d = np.asarray(data).astype(dtype.np)
    v = valid
    if v is not None:
        # lint: transfer-ok(inputs already landed by the caller's batched device_get)
        v = np.asarray(v)
        if v.all():
            v = None
    return ColumnData(d, v, dictionary)


def to_host(dblock: DeviceBlock) -> HostBlock:
    import jax

    n = int(dblock.length)
    # one batched device→host transfer for all columns (each np.asarray on
    # a device array is a separate blocking round-trip)
    sliced = {name: a[:n] for name, a in dblock.arrays.items()}
    vsliced = {name: v[:n] for name, v in dblock.valids.items()}
    # lint: transfer-ok(result egress — the one batched client-boundary readback)
    host_a, host_v = jax.device_get((sliced, vsliced))
    from ydb_tpu.utils import memledger
    memledger.record_transfer("ops/device.py::to_host",
                              memledger.deep_nbytes((host_a, host_v)),
                              boundary=True)
    cols = {}
    for c in dblock.schema:
        cols[c.name] = host_column(host_a[c.name], host_v.get(c.name),
                                   c.dtype, dblock.dictionaries.get(c.name))
    return HostBlock(dblock.schema, cols, n)


class DeviceStageBlock(HostBlock):
    """A stage-boundary block whose columns still live on the
    accelerator: the device-resident spine's unit of flow between DQ
    stages.

    It IS a ``HostBlock`` to every consumer that only looks at
    ``schema``/``length`` or calls the block protocol — but ``columns``
    is a lazy property that materializes host arrays ONCE (one batched
    ``to_host`` readback, honestly counted as a boundary transfer) the
    first time a host-only path touches it. Stage plumbing that stays
    device-resident (the planned ICI exchange, the device landing in
    the channel table, the fused scan fast path) reads ``.device``
    directly and never triggers that readback; ``to_pandas`` therefore
    survives only where a consumer genuinely leaves the device plane —
    the client-result boundary.

    ``length`` is host-known (stamped at capture from the fused
    program's length scalar), so shape planning — segment sizing, the
    count exchange, channel stats — never syncs."""

    def __init__(self, device: DeviceBlock, length: int):
        # deliberately NOT the dataclass __init__: `columns` is a
        # read-only property here, not a field
        self.schema = device.schema
        self.device = device
        self.length = int(length)
        self._cols = None

    @property
    def columns(self) -> dict:
        if self._cols is None:
            self._cols = to_host(
                DeviceBlock(self.device.schema, self.device.arrays,
                            self.device.valids, self.length,
                            self.device.capacity,
                            self.device.dictionaries)).columns
        return self._cols

    @property
    def materialized(self) -> bool:
        """True once a host path has forced the readback."""
        return self._cols is not None

    def live_nbytes(self) -> int:
        """Live payload bytes (length x schema itemsizes + masks) —
        shape arithmetic only, never a device sync."""
        n = 0
        for c in self.schema:
            n += self.length * int(np.dtype(c.dtype.np).itemsize)
            if c.name in self.device.valids:
                n += self.length
        return n

    def project(self, output: list) -> "DeviceStageBlock":
        """Device-side mirror of the executor's `_project_output`
        (rename + duplicate-label suffixing) — array references move,
        no bytes do."""
        from ydb_tpu.core.schema import Column

        arrays, valids, dicts = {}, {}, {}
        schema_cols = []
        used = set()
        for (internal, label) in output:
            lbl = label
            k = 2
            while lbl in used:
                lbl = f"{label}_{k}"
                k += 1
            used.add(lbl)
            arrays[lbl] = self.device.arrays[internal]
            if internal in self.device.valids:
                valids[lbl] = self.device.valids[internal]
            if internal in self.device.dictionaries:
                dicts[lbl] = self.device.dictionaries[internal]
            schema_cols.append(Column(lbl, self.schema.dtype(internal)))
        dev = DeviceBlock(Schema(schema_cols), arrays, valids,
                          self.device.length, self.device.capacity, dicts)
        return DeviceStageBlock(dev, self.length)


class DeviceResultFuture:
    """Handle to a dispatched device computation whose device→host
    readout is deferred until the result is actually consumed.

    The dispatch cliff (PERF.md) makes overlap the whole game: a
    dispatch is ~async and cheap, but every blocking readout costs a
    full link round trip — so a query pipeline that dispatches query
    N+1 while query N drains D2H turns N × (dispatch + readout) into
    ~max(compute) + one readout. The future is the seam: the executor
    dispatches the fused program WITHOUT `block_until_ready`, wraps the
    single-pytree `jax.device_get` (plus host-side unpack) in `fetch`,
    and the engine resolves it in its lock-free readout phase.

    `result()` runs `fetch` exactly once (thread-safe) and caches the
    block — or the exception, which re-raises on every later call.
    """

    __slots__ = ("_fetch", "_value", "_exc", "_done", "_mu")

    def __init__(self, fetch):
        import threading
        self._fetch = fetch            # () -> HostBlock
        self._value = None
        self._exc = None
        self._done = False
        self._mu = threading.Lock()

    @classmethod
    def completed(cls, block) -> "DeviceResultFuture":
        """Wrap an already-materialized result (host-lane / distributed
        paths) so every executor path speaks one readout protocol."""
        fut = cls(None)
        fut._value = block
        fut._done = True
        return fut

    def done(self) -> bool:
        return self._done

    def result(self):
        with self._mu:
            if not self._done:
                # only Exception is cached as the computation's outcome;
                # control-flow BaseExceptions (KeyboardInterrupt,
                # SystemExit) propagate WITHOUT poisoning the future —
                # _done stays False so a later result() can refetch
                try:
                    self._value = self._fetch()
                except Exception as e:       # noqa: BLE001 — re-raised
                    self._exc = e
                self._done = True
                self._fetch = None           # drop device refs promptly
            if self._exc is not None:
                raise self._exc
            return self._value

    def map(self, fn) -> "DeviceResultFuture":
        """Chain a host-side transform onto the readout (projection,
        offset slicing) without forcing it now."""
        return DeviceResultFuture(lambda: fn(self.result()))


def to_host_async(dblock: DeviceBlock) -> DeviceResultFuture:
    """`to_host` as a future: the device program stays in flight (jax
    async dispatch) and the single pytree `device_get` runs when the
    result is consumed."""
    return DeviceResultFuture(lambda: to_host(dblock))
