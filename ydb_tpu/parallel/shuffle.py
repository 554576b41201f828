"""Device-mesh hash shuffle + distributed two-phase aggregation.

The TPU-native replacement for DQ's hash-shuffle channels
(`DqCnHashShuffle`, partitioner `dq_output_consumer.cpp:99`, channel data
events `dq_compute_actor_channels.h:90`): instead of packing rows into
TEvChannelData and pushing them over Interconnect TCP, every stage-boundary
repartition is a single `jax.lax.all_to_all` across the pod's ICI mesh:

  per device:  partial GroupBy (BlockCombineHashed analog)
               → bucket rows by key hash  (TDqOutputHashPartitionConsumer)
               → build D fixed-capacity segments
  all_to_all:  segment d of device s  →  device d segment s     (ICI)
  per device:  compact received segments → final GroupBy
               (BlockMergeFinalizeHashed analog)

Group keys are disjoint across devices after the shuffle, so the final
merge is local and the host only concatenates per-device results.

Everything is static-shape: segments have a fixed per-edge capacity and
carry a row count. Overflow is detected on device (a bool reduced across
segments); `run` then rebuilds with full-capacity segments — which cannot
overflow — and reruns the batch, the analog of DQ channel spilling
(`dq/actors/spilling/channel_storage.cpp`).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ydb_tpu.core.block import ColumnData, HostBlock
from ydb_tpu.core.dtypes import DType, Kind
from ydb_tpu.core.schema import Column, Schema
from ydb_tpu.ops import ir
from ydb_tpu.ops.device import bucket_capacity
from ydb_tpu.ops.fused import _named, mesh_program_name
from ydb_tpu.ops.xla_exec import _trace_program, compress, groupby_tuning
from ydb_tpu.parallel.collective import (AXIS, bucket_of, bucket_segments,
                                         compact_segments, env_row_bytes,
                                         exchange_segments, gather_all,
                                         record_exchange_bytes)
from ydb_tpu.utils import progstats


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.array(devs[:n]), (AXIS,))


@partial(jax.jit, static_argnames=("caps", "pcap", "names"))
def _fuse_device_blocks(blocks, caps, pcap, names):
    """Concat + compact a device's partial blocks into one [pcap] buffer
    (runs on the device that owns the blocks — committed inputs pin the
    execution there)."""
    datas = {n: [] for n in names}
    vals = {n: [] for n in names}
    masks = []
    total = 0
    for (arrays, valids, length), cap in zip(blocks, caps):
        iota = jnp.arange(cap, dtype=jnp.int32)
        masks.append(iota < length)
        total += cap
        for n in names:
            datas[n].append(arrays[n])
            v = valids.get(n)
            vals[n].append(v if v is not None
                           else jnp.ones((cap,), jnp.bool_))
    env = {n: (jnp.concatenate(datas[n]), jnp.concatenate(vals[n]))
           for n in names}
    mask = jnp.concatenate(masks)
    env, cnt = compress(env, jnp.int32(total), mask, total)
    out_d, out_v = {}, {}
    for n in names:
        d, v = env[n]
        if total < pcap:
            d = jnp.pad(d, (0, pcap - total))
            v = jnp.pad(v, (0, pcap - total))
        else:
            d, v = d[:pcap], v[:pcap]
        out_d[n], out_v[n] = d, v
    return out_d, out_v, cnt


def live_capacity(per_dev_blocks: list) -> int:
    """Capacity of one device's fused buffer: the most LIVE rows any
    device holds in its blocks, rounded up a power of two — not the sum
    of the blocks' capacities, which after an exchange is ndev times the
    rows that can be live and multiplied again at the next one. One small
    transfer of the blocks' row counts; their programs have run by now,
    or the wait for them falls here."""
    lens = jax.device_get([[b.length for b in blks]
                           for blks in per_dev_blocks])
    return bucket_capacity(max(max(int(sum(ls)) for ls in lens), 1),
                           minimum=128)


def record_exchange_rows(kind: str, lengths, rows) -> None:
    """Book the rows a mesh exchange was fed, per device that held them:
    `mesh/exchange_rows/<kind>/dev<id>`. `lengths` is the exchange's
    sharded row-count vector (one shard per mesh device), `rows` its
    values already on the host — the caller reads them in a transfer it
    makes anyway or after the exchange is dispatched."""
    from ydb_tpu.utils.metrics import GLOBAL
    for shard in lengths.addressable_shards:
        # lint: allow-counters(mesh/exchange_rows/* registered)
        GLOBAL.inc(f"mesh/exchange_rows/{kind}/dev{shard.device.id}",
                   int(rows[shard.index[0]].sum()))


@dataclass
class DistributedAgg:
    """Compiled distributed two-phase aggregation over a device mesh."""

    partial: ir.Program
    final: ir.Program
    in_schema: Schema
    mesh: Mesh
    seg_rows: int = 0        # per-edge segment capacity (0: = capacity)
    table: str = ""          # the plan's root table: names the program

    def __post_init__(self):
        # sig -> (shard_fn, out-schema holder): alternating signatures
        # (capacity buckets, valid sets, param sets) each keep their
        # compiled fn instead of thrashing a single slot
        self._fns: dict = {}
        self.name = mesh_program_name(
            "merge", self.table, [self.partial, self.final],
            [",".join(self.in_schema.names)])

    # -- compile ----------------------------------------------------------

    def _build(self, cap: int, valid_names: tuple, param_names: tuple):
        ndev = self.mesh.devices.size
        in_cols = list(self.in_schema.columns)
        partial_prog, final_prog = self.partial, self.final

        gb = next(c for c in partial_prog.commands
                  if isinstance(c, ir.GroupBy))
        key_names = list(gb.keys)

        def per_device(arrays, valids, length, params):
            env = {}
            for c in in_cols:
                env[c.name] = (arrays[c.name][0], valids.get(c.name))
            env = {k: (d, v[0] if v is not None else None)
                   for k, (d, v) in env.items()}
            with jax.named_scope("partial"):
                env, glen, sel, schema = _trace_program(
                    partial_prog, in_cols, cap, env, length[0], params)
            assert sel is None  # partial ends in GroupBy
            names = list(schema.names)
            # the scatter group-by path shrinks the working capacity
            pcap = next(iter(env.values()))[0].shape[0] if env else cap
            seg = min(self.seg_rows or pcap, pcap)
            row_bytes = env_row_bytes(env, names)

            if not key_names or ndev == 1:
                # global agg: no shuffle, merge via all_gather (every
                # device receives the other devices' `pcap` rows: the
                # wire of ndev² segments of `pcap`, less a device's own)
                wire["shape"] = (pcap, row_bytes)
                env2, tot = gather_all(
                    {n: env[n][0] for n in names},
                    {n: env[n][1] if env[n][1] is not None
                     else jnp.ones((pcap,), jnp.bool_) for n in names},
                    glen, pcap, ndev, names)
                with jax.named_scope("merge"):
                    fenv, flen, fsel, fschema = _trace_program(
                        final_prog, list(schema.columns), ndev * pcap, env2,
                        tot, params)
                    if fsel is not None:
                        fcap = next(iter(fenv.values()))[0].shape[0] \
                            if fenv else ndev * pcap
                        fenv, flen = compress(fenv, flen, fsel, fcap)
                # merged result is identical on every device — report once
                flen = jnp.where(jax.lax.axis_index(AXIS) == 0, flen, 0)
                out_d = {n: fenv[n][0] for n in fschema.names}
                out_v = {n: (fenv[n][1] if fenv[n][1] is not None
                             else jnp.ones_like(out_d[n], dtype=jnp.bool_))
                         for n in fschema.names}
                return out_d, out_v, flen, jnp.bool_(False), glen, tuple(
                    (c.name, c.dtype.kind.value, c.dtype.nullable)
                    for c in fschema.columns)

            # hash shuffle: build ndev segments of seg rows each, swap
            # them over ICI, compact (shared with shuffle_join + the DQ
            # ICI channel plane — parallel/collective.py)
            wire["shape"] = (seg, row_bytes)
            bucket = bucket_of(env, key_names, ndev)
            stacked_d, stacked_v, cnts, overflow = bucket_segments(
                env, bucket, glen, pcap, seg, ndev, names)
            recv_d, recv_v, recv_c = exchange_segments(
                stacked_d, stacked_v, cnts, names)
            flat = ndev * seg
            env2, tot = compact_segments(recv_d, recv_v, recv_c, seg,
                                         ndev, names)
            with jax.named_scope("merge"):
                fenv, flen, fsel, fschema = _trace_program(
                    final_prog, list(schema.columns), flat, env2, tot,
                    params)
                if fsel is not None:
                    fcap = next(iter(fenv.values()))[0].shape[0] \
                        if fenv else flat
                    fenv, flen = compress(fenv, flen, fsel, fcap)
            out_d = {n: fenv[n][0] for n in fschema.names}
            out_v = {n: (fenv[n][1] if fenv[n][1] is not None
                         else jnp.ones_like(out_d[n], dtype=jnp.bool_))
                     for n in fschema.names}
            return out_d, out_v, flen, overflow, cnts.sum(), tuple(
                (c.name, c.dtype.kind.value, c.dtype.nullable)
                for c in fschema.columns)

        # filled at trace time: the out schema, and the exchange's
        # (segment rows, bytes a row) for the byte counters
        out_schema_holder = wire = {}

        def wrapper(arrays, valids, lengths, params):
            out_d, out_v, flen, overflow, sent, out_sig = per_device(
                arrays, valids, lengths, params)
            out_schema_holder["sig"] = out_sig
            return (
                {n: x[None] for n, x in out_d.items()},
                {n: x[None] for n, x in out_v.items()},
                flen[None],
                overflow[None],
                sent[None],
            )

        pspec_in = (
            {c.name: P(AXIS, None) for c in in_cols},
            {n: P(AXIS, None) for n in valid_names},
            P(AXIS),
            {n: P() for n in param_names},
        )
        shard_fn = jax.jit(jax.shard_map(
            _named(wrapper, self.name), mesh=self.mesh, in_specs=pspec_in,
            out_specs=(P(AXIS, None), P(AXIS, None), P(AXIS), P(AXIS),
                       P(AXIS)),
            check_vma=False,
        ))
        return shard_fn, out_schema_holder

    def _filled(self, sig, build_args: tuple, call_args: tuple):
        """The compiled program of `sig`, built on a miss and captured
        through the program inventory (`utils/progstats.capture`: one
        trace, one compile, `prog/registered`, its cost analysis):
        -> (fn, holder, fresh)."""
        entry = self._fns.get(sig)
        if entry is not None:
            progstats.record_hit(getattr(entry[0], "key_id", None))
            return entry + (False,)
        fn, holder = self._build(*build_args)
        key = (self.name, self.partial.fingerprint(),
               self.final.fingerprint(),
               tuple((c.name, c.dtype.kind.value, c.dtype.nullable)
                     for c in self.in_schema.columns),
               self.mesh.devices.size, sig)
        # the out schema is a product of the trace: never from the store
        fn = progstats.capture("mesh-merge", key, fn, call_args,
                               consult_store=False)
        self._fns[sig] = (fn, holder)
        return fn, holder, True

    def _book_exchange(self, holder: dict, sent) -> None:
        """`mesh/exchange_bytes/merge` and its live part: `sent` is the
        rows each device put into segments (its groups after the local
        combine), read in a transfer the caller makes anyway; of a hash
        over the group keys (ndev-1)/ndev of them leave their device."""
        seg, row_bytes = holder["shape"]
        ndev = self.mesh.devices.size
        record_exchange_bytes("merge", ndev, seg, row_bytes,
                              int(sent.sum()) * (ndev - 1) // ndev)

    # -- run ---------------------------------------------------------------

    def run(self, blocks_per_device: list, params: Optional[dict] = None
            ) -> HostBlock:
        """blocks_per_device: one HostBlock per mesh device (row partition)."""
        ndev = self.mesh.devices.size
        assert len(blocks_per_device) == ndev
        params = params or {}
        cap = bucket_capacity(max(max(b.length for b in blocks_per_device), 1))
        arrays, valids, lengths = {}, {}, []
        valid_names = []
        for c in self.in_schema:
            stk, vstk, any_valid = [], [], False
            for b in blocks_per_device:
                cd = b.columns[c.name]
                pad = cap - b.length
                stk.append(np.pad(cd.data, (0, pad)))
                if cd.valid is not None:
                    any_valid = True
                    vstk.append(np.pad(cd.valid, (0, pad)))
                else:
                    vstk.append(np.ones(cap, np.bool_))
            arrays[c.name] = np.stack(stk)
            if any_valid:
                valids[c.name] = np.stack(vstk)
                valid_names.append(c.name)
        lengths = np.array([b.length for b in blocks_per_device],
                           dtype=np.int32)

        # groupby_tuning is part of the identity: _build traces the
        # partial/final GroupBy under the env knobs live at trace time,
        # and this instance can outlive a knob flip (tests construct
        # DistributedAgg directly; the executor's outer cache already
        # keys on the tuning, this inner cache must agree)
        sig = ("host", cap, tuple(sorted(valid_names)),
               tuple(sorted(params)), self.seg_rows, groupby_tuning())
        dev_params = {k: jnp.asarray(v) for k, v in params.items()}
        fn, holder, _fresh = self._filled(
            sig, (cap, tuple(sorted(valid_names)), tuple(sorted(params))),
            (arrays, valids, lengths, dev_params))
        out_d, out_v, flens, overflow, sent = fn(arrays, valids, lengths,
                                                 dev_params)
        # ONE batched device_get for the overflow verdict (was a
        # per-flag np.asarray sync — a baselined host-sync debt)
        overflow, sent = jax.device_get((overflow, sent))
        if overflow.any():
            # overflowed rows were clamped on device, so that result is
            # partial — discard it, rebuild with full-capacity segments
            # (seg = pcap ≥ any per-bucket count: cannot overflow) and rerun
            assert self.seg_rows, "full-capacity segments cannot overflow"
            self.seg_rows = 0
            return self.run(blocks_per_device, params)
        self._holder = holder
        self._book_exchange(holder, sent)
        # padding-waste account of the shuffle's fixed-capacity segments
        from ydb_tpu.parallel.collective import segment_pad_account
        segment_pad_account(
            "shuffle_segments", ndev, min(self.seg_rows or cap, cap),
            int(lengths.sum()),
            sum(a.dtype.itemsize for a in arrays.values())
            + len(valids))
        dicts = {}
        for b in blocks_per_device:
            for name, cd in b.columns.items():
                if cd.dictionary is not None:
                    dicts[name] = cd.dictionary
        return self._finish(out_d, out_v, flens, dicts)

    def run_device_blocks(self, per_dev_blocks: list,
                          params: Optional[dict] = None,
                          await_device=None) -> HostBlock:
        """Distributed merge over ALREADY device-resident partials.

        ``per_dev_blocks[d]`` is a list of DeviceBlocks committed to mesh
        device d (the per-portion partial-aggregation outputs of the SQL
        executor). Each device fuses its partials locally (concat +
        compress, jit'd on that device), the fused buffers are assembled
        into one globally-sharded array — no host round-trip — and the
        shard-mapped shuffle+merge runs over it.

        `await_device(outputs, t_enqueued, program key id, fresh)`: the
        executor's accounting of the wait for the devices
        (`Executor._await_device`); without it the wait falls into the
        first transfer below.
        """
        ndev = self.mesh.devices.size
        assert len(per_dev_blocks) == ndev
        assert all(blks for blks in per_dev_blocks), \
            "every device needs at least one (possibly empty) partial block"
        params = params or {}
        names = tuple(self.in_schema.names)
        pcap = live_capacity(per_dev_blocks)
        fused = []
        for blks in per_dev_blocks:
            blocks_in = tuple((b.arrays, b.valids, b.length) for b in blks)
            caps = tuple(b.capacity for b in blks)
            fused.append(_fuse_device_blocks(blocks_in, caps, pcap, names))

        sh2 = NamedSharding(self.mesh, P(AXIS, None))
        sh1 = NamedSharding(self.mesh, P(AXIS))
        arrays = {n: jax.make_array_from_single_device_arrays(
            (ndev, pcap), sh2, [fused[d][0][n][None] for d in range(ndev)])
            for n in names}
        valids = {n: jax.make_array_from_single_device_arrays(
            (ndev, pcap), sh2, [fused[d][1][n][None] for d in range(ndev)])
            for n in names}
        lengths = jax.make_array_from_single_device_arrays(
            (ndev,), sh1, [fused[d][2][None] for d in range(ndev)])

        sig = ("device", pcap, tuple(sorted(names)), tuple(sorted(params)),
               self.seg_rows, groupby_tuning())
        dev_params = {k: jnp.asarray(v) for k, v in params.items()}
        fn, self._holder, fresh = self._filled(
            sig, (pcap, tuple(sorted(names)), tuple(sorted(params))),
            (arrays, valids, lengths, dev_params))
        out_d, out_v, flens, overflow, sent = fn(arrays, valids, lengths,
                                                 dev_params)
        if await_device is not None:
            await_device((out_d, out_v, flens), time.perf_counter(),
                         getattr(fn, "key_id", None), fresh)
        # seg_rows here is 0 (full capacity) or a PROVEN merge-GroupBy
        # bound (each producer's partial holds ≤ out_bound groups, so a
        # bound-bucket segment cannot overflow) — either way overflow is
        # impossible; keep the invariant checked LOUDLY (an understated
        # bound must crash, never silently clamp rows). Batched
        # device_get, not a per-flag np.asarray sync.
        over, in_rows, sent = jax.device_get((overflow, lengths, sent))
        assert not over.any(), \
            "proven segment bound overflowed — bound source is wrong"
        record_exchange_rows("merge", lengths, in_rows)
        self._book_exchange(self._holder, sent)
        # NO pad record here: the partials' live row counts are
        # device-resident scalars, and the ledger must never force a
        # sync to measure — the host-input `run` path carries the gauge
        dicts = {}
        for blks in per_dev_blocks:
            for b in blks:
                dicts.update(b.dictionaries)
        return self._finish(out_d, out_v, flens, dicts)

    def _finish(self, out_d, out_v, flens, dicts) -> HostBlock:
        """Per-device results → host concat (groups are disjoint)."""
        ndev = self.mesh.devices.size
        out_sig = self._holder["sig"]
        out_cols = [Column(n, DType(Kind(k), nullable))
                    for (n, k, nullable) in out_sig]
        schema = Schema(out_cols)
        # ONE batched device→host transfer for every (column, device) —
        # the to_host discipline (ops/device.py): each np.asarray on a
        # device array is its own blocking round trip, 2·cols·ndev of
        # them before this was batched
        host_d, host_v, flens = jax.device_get(
            ({c.name: out_d[c.name] for c in out_cols},
             {c.name: out_v[c.name] for c in out_cols}, flens))
        from ydb_tpu.utils import memledger
        memledger.record_transfer(
            "parallel/shuffle.py::DistributedAgg._finish",
            memledger.deep_nbytes((host_d, host_v)))
        blocks = []
        for d in range(ndev):
            n = int(flens[d])
            cols = {}
            for c in out_cols:
                data = host_d[c.name][d][:n].astype(c.dtype.np)
                v = host_v[c.name][d][:n]
                cols[c.name] = ColumnData(
                    data, None if v.all() else v, dicts.get(c.name))
            blocks.append(HostBlock(schema, cols, n))
        return HostBlock.concat(blocks)
