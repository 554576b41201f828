"""Distributed shuffle join: partitioned build + probe-row exchange.

The reference's shuffle-join strategy (`dq_opt_join.cpp` EJoinAlgoType::
ShuffleJoin over `dq_tasks_graph.h:43` task stages): when a join's build
side is too large to broadcast to every node, BOTH sides hash-partition
by the join key — stage N builds its partition's hash table, stage N+1
routes each probe row to its key's owner over the interconnect.

TPU shape: the build is hash-partitioned host-side (splitmix64, the same
family as every other routing decision) with partition d committed to
mesh device d — no device holds the full build. Probe rows arrive as the
per-device stage-A outputs; ONE `shard_map` program buckets them by key,
exchanges segments via `jax.lax.all_to_all` over ICI, compacts, probes
the LOCAL build partition with a vectorized searchsorted, and runs the
rest of the pipeline (post-join programs + partial aggregation) without
leaving the device.

The segments are sized from COUNTED rows: each device counts its live
rows per target with the program's own bucket function, the host reads
the ndev x ndev counts (where it read the row counts before), a segment
holds the largest of them and the buffer after the exchange the most rows
any device receives, each rounded up a power of two. Segments of the whole
capacity made everything after the exchange work on ndev times the probe
side's capacity (8 Mi slots a chip for 0.8 M live rows of TPC-H Q3 at
SF1, and the partials' merge on 32 Mi: 52 s of device a statement,
PERF.md round 28).
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ydb_tpu.core.block import HostBlock
from ydb_tpu.core.dtypes import DType, Kind
from ydb_tpu.core.schema import Column, Schema
from ydb_tpu.ops import ir
from ydb_tpu.ops.device import DeviceBlock, bucket_capacity
from ydb_tpu.ops.fused import _named, mesh_program_name
from ydb_tpu.ops.join import _select_and_gather, build as build_table
from ydb_tpu.ops.xla_exec import _trace_program, compress, groupby_tuning
from ydb_tpu.parallel.collective import (AXIS, bucket_of, bucket_segments,
                                         compact_segments,
                                         exchange_segments,
                                         record_exchange_bytes)
from ydb_tpu.parallel.shuffle import (_fuse_device_blocks, live_capacity,
                                      record_exchange_rows)
from ydb_tpu.utils import progstats
from ydb_tpu.utils.hashing import splitmix64


def partition_build(built: HostBlock, key: str, payload: list, ndev: int):
    """Hash-partition a build side into ndev per-device BuildTables plus
    the padded/stacked arrays a shard_map consumes. Returns
    (stacked arrays dict, payload schema, dictionaries, max row count)."""
    from ydb_tpu.ops.join import _host_key

    enc, valid = _host_key(built, key)
    if valid is not None:
        keep = np.nonzero(valid)[0]       # NULL keys never match
        built = built.take(keep)
        enc = enc[keep]
    h = splitmix64(np, enc.astype(np.int64))
    part = (h % np.uint64(ndev)).astype(np.int64)
    tables = []
    for p in range(ndev):
        idx = np.nonzero(part == p)[0]
        tables.append(build_table(built.take(idx), key, list(payload)))
    cap = max(t.keys_sorted.shape[0] for t in tables)
    keys = np.full((ndev, cap), np.iinfo(np.int64).max, np.int64)
    ns = np.zeros(ndev, np.int32)
    payload_np: dict = {n: None for n in payload}
    pvalid_np: dict = {}
    # ONE batched device→host landing for every partition's keys/payload
    # (was 2·cols·ndev per-array np.asarray round trips — a baselined
    # host-sync debt); a partition already host-side passes through
    fetched = jax.device_get(
        [{"keys": t.keys_sorted, "payload": dict(t.payload),
          "pvalid": dict(t.payload_valid)} for t in tables])
    for p, (t, host) in enumerate(zip(tables, fetched)):
        kcap = host["keys"].shape[0]
        keys[p, :kcap] = host["keys"]
        ns[p] = t.n
        for n in payload:
            arr = host["payload"][n]
            if payload_np[n] is None:
                payload_np[n] = np.zeros((ndev, cap), arr.dtype)
            payload_np[n][p, :len(arr)] = arr
            pv = host["pvalid"].get(n)
            if pv is not None:
                pvalid_np.setdefault(
                    n, np.zeros((ndev, cap), np.bool_))
                pvalid_np[n][p, :len(pv)] = pv
    dicts = dict(tables[0].dictionaries) if tables else {}
    from ydb_tpu.utils import memledger
    memledger.record_padded_buffers(
        "shuffle_join_build", "build", int(ns.sum()), ndev * cap,
        keys, payload_np, pvalid_np)
    return ({"keys": keys, "ns": ns, "payload": payload_np,
             "pvalid": pvalid_np},
            tables[0].schema if tables else Schema([]), dicts, cap)


@partial(jax.jit, static_argnames=("ndev",))
def _target_counts(key, valid, length, ndev):
    """[ndev] live rows of one device's fused probe buffer per target
    device: the exchange program's own `bucket_of` over the same key
    column, so a segment sized from these counts cannot overflow."""
    bucket = bucket_of({"k": (key, valid)}, ["k"], ndev)
    active = jnp.arange(key.shape[0], dtype=jnp.int32) < length
    return jnp.stack([jnp.sum(active & (bucket == t), dtype=jnp.int32)
                      for t in range(ndev)])


class ShuffleJoin:
    """Compiled probe-row exchange + local probe + post-join pipeline."""

    def __init__(self, mesh, in_schema: Schema, probe_key: str, kind: str,
                 payload_cols: list, mark_col: str, not_in: bool,
                 rest_programs: list, partial, table: str = ""):
        self.mesh = mesh
        self.in_schema = in_schema
        self.probe_key = probe_key
        self.kind = kind
        self.payload_cols = payload_cols       # [Column] appended by probe
        self.mark_col = mark_col
        self.not_in = not_in
        self.rest_programs = rest_programs     # [ir.Program] after the join
        self.partial = partial                 # ir.Program | None
        self._fns: dict = {}
        self.name = mesh_program_name(
            "sj", table, list(rest_programs) + [partial],
            [f"join {probe_key} {kind} "
             f"{','.join(c.name for c in payload_cols)}",
             ",".join(in_schema.names)])

    def _build(self, pcap: int, seg: int, rcap: int, bcap: int,
               payload_names: tuple, pvalid_names: tuple,
               param_names: tuple):
        """The program for `pcap` probe slots a device, segments of `seg`
        rows, `rcap` slots after the exchange (both from counted rows:
        neither can overflow) and build partitions of `bcap` keys."""
        ndev = self.mesh.devices.size
        in_cols = list(self.in_schema.columns)
        names = [c.name for c in in_cols]
        probe_key, kind, not_in = self.probe_key, self.kind, self.not_in
        payload_cols = self.payload_cols
        mark_col = self.mark_col
        rest = list(self.rest_programs)
        partial = self.partial

        def per_device(arrays, valids, length, bkeys, bns, bpay, bpv,
                       params):
            env = {n: (arrays[n][0], valids[n][0]) for n in names}
            glen = length[0]
            # --- route probe rows to their key's owner (ICI all_to_all;
            # shared segment machinery — parallel/collective.py).
            # `seg` holds the largest COUNTED bucket: cannot overflow
            bucket = bucket_of(env, [probe_key], ndev)
            stacked_d, stacked_v, cnts, _ovf = bucket_segments(
                env, bucket, glen, pcap, seg, ndev, names)
            recv_d, recv_v, recv_c = exchange_segments(
                stacked_d, stacked_v, cnts, names)
            flat = min(rcap, ndev * seg)
            env2, tot = compact_segments(recv_d, recv_v, recv_c, seg,
                                         ndev, names, out_cap=flat)

            # --- probe the LOCAL build partition (vectorized binsearch)
            with jax.named_scope("shuffle.probe"):
                d, v = env2[probe_key]
                enc = d.astype(jnp.int64)
                iota2 = jnp.arange(flat, dtype=jnp.int32)
                act2 = iota2 < tot
                matchable = act2 if v is None else (act2 & v)
                keys_local = bkeys[0]
                n_local = bns[0]
                pos = jnp.searchsorted(keys_local, enc).astype(jnp.int32)
                safe = jnp.clip(pos, 0, bcap - 1)
                found = (keys_local[safe] == enc) & matchable \
                    & (safe < n_local)
                payload_local = {n: bpay[n][0] for n in payload_names}
                pvalid_local = {n: bpv[n][0] for n in pvalid_names}
                out_sel, gathered, gathered_valid = _select_and_gather(
                    found, safe, act2, v, n_local, kind, not_in,
                    payload_local, pvalid_local, payload_names)

                schema = Schema(list(in_cols))
                for c in payload_cols:
                    if c.name == mark_col:
                        env2[c.name] = (found, None)
                    elif c.name in gathered:
                        env2[c.name] = (gathered[c.name],
                                        gathered_valid[c.name])
                    schema = Schema([x for x in schema.columns
                                     if x.name != c.name] + [c])
                if kind != "mark":
                    env2, tot = compress(env2, tot, out_sel, flat)

            # --- rest of the pipeline + partial, all on-device
            cap2 = flat
            sel = None
            with jax.named_scope("rest"):
                for prog in rest:
                    env2, tot, sel, schema = _trace_program(
                        prog, schema.columns, cap2, env2, tot, params,
                        sel=sel)
                    if env2:
                        cap2 = next(iter(env2.values()))[0].shape[0]
            with jax.named_scope("partial"):
                if partial is not None:
                    env2, tot, sel, schema = _trace_program(
                        partial, schema.columns, cap2, env2, tot, params,
                        sel=sel)
                    if env2:
                        cap2 = next(iter(env2.values()))[0].shape[0]
                if sel is not None:
                    env2, tot = compress(env2, tot, sel, cap2)
            out_d = {n: env2[n][0] for n in schema.names}
            out_v = {n: (env2[n][1] if env2[n][1] is not None
                         else jnp.ones_like(out_d[n], dtype=jnp.bool_))
                     for n in schema.names}
            return out_d, out_v, tot, tuple(
                (c.name, c.dtype.kind.value, c.dtype.nullable)
                for c in schema.columns)

        holder = {}

        def wrapper(arrays, valids, lengths, bkeys, bns, bpay, bpv, params):
            out_d, out_v, tot, sig = per_device(
                arrays, valids, lengths, bkeys, bns, bpay, bpv, params)
            holder["sig"] = sig
            return ({n: x[None] for n, x in out_d.items()},
                    {n: x[None] for n, x in out_v.items()}, tot[None])

        pspec_in = (
            {n: P(AXIS, None) for n in names},
            {n: P(AXIS, None) for n in names},
            P(AXIS),
            P(AXIS, None),
            P(AXIS),
            {n: P(AXIS, None) for n in payload_names},
            {n: P(AXIS, None) for n in pvalid_names},
            {n: P() for n in param_names},
        )
        fn = jax.jit(jax.shard_map(
            _named(wrapper, self.name), mesh=self.mesh, in_specs=pspec_in,
            out_specs=(P(AXIS, None), P(AXIS, None), P(AXIS)),
            check_vma=False))
        return fn, holder

    def identity(self) -> tuple:
        """What tells this join from another: the executor's cache key,
        and with a run's shapes a program's id in the inventory (its
        name holds shape only)."""
        sig = lambda cols: tuple(                         # noqa: E731
            (c.name, c.dtype.kind.value, c.dtype.nullable) for c in cols)
        return (sig(self.in_schema.columns), self.probe_key, self.kind,
                sig(self.payload_cols), self.mark_col, self.not_in,
                self.mesh.devices.size,
                tuple(p.fingerprint() for p in self.rest_programs),
                self.partial.fingerprint() if self.partial else "")

    def run(self, per_dev_blocks: list, build_arrays: dict, bcap: int,
            params: dict, dicts: dict, await_device=None,
            min_segment_rows: int = 128) -> list:
        """per_dev_blocks[d]: stage-A DeviceBlocks on device d. Returns one
        post-join (post-partial) DeviceBlock per device.

        `await_device(outputs, t_enqueued, program key id, fresh)`: the
        executor's accounting of the wait for the devices
        (`Executor._await_device`); without it nothing here waits.
        `min_segment_rows`: the floor of a counted segment
        (`Executor.mesh_min_segment_rows`)."""
        ndev = self.mesh.devices.size
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
        # rows per (source, target): the one transfer before the exchange
        # (it waits for the scan partials, as the row counts' did)
        counts = np.stack(jax.device_get(
            [_target_counts(fused[d][0][self.probe_key],
                            fused[d][1][self.probe_key], fused[d][2],
                            ndev=ndev) for d in range(ndev)]))
        seg = min(pcap, bucket_capacity(max(int(counts.max()), 1),
                                        minimum=min_segment_rows))
        rcap = bucket_capacity(max(int(counts.sum(axis=0).max()), 1),
                               minimum=min_segment_rows)

        bkeys = jax.device_put(build_arrays["keys"], sh2)
        bns = jax.device_put(build_arrays["ns"], sh1)
        bpay = {n: jax.device_put(a, sh2)
                for n, a in build_arrays["payload"].items()}
        bpv = {n: jax.device_put(a, sh2)
               for n, a in build_arrays["pvalid"].items()}

        payload_names = tuple(sorted(build_arrays["payload"]))
        pvalid_names = tuple(sorted(build_arrays["pvalid"]))
        # groupby_tuning: _build traces rest_programs/partial (GroupBy
        # lowerings read the tile-rows knob) — same identity rule as
        # every other compiled-program cache key
        key = (pcap, seg, rcap, bcap, payload_names, pvalid_names,
               tuple(sorted(params)), groupby_tuning())
        dev_params = {k: jnp.asarray(v) for k, v in params.items()}
        args = (arrays, valids, lengths, bkeys, bns, bpay, bpv, dev_params)
        entry = self._fns.get(key)
        fresh = entry is None
        if fresh:
            fn, holder = self._build(pcap, seg, rcap, bcap, payload_names,
                                     pvalid_names, tuple(sorted(params)))
            # captured through the program inventory: one trace, one
            # compile, `prog/registered`, its cost analysis. The out
            # schema is a product of the trace: never from the store
            fn = progstats.capture(
                "mesh-sj", (self.name, self.identity(), key), fn, args,
                consult_store=False)
            entry = self._fns[key] = (fn, holder)
        else:
            progstats.record_hit(getattr(entry[0], "key_id", None))
        fn, holder = entry
        out_d, out_v, lens = fn(*args)
        t_enqueued = time.perf_counter()
        record_exchange_rows("shuffle-join", lengths, counts.sum(axis=1))
        # every column travels with its validity plane; the diagonal of
        # `counts` stays on its device
        record_exchange_bytes(
            "shuffle-join", ndev, seg,
            sum(arrays[n].dtype.itemsize + 1 for n in names),
            int(counts.sum() - np.trace(counts)))
        if await_device is not None:
            await_device((out_d, out_v, lens), t_enqueued,
                         getattr(fn, "key_id", None), fresh)
        out_cols = [Column(n, DType(Kind(k), nullable))
                    for (n, k, nullable) in holder["sig"]]
        schema = Schema(out_cols)
        out_cap = next(iter(out_d.values())).shape[1] if out_d else 0
        blocks = []
        for d in range(ndev):
            arrays_d = {c.name: out_d[c.name].addressable_shards[d].data[0]
                        for c in out_cols}
            valids_d = {c.name: out_v[c.name].addressable_shards[d].data[0]
                        for c in out_cols}
            len_d = lens.addressable_shards[d].data[0]
            blocks.append(DeviceBlock(
                schema, arrays_d, valids_d, len_d, out_cap,
                {n: dc for n, dc in dicts.items() if schema.has(n)}))
        return blocks
