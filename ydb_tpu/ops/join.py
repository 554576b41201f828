"""Broadcast hash-join — TPU-native MapJoin + duplicate-key expansion.

The reference's broadcast join (`mkql_map_join.cpp` MapJoinCore) builds a
host hash table and probes row-by-row; GraceJoin (`mkql_grace_join.cpp`)
handles duplicate keys by bucket partitioning. The TPU-native design
replaces both probes with fully vectorized binary search over a *sorted*
build side:

  * build (host, once per build table): sort build keys, keep the
    permutation — O(n log n) on small dimension tables;
  * unique-key probe (device, per block): ``jnp.searchsorted`` (vectorized
    binary search, log2(n) gathers) + one equality check + payload gathers;
  * duplicate-key probe (``probe_expand``): left/right searchsorted give
    each probe row its matching build range [lo, hi); an exclusive
    prefix-sum over the counts lays out the expanded output; one
    host sync picks the output capacity bucket; a second program maps each
    output slot back to (probe row, build row) with two searchsorted-style
    gathers. This is the TPU analog of GraceJoin's duplicate handling —
    expansion instead of per-bucket nested loops.

Join kinds: inner, left, left_semi, left_anti (the kinds KQP plans emit for
broadcast joins), plus mark (match-flag attach, unique builds only).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ydb_tpu.core.block import ColumnData, HostBlock
from ydb_tpu.core.dtypes import DType, Kind
from ydb_tpu.core.schema import Column, Schema
from ydb_tpu.ops.device import DeviceBlock, bucket_capacity


def _host_key(block: HostBlock, name: str) -> tuple[np.ndarray, Optional[np.ndarray]]:
    """Key in its search domain: float keys stay float64, the rest int64.

    (No IEEE bitcast encodings: the TPU x64 emulation pass cannot rewrite
    f64<->s64 bitcasts, and searchsorted compares floats natively.)"""
    cd = block.columns[name]
    d = cd.data
    if np.issubdtype(d.dtype, np.floating):
        return d.astype(np.float64), cd.valid
    return d.astype(np.int64), cd.valid


_LUT_SPAN_BUDGET = 1 << 26         # max direct-address entries (256MB int32)
_FD_BUDGET = 1 << 28               # max host bytes retained for FD checks
# join kinds whose probe asks only whether a key is in the build: the
# LUT's one job is membership, so no payload density caps its span
EXISTENCE_KINDS = ("left_semi", "left_anti", "mark")


@dataclass
class BuildTable:
    """Sorted build side, resident on device.

    When the key is integral with a bounded span, a direct-address lookup
    table maps (key - lut_base) → sorted build row (-1 = absent), so a probe
    is ONE fused gather instead of a binary search (`jnp.searchsorted`
    lowers to a serializing scan loop on this platform — see PERF.md).
    With duplicate keys the LUT holds the FIRST sorted row of the key
    run (existence checks — semi/anti/mark — stay LUT-probeable)."""
    keys_sorted: object            # jnp int64 (padded with INT64_MAX)
    n: int                         # real build rows
    payload: dict                  # name -> jnp array (sorted by key)
    payload_valid: dict            # name -> jnp bool
    schema: Schema                 # payload schema
    dictionaries: dict
    unique: bool
    lut: object = None             # jnp int32 (span,) or None
    lut_base: int = 0              # key value of lut[0]
    # NOT IN: the build side contained a NULL key — x NOT IN S is then
    # never TRUE for any x (NULL or FALSE), so a not_in anti probe must
    # select nothing. Set by the executor's anti-null check.
    anti_has_null: bool = False
    # bounds lattice: the HOST build block (post null-key drop), retained
    # so the executor's carry rewrite can VERIFY functional dependencies
    # between payload columns by measured distinct counts
    # (`Executor._fd_determinant`). None above the retention budget.
    fd_block: object = None
    fd_memo: object = None         # {cols tuple: distinct count} cache


def build(block: HostBlock, key: str, payload_names: list[str],
          keep_fd: bool = False, existence: bool = False) -> BuildTable:
    """Sort the build side and, for an integral key whose span fits, fill
    its direct-address LUT. `existence`: the join is semi / anti / mark
    (`EXISTENCE_KINDS`), so the LUT is bounded by `_LUT_SPAN_BUDGET` alone
    and not by the 64x density cap that weighs it against a payload."""
    from ydb_tpu.utils.metrics import GLOBAL
    enc, valid = _host_key(block, key)
    if valid is not None:
        # null build keys never match; drop them
        keep = np.nonzero(valid)[0]
        block = block.take(keep)
        enc = enc[keep]
    order = np.argsort(enc, kind="stable")
    enc = enc[order]
    unique = bool(np.all(np.diff(enc) != 0)) if len(enc) > 1 else True
    cap = bucket_capacity(max(len(enc), 1), minimum=128)
    sentinel = np.inf if enc.dtype == np.float64 else np.iinfo(np.int64).max
    keys_pad = np.full(cap, sentinel, dtype=enc.dtype)
    keys_pad[:len(enc)] = enc

    lut = None
    lut_base = 0
    if enc.dtype != np.float64 and len(enc):
        lo, hi = int(enc[0]), int(enc[-1])
        span = hi - lo + 1
        # density cap 64x: a filtered 1.6M-row build over a 15M-key span
        # (TPC-H q3/q18 shapes) is a 60MB LUT — far cheaper than losing
        # whole-query fusion; the absolute budget still bounds HBM. An
        # existence build skips the cap: Q18's 6k-key `having` set over
        # 1.5M orderkeys probes with one gather, not a 13-step bsearch
        dense = max(1 << 12, min(_LUT_SPAN_BUDGET, 64 * len(enc)))
        if 0 < span <= (_LUT_SPAN_BUDGET if existence else dense):
            span_cap = bucket_capacity(span, minimum=1024)
            lut_np = np.full(span_cap, -1, np.int32)
            offs = (enc - lo).astype(np.int64)
            # first sorted row of each key run wins (reversed assignment:
            # numpy keeps the last write, which is the run's first row)
            lut_np[offs[::-1]] = np.arange(len(enc) - 1, -1, -1,
                                           dtype=np.int32)
            lut = jnp.asarray(lut_np)
            lut_base = lo
            if span > dense:
                GLOBAL.inc("join/existence_lut_builds")
    if lut is None:
        GLOBAL.inc("join/bsearch_builds")
    else:
        GLOBAL.inc("join/lut_builds")

    payload, payload_valid, dicts = {}, {}, {}
    for name in payload_names:
        cd = block.columns[name]
        d = cd.data[order]
        pad = np.zeros(cap - len(d), dtype=d.dtype)
        payload[name] = jnp.asarray(np.concatenate([d, pad]))
        if cd.valid is not None:
            v = np.concatenate([cd.valid[order], np.zeros(cap - len(d), np.bool_)])
            payload_valid[name] = jnp.asarray(v)
        if cd.dictionary is not None:
            dicts[name] = cd.dictionary
    # retain the host block for measured functional-dependency checks
    # (the carry rewrite's dataset verification) — host RAM is the cheap
    # side of this platform, but only the consumer's exact shape pins it:
    # the caller passes keep_fd when the consuming pipeline carries a
    # multi-key group-by (`Executor._prepare_builds`), and the FD lane
    # only ever reads unique-keyed builds; anything else (and any build
    # past the budget) just skips the FD lane and keeps every key in the
    # sort identity
    fd_block = block if keep_fd and unique and block.length \
        and sum(cd.data.nbytes
                for cd in block.columns.values()) <= _FD_BUDGET \
        else None
    return BuildTable(jnp.asarray(keys_pad), len(enc), payload, payload_valid,
                      block.schema.select(payload_names), dicts, unique,
                      lut, lut_base, fd_block=fd_block)


def place(table: BuildTable, device) -> BuildTable:
    """Replicate a build table onto a specific device (the broadcast leg of
    MapJoin on a mesh: every device probes its own copy)."""
    put = lambda x: jax.device_put(x, device)  # noqa: E731
    return BuildTable(
        put(table.keys_sorted), table.n,
        {k: put(v) for k, v in table.payload.items()},
        {k: put(v) for k, v in table.payload_valid.items()},
        table.schema, table.dictionaries, table.unique,
        None if table.lut is None else put(table.lut), table.lut_base,
        table.anti_has_null)


@dataclass
class PartitionedBuild:
    """GraceJoin-style hash-partitioned build side (`mkql_grace_join.cpp`):
    the build rows are split host-side by key hash into partitions small
    enough for the device budget; the probe side routes each row to its
    key's partition, so every partition joins independently. Partitions
    stay in host DRAM until probed — the HBM→host spill discipline of
    SURVEY §5.7 (the reference spills buckets to disk)."""
    tables: list                   # [BuildTable] per partition
    n_partitions: int
    key: str


def build_partitioned(block: HostBlock, key: str, payload_names: list[str],
                      budget_bytes: int) -> PartitionedBuild:
    """Partition a too-big build side by key hash (splitmix64, matching
    the device-side routing in the probe)."""
    from ydb_tpu.utils.hashing import splitmix64

    row_bytes = max(1, sum(block.columns[n].data.itemsize
                           for n in payload_names) + 8)
    total = row_bytes * max(block.length, 1)
    nparts = 1
    while total / nparts > budget_bytes:
        nparts *= 2
    enc, _valid = _host_key(block, key)
    h = splitmix64(np, enc.astype(np.int64))
    part = (h % np.uint64(nparts)).astype(np.int64)
    tables = []
    for p in range(nparts):
        idx = np.nonzero(part == p)[0]
        tables.append(build(block.take(idx), key, payload_names))
    return PartitionedBuild(tables, nparts, key)


def bsearch_traced(keys_sorted, enc):
    """Branchless lower_bound as log2(cap) UNROLLED gathers — the fused
    replacement for `jnp.searchsorted`, which lowers to a serializing
    scan loop on this platform (~4s for 6M probes, PERF.md). keys_sorted
    must be padded to a power-of-two capacity with a +inf/INT64_MAX
    sentinel (what `build()` produces)."""
    cap = keys_sorted.shape[0]
    assert cap & (cap - 1) == 0, "bsearch needs a pow2-padded build"
    pos = jnp.zeros(enc.shape, jnp.int32)
    step = cap >> 1
    while step:
        kv = keys_sorted[pos + (step - 1)]
        pos = jnp.where(kv < enc, pos + step, pos)
        step >>= 1
    return pos


def probe_lut_traced(env: dict, sel, bt_arrays: dict, meta: dict):
    """Build-probe inside a fused query trace (`ops/fused.py`): a
    direct-address LUT gather when the build has one, an unrolled
    binary search otherwise (a payload build's sparse span, a span past
    the LUT budget, float keys).

    env: {name: (data, valid|None)}; sel: bool selection mask — REQUIRED,
    and must already include the row-activity mask (`iota < length`; the
    fused pipeline threads it instead of compressing, so there is no
    separate length here); bt_arrays: traced build inputs {lut, lut_base,
    n, keys, payload.<name>, pvalid.<name>}; meta (static): probe_key,
    kind, payload_names (post-rename), src_names, mark_col, not_in,
    bsearch.

    Returns (env', sel'). Selection semantics match `_probe`: matched rows
    selected for inner/semi, unmatched for anti, all for left/mark."""
    if sel is None:
        raise ValueError("probe_lut_traced needs the row-activity mask")
    d, v = env[meta["probe_key"]]
    active = sel
    matchable = active if v is None else (active & v)
    kind = meta["kind"]

    if meta.get("bsearch"):
        keys = bt_arrays["keys"]
        enc = _probe_enc(d)
        pos = bsearch_traced(keys, enc)
        idx = jnp.clip(pos, 0, keys.shape[0] - 1)
        found = (keys[idx] == enc) & matchable \
            & (idx < bt_arrays["n"])
    else:
        if np.issubdtype(np.dtype(d.dtype), np.floating):
            # LUTs address integer keys; truncating a float probe would
            # mis-match (10.5 → 10) — floats must take the bsearch path
            raise TypeError("LUT probe requires an integral probe key")
        enc = d.astype(jnp.int64)
        lut = bt_arrays["lut"]
        span = lut.shape[0]
        off = enc - bt_arrays["lut_base"]
        inb = (off >= 0) & (off < span)
        idx = lut[jnp.clip(off, 0, span - 1).astype(jnp.int32)]
        found = inb & (idx >= 0) & matchable

    pcap = next(iter(bt_arrays["payload"].values())).shape[0] \
        if bt_arrays["payload"] else d.shape[0]
    safe = jnp.clip(idx, 0, pcap - 1)
    # late materialization: thread the (build row-id, match) pair instead
    # of gathering payload widths at probe capacity — the fused body
    # gathers from `payload[...]` at the first reference (post-compact)
    # or at the bound-sized tail (`ops/fused.py`). Selection semantics
    # are computed identically either way.
    late = bool(meta.get("late")) and kind in ("inner", "left")
    out_sel, gathered, gathered_valid = _select_and_gather(
        found, safe, active, v, bt_arrays["n"], kind, meta["not_in"],
        bt_arrays["payload"], bt_arrays["pvalid"],
        () if late else meta["src_names"])

    if kind == "left_anti" and meta["not_in"]:
        # a NULL in the build set makes NOT IN never-true for every row
        out_sel = out_sel & ~bt_arrays["has_null"]

    env2 = dict(env)
    if late:
        env2[meta["row_col"]] = (safe.astype(jnp.int32), None)
        env2[meta["found_col"]] = (found, None)
    else:
        for src, out in zip(meta["src_names"], meta["payload_names"]):
            if src in gathered:
                env2[out] = (gathered[src], gathered_valid[src])
    if kind == "mark":
        env2[meta["mark_col"] or "__mark"] = (found, None)
    return env2, out_sel


def _probe_enc(d):
    if d.dtype in (jnp.float64, jnp.float32):
        return d.astype(jnp.float64)
    return d.astype(jnp.int64)


@partial(jax.jit, static_argnames=("probe_key", "kind", "payload_names",
                                   "not_in"))
def _probe(probe_arrays, probe_valids, length, sel, n_build,
           keys_sorted, payload, payload_valid,
           probe_key, kind: str, payload_names: tuple, not_in: bool = False):
    cap = probe_arrays[probe_key].shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    row_mask = iota < length
    active = row_mask if sel is None else (row_mask & sel)

    d = probe_arrays[probe_key]
    enc = _probe_enc(d)
    v = probe_valids.get(probe_key)
    # NULL probe keys never match but must survive LEFT / LEFT ANTI joins
    matchable = active if v is None else (active & v)

    padded = keys_sorted.shape[0]
    pos = jnp.searchsorted(keys_sorted, enc).astype(jnp.int32)
    safe = jnp.clip(pos, 0, padded - 1)
    # `safe < n_build` guards against probe keys equal to the padding
    # sentinel (INT64_MAX / +inf) matching padding slots
    found = (keys_sorted[safe] == enc) & matchable & (safe < n_build)
    out_sel, gathered, gathered_valid = _select_and_gather(
        found, safe, active, v, n_build, kind, not_in, payload,
        payload_valid, payload_names)
    return out_sel, gathered, gathered_valid, found


def _select_and_gather(found, safe, active, v, n_build, kind: str,
                       not_in: bool, payload, payload_valid,
                       payload_names: tuple):
    """Shared post-match join logic (selection semantics + payload
    gathers) for the searchsorted (`_probe`) and LUT
    (`probe_lut_traced`) probes — the NOT IN three-valued rule and
    null-extension behavior live only here."""
    out_sel = found if kind in ("inner", "left_semi") else (
        (~found) & active if kind == "left_anti" else active)
    if kind == "left_anti" and not_in and v is not None:
        # x NOT IN S: NULL when x is NULL and S non-empty (row excluded),
        # TRUE when S is empty (row kept regardless of x)
        out_sel = out_sel & (v | (n_build == 0))

    gathered, gathered_valid = {}, {}
    if kind in ("inner", "left", "mark"):
        for name in payload_names:
            gathered[name] = payload[name][safe]
            pv = payload_valid.get(name)
            gathered_valid[name] = found if pv is None else (found & pv[safe])
    return out_sel, gathered, gathered_valid


def probe(dblock: DeviceBlock, table: BuildTable, probe_key: str,
          kind: str = "inner", sel=None,
          rename: Optional[dict] = None,
          mark_col: Optional[str] = None,
          not_in: bool = False) -> tuple[DeviceBlock, object]:
    """Probe a device block against a build table.

    Returns (new DeviceBlock with payload columns appended, new selection
    mask). The caller decides when to compress.

    kind "mark" keeps every active row, attaches payloads (null where
    unmatched) and a bool `mark_col` column holding the match flag — the
    building block for semi/anti joins that need post-join verification
    (composite hash keys, NOT IN null checks).
    """
    if not table.unique and kind in ("inner", "left", "mark"):
        raise ValueError(
            "broadcast MapJoin requires unique build keys for inner/left "
            "joins; duplicate keys need the partitioned GraceJoin path")
    rename = rename or {}
    names = tuple(table.schema.names)
    out_sel, gathered, gathered_valid, found = _probe(
        dblock.arrays, dblock.valids, dblock.length, sel, jnp.int32(table.n),
        table.keys_sorted, table.payload, table.payload_valid,
        probe_key, kind, names, not_in)
    if kind == "left_anti" and not_in and table.anti_has_null:
        # NULL in the build set: NOT IN is never TRUE (host-static — the
        # flag is known at build time, no traced input needed here)
        out_sel = jnp.zeros_like(out_sel)

    arrays = dict(dblock.arrays)
    valids = dict(dblock.valids)
    dicts = dict(dblock.dictionaries)
    cols = list(dblock.schema.columns)
    if kind in ("inner", "left", "mark"):
        for name in names:
            out_name = rename.get(name, name)
            arrays[out_name] = gathered[name]
            valids[out_name] = gathered_valid[name]
            dt = table.schema.dtype(name).with_nullable(True)
            cols = [c for c in cols if c.name != out_name] + [Column(out_name, dt)]
            if name in table.dictionaries:
                dicts[out_name] = table.dictionaries[name]
    if kind == "mark":
        name = mark_col or "__mark"
        arrays[name] = found
        cols = [c for c in cols if c.name != name] + [
            Column(name, DType(Kind.BOOL, nullable=False))]
    schema = Schema(cols)
    out = DeviceBlock(schema, arrays, valids, dblock.length, dblock.capacity, dicts)
    return out, out_sel


# -- duplicate-key (expanding) probe ---------------------------------------

@partial(jax.jit, static_argnames=("probe_key", "left"))
def _expand_counts(probe_arrays, probe_valids, length, n_build, keys_sorted,
                   probe_key, left: bool):
    cap = probe_arrays[probe_key].shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    active = iota < length
    enc = _probe_enc(probe_arrays[probe_key])
    v = probe_valids.get(probe_key)
    matchable = active if v is None else (active & v)

    lo = jnp.searchsorted(keys_sorted, enc, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(keys_sorted, enc, side="right").astype(jnp.int32)
    # sentinel padding (+inf / INT64_MAX) must not count as matches
    lo = jnp.minimum(lo, n_build)
    hi = jnp.minimum(hi, n_build)
    mcounts = jnp.where(matchable, hi - lo, 0)
    counts = jnp.where(active, jnp.maximum(mcounts, 1), 0) if left \
        else mcounts
    offsets = jnp.cumsum(counts) - counts          # exclusive prefix sum
    total = jnp.sum(counts)
    return lo, mcounts, counts, offsets, total


@partial(jax.jit, static_argnames=("kind", "payload_names", "out_cap"))
def _expand_gather(probe_arrays, probe_valids, lo, mcounts, offsets, total,
                   payload, payload_valid, kind: str, payload_names: tuple,
                   out_cap: int):
    cap = lo.shape[0]
    j = jnp.arange(out_cap, dtype=jnp.int32)
    row = jnp.searchsorted(offsets, j, side="right").astype(jnp.int32) - 1
    row = jnp.clip(row, 0, cap - 1)
    k = j - offsets[row]
    padded = next(iter(payload.values())).shape[0] if payload else cap
    bidx = jnp.clip(lo[row] + k, 0, padded - 1)
    live = j < total
    found = (mcounts[row] > 0) & live

    out_arrays = {n: a[row] for n, a in probe_arrays.items()}
    out_valids = {n: v[row] for n, v in probe_valids.items()}
    for n in payload_names:
        out_arrays[n] = payload[n][bidx]
        pv = payload_valid.get(n)
        out_valids[n] = found if pv is None else (found & pv[bidx])
    return out_arrays, out_valids


def probe_expand(dblock: DeviceBlock, table: BuildTable, probe_key: str,
                 kind: str = "inner",
                 rename: Optional[dict] = None) -> DeviceBlock:
    """Join a device block against a build table with duplicate keys.

    Returns a NEW compacted DeviceBlock whose capacity is the bucket for
    the expanded row count (inner: one output row per probe×build match;
    left: additionally one null-extended row per unmatched probe row).
    One device→host sync decides the capacity bucket.
    """
    assert kind in ("inner", "left"), kind
    rename = rename or {}
    lo, mcounts, counts, offsets, total = _expand_counts(
        dblock.arrays, dblock.valids, dblock.length, jnp.int32(table.n),
        table.keys_sorted, probe_key, kind == "left")
    n_out = int(total)                     # sync point (capacity decision)
    out_cap = bucket_capacity(max(n_out, 1), minimum=128)
    names = tuple(table.schema.names)
    payload = {rename.get(n, n): table.payload[n] for n in names}
    payload_valid = {rename.get(n, n): v for n, v in
                     table.payload_valid.items()}
    out_names = tuple(rename.get(n, n) for n in names)
    out_arrays, out_valids = _expand_gather(
        dblock.arrays, dblock.valids, lo, mcounts, offsets, total,
        payload, payload_valid, kind, out_names, out_cap)

    dicts = dict(dblock.dictionaries)
    cols = [c for c in dblock.schema.columns if c.name not in out_names]
    for n in names:
        out_name = rename.get(n, n)
        dt = table.schema.dtype(n).with_nullable(True)
        cols.append(Column(out_name, dt))
        if n in table.dictionaries:
            dicts[out_name] = table.dictionaries[n]
    return DeviceBlock(Schema(cols), out_arrays, out_valids,
                       jnp.int32(n_out), out_cap, dicts)
