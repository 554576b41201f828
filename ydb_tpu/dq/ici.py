"""The DQ channel ICI data plane — device-resident redistribution.

A host-plane channel serializes every partition to an npz frame and
round-trips it through gRPC (`cluster/exchange.py ChannelWriter` →
ExchangePut), so shuffle bandwidth between chips on the SAME mesh is
gRPC-bound. When the lowering marks an edge `plane="ici"` (both
endpoints' tasks run on devices of one JAX mesh — `dq/lower.py
_assign_planes`), the runner executes the redistribution here instead:

  hash_shuffle   bucketize + `lax.all_to_all` + compact — the portable
                 collective shuffle of `parallel/shuffle.py` (arxiv
                 2112.01075), over the SAME per-row buckets the host
                 plane would compute (`cluster/exchange.key_buckets`),
                 so a key routes to the same consumer on either plane
                 and the two sides of a join agree even if their edges
                 lowered differently;
  broadcast      all-gather of every producer's rows to every consumer.

On top, optional EQuARX-style block quantization (arxiv 2506.17615):
columns the lowering PROVED aggregation-tolerant (`Channel.quant_cols`
— pure SUM/AVG inputs behind a final reduction) cross the wire as int8
codes + per-block float32 scales (~1/8 the bytes) when
`YDB_TPU_DQ_QUANT=1`; keys, group-bys and every other exact-context
column always ship verbatim. A quant request the runtime cannot honor
(non-float column) is REFUSED loudly — counted on `dq/quant_refused`,
shipped exact — never silently lossy.

Anything this plane cannot express (exotic dtypes, mixed object
columns, a mesh that went away) raises `IciPlaneError`; the runner
catches it and re-runs the edge on the host plane — correctness never
depends on the fast path.
"""

from __future__ import annotations

import os

import numpy as np
import pandas as pd

from ydb_tpu.parallel.collective import (QUANT_BLOCK, bucket_segments,
                                         compact_segments,
                                         dequantize_blocked,
                                         exchange_segments, gather_all,
                                         quantize_blocked)

AXIS = "shards"


class IciPlaneError(Exception):
    """This edge cannot (or could not) run device-resident; the runner
    falls back to the host plane."""


def quant_enabled() -> bool:  # lint: tuning-provider
    """`YDB_TPU_DQ_QUANT` lever: 0/unset = off (byte-equal frames)."""
    return os.environ.get("YDB_TPU_DQ_QUANT", "0").strip() == "1"


def planned_enabled() -> bool:  # lint: tuning-provider
    """`YDB_TPU_DQ_PLANNED` lever: 1/unset = planned redistribution
    (`exchange_blocks` — device blocks by reference, count-exchange
    segment sizing on the fine ladder); 0 = the legacy pandas exchange
    with 2x power-of-two segments and the device overflow probe."""
    return os.environ.get("YDB_TPU_DQ_PLANNED", "1").strip() != "0"


# -- mesh + compiled-exchange caches ---------------------------------------

_MESHES: dict = {}
_FNS: dict = {}


def _mesh(ndev: int):
    import jax
    from jax.sharding import Mesh
    m = _MESHES.get(ndev)
    if m is None:
        devs = jax.devices()
        if len(devs) < ndev:
            raise IciPlaneError(
                f"ICI plane needs {ndev} mesh devices, platform has "
                f"{len(devs)}")
        m = _MESHES[ndev] = Mesh(np.array(devs[:ndev]), (AXIS,))
    return m


# -- column codecs ---------------------------------------------------------
#
# Every landed column must be indistinguishable from the host plane's
# npz round trip: plain numeric dtypes pass through; object columns
# (how `to_pandas` renders NULL-bearing numerics and strings) ride as
# typed arrays + valid masks (+ a shared dictionary for strings) and
# decode back to object-with-None.

_NUM = "num"
_MASK_INT = "maskint"
_MASK_FLOAT = "maskfloat"
_DICT = "dict"


def _classify(series_per_dev: list, col: str, hint: str):
    """One codec per column, decided over ALL producers (the same
    column can be int64 on a NULL-free shard and object on another)."""
    dts = {str(s.dtype) for s in series_per_dev if len(s)}
    if not dts:
        dts = {hint or "float64"}
    objish = {"object", "str", "string"}
    if not (dts & objish):
        if len(dts) > 1:
            raise IciPlaneError(f"column {col!r}: producers disagree on "
                                f"dtype ({sorted(dts)})")
        np_dt = np.dtype(next(iter(dts)))
        if np_dt.kind not in "iufb":
            raise IciPlaneError(f"column {col!r}: dtype {np_dt} is not "
                                "ICI-encodable")
        return (_NUM, np_dt)
    vals = [v for s in series_per_dev for v in s.dropna().tolist()]
    if all(isinstance(v, (int, np.integer)) and not isinstance(v, bool)
           for v in vals):
        return (_MASK_INT, np.dtype(np.int64))
    if all(isinstance(v, (int, float, np.integer, np.floating))
           and not isinstance(v, bool) for v in vals):
        return (_MASK_FLOAT, np.dtype(np.float64))
    if all(isinstance(v, str) for v in vals):
        # shared dictionary across every producer: codes agree on all
        # devices, values ship once host-side (metadata, not row bytes)
        values = sorted(set(vals))
        return (_DICT, np.dtype(np.int32), values)
    raise IciPlaneError(f"column {col!r}: mixed object values are not "
                        "ICI-encodable")


def _encode(series: pd.Series, spec, cap: int):
    """→ (data[cap], valid[cap]) numpy arrays for one producer."""
    n = len(series)
    valid = np.ones(cap, np.bool_)
    valid[n:] = False
    if spec[0] == _NUM:
        data = np.zeros(cap, spec[1])
        data[:n] = series.to_numpy(dtype=spec[1], copy=False)
        return data, valid
    notna = series.notna().to_numpy() if n else np.zeros(0, np.bool_)
    valid[:n] = notna
    data = np.zeros(cap, spec[1])
    if spec[0] == _DICT:
        code_of = {v: i for i, v in enumerate(spec[2])}
        vals = series.to_numpy()
        data[:n] = [code_of[v] if m else 0
                    for v, m in zip(vals, notna)]
    elif n:
        if series.dtype != object:        # NULL-free numeric producer
            data[:n] = series.to_numpy(dtype=spec[1], copy=False)
        else:
            vals = series.to_numpy()
            data[:n] = [spec[1].type(v) if m else 0
                        for v, m in zip(vals, notna)]
    return data, valid


def _decode(spec, data: np.ndarray, valid: np.ndarray):
    """Per-consumer column: device output rows (already transferred —
    the caller batches every column through ONE jax.device_get) → the
    pandas column the host plane's npz round trip would have landed."""
    if spec[0] == _NUM:
        return data.astype(spec[1], copy=False)
    if spec[0] == _DICT:
        # lint: transfer-ok(string pool is host metadata, never a device value)
        pool = np.asarray(spec[2], dtype=object)
        out = np.array(
            pool[np.clip(data.astype(np.int64), 0,
                         max(len(pool) - 1, 0))]
            if len(pool) else np.zeros(len(data), object),
            dtype=object)
    else:
        out = data.astype(spec[1], copy=False).astype(object)
    out[~valid] = None
    return out


# -- the exchange ----------------------------------------------------------


def _wire_bytes_per_row(spec, quantized: bool) -> float:
    """Bytes one row of this column occupies on the interconnect (data
    + valid mask; quantized columns ride int8 codes + amortized
    per-block scale)."""
    if quantized:
        return 1 + 4.0 / QUANT_BLOCK + 1
    return spec[1].itemsize + 1


def _build_shuffle_fn(mesh, ndev, cap, seg, names, dtypes, quant_names):
    """Compile the shard-mapped bucketize → (quantize) → all_to_all →
    (dequantize) → compact program for one signature. `seg` is the
    per-target segment capacity: smaller than `cap` cuts wire bytes
    proportionally (uniform hashing puts ~rows/ndev in each target);
    the returned overflow flag tells the host to rerun with full
    segments when a target bucket didn't fit (the DQ channel spilling
    analog, same discipline as `DistributedAgg.run`)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def per_device(arrays, valids, bucket, length):
        env = {n: (arrays[n][0], valids[n][0]) for n in names}
        stacked_d, stacked_v, cnts, ovf = bucket_segments(
            env, bucket[0], length[0], cap, seg, ndev, names)
        scales = {}
        for n in quant_names:
            stacked_d[n], scales[n] = quantize_blocked(stacked_d[n])
        recv_d, recv_v, recv_c = exchange_segments(
            stacked_d, stacked_v, cnts, names, axis=AXIS)
        recv_s = {n: jax.lax.all_to_all(scales[n], AXIS, 0, 0,
                                        tiled=False)
                  for n in quant_names}
        for n in quant_names:
            recv_d[n] = dequantize_blocked(recv_d[n], recv_s[n],
                                           dtypes[n])
        env2, tot = compact_segments(recv_d, recv_v, recv_c, seg, ndev,
                                     names)
        out_d = {n: env2[n][0] for n in names}
        out_v = {n: (env2[n][1] if env2[n][1] is not None
                     else jnp.ones_like(out_d[n], dtype=jnp.bool_))
                 for n in names}
        return out_d, out_v, tot, ovf

    def wrapper(arrays, valids, bucket, length):
        out_d, out_v, tot, ovf = per_device(arrays, valids, bucket,
                                            length)
        return ({n: x[None] for n, x in out_d.items()},
                {n: x[None] for n, x in out_v.items()}, tot[None],
                ovf[None])

    pspec_in = ({n: P(AXIS, None) for n in names},
                {n: P(AXIS, None) for n in names},
                P(AXIS, None), P(AXIS))
    return jax.jit(jax.shard_map(
        wrapper, mesh=mesh, in_specs=pspec_in,
        out_specs=(P(AXIS, None), P(AXIS, None), P(AXIS), P(AXIS)),
        check_vma=False))


def _build_broadcast_fn(mesh, ndev, cap, names):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def wrapper(arrays, valids, length):
        d = {n: arrays[n][0] for n in names}
        v = {n: valids[n][0] for n in names}
        env2, tot = gather_all(d, v, length[0], cap, ndev, names,
                               axis=AXIS)
        out_d = {n: env2[n][0] for n in names}
        out_v = {n: (env2[n][1] if env2[n][1] is not None
                     else jnp.ones_like(out_d[n], dtype=jnp.bool_))
                 for n in names}
        return ({n: x[None] for n, x in out_d.items()},
                {n: x[None] for n, x in out_v.items()}, tot[None])

    pspec_in = ({n: P(AXIS, None) for n in names},
                {n: P(AXIS, None) for n in names},
                P(AXIS))
    return jax.jit(jax.shard_map(
        wrapper, mesh=mesh, in_specs=pspec_in,
        out_specs=(P(AXIS, None), P(AXIS, None), P(AXIS)),
        check_vma=False))


def exchange(ch, dfs: list, key_kind: str = None,
             dtypes_hint: dict = None, counters=None) -> tuple:
    """Execute one ICI-plane channel over its producers' stage outputs.

    `dfs[d]` is mesh device d's stage output (one per worker, worker
    order). Returns `(out_dfs, stats)`: the per-consumer landed frames
    and `{"ici_bytes", "ici_frames", "quant_bytes_saved", "quant_cols",
    "quant_refused"}`. Raises `IciPlaneError` when the edge cannot run
    device-resident (the caller falls back to the host plane)."""
    import jax

    from ydb_tpu.dq.graph import BROADCAST, HASH_SHUFFLE
    from ydb_tpu.ops.device import bucket_capacity
    from ydb_tpu.utils import memledger

    ndev = len(dfs)
    if ndev < 2:
        raise IciPlaneError("ICI plane needs at least 2 producers")
    mesh = _mesh(ndev)
    if ch.kind not in (HASH_SHUFFLE, BROADCAST):
        raise IciPlaneError(f"channel kind {ch.kind!r} has no ICI form")

    columns = None
    for df in dfs:
        if list(df.columns):
            columns = list(df.columns)
            break
    if columns is None:
        columns = list(ch.columns)
    if not columns:
        raise IciPlaneError(f"channel {ch.id}: no columns to exchange")

    if ch.kind == HASH_SHUFFLE:
        from ydb_tpu.cluster.exchange import key_buckets
        # host-plane parity: NULL join keys drop (inner semantics), and
        # the bucket per row is the SAME hash the host plane routes by
        dropped = []
        buckets = []
        for df in dfs:
            keep = df[ch.key].notna()
            df = df[keep] if not keep.all() else df
            dropped.append(df)
            try:
                buckets.append(
                    key_buckets(df[ch.key].to_numpy(), ndev, key_kind)
                    if len(df) else np.zeros(0, np.int64))
            except ValueError as e:
                raise IciPlaneError(f"channel {ch.id} key {ch.key!r}: "
                                    f"{e}") from e
        dfs = dropped

    hints = dtypes_hint or {}
    specs = {c: _classify([df[c] for df in dfs], c, hints.get(c))
             for c in columns}

    # quantization: only lowering-proven columns, only plain floats,
    # only with the lever on. A declared column the runtime cannot
    # quantize is refused LOUDLY and shipped exact.
    quant_names: list = []
    refused: list = []
    if quant_enabled():
        for c in ch.quant_cols:
            spec = specs.get(c)
            if spec is not None and spec[0] == _NUM \
                    and spec[1].kind == "f":
                quant_names.append(c)
            elif spec is not None:
                refused.append(c)
        if refused and counters is not None:
            counters.inc("dq/quant_refused", len(refused))

    cap = bucket_capacity(max(max((len(df) for df in dfs), default=0),
                              1), minimum=QUANT_BLOCK)
    arrays = {}
    valids = {}
    for c in columns:
        enc = [_encode(df[c] if c in df.columns
                       else pd.Series(np.zeros(0, specs[c][1])),
                       specs[c], cap) for df in dfs]
        arrays[c] = np.stack([d for (d, _v) in enc])
        valids[c] = np.stack([v for (_d, v) in enc])
    lengths = np.array([len(df) for df in dfs], np.int32)

    names = tuple(columns)
    dt_sig = tuple((c, specs[c][0], str(specs[c][1])) for c in names)
    if ch.kind == HASH_SHUFFLE:
        bucket = np.zeros((ndev, cap), np.int32)
        for d, b in enumerate(buckets):
            bucket[d, :len(b)] = b.astype(np.int32)
        # segment sizing: uniform hashing sends ~rows/ndev to each
        # target, so 2× that (power-of-two) usually fits and cuts wire
        # bytes vs full-capacity segments; a skewed edge overflows on
        # device and reruns ONCE with seg = cap, which cannot overflow
        # (a target receives at most one producer's full row count)
        max_rows = max((len(df) for df in dfs), default=0)
        seg = min(cap, bucket_capacity(
            max(1, (2 * max_rows + ndev - 1) // ndev),
            minimum=QUANT_BLOCK))
        # (Channel.out_bound is NOT consulted on THIS legacy path:
        # `cap` above is already sized from the producers' MEASURED
        # rows — this exchange routes materialized frames, so a static
        # bound can never be tighter. The planned path
        # (`exchange_blocks`) is the bound's consumer: it caps the
        # count-exchange segment sizing with it.)
        while True:
            sig = ("shuffle", ndev, cap, seg, dt_sig,
                   tuple(quant_names))
            # lint: allow-cache-key(the quant lever rides in quant_names above — flipping YDB_TPU_DQ_QUANT changes the tuple, never serves a stale program)
            fn = _FNS.get(sig)
            if fn is None:
                dtypes = {c: specs[c][1] for c in names}
                fn = _FNS[sig] = _build_shuffle_fn(
                    mesh, ndev, cap, seg, names, dtypes,
                    tuple(quant_names))
            out_d, out_v, lens, ovf = fn(arrays, valids, bucket,
                                         lengths)
            # the blessed batched escape for the overflow verdict (was
            # a per-device np.asarray sync — a baselined host-sync debt)
            if not jax.device_get(ovf).any():
                break
            assert seg < cap, "full-capacity segments cannot overflow"
            seg = cap
    else:
        seg = cap                      # broadcast gathers full buffers
        sig = ("broadcast", ndev, cap, dt_sig)
        # lint: allow-cache-key(broadcast edges never quantize — quant_cols apply only to hash-shuffle segments)
        fn = _FNS.get(sig)
        if fn is None:
            fn = _FNS[sig] = _build_broadcast_fn(mesh, ndev, cap, names)
        out_d, out_v, lens = fn(arrays, valids, lengths)

    # ONE batched device→host transfer for every (column, device)
    # segment — 2·cols·ndev separate blocking np.asarray round trips
    # before this was batched (the to_host discipline, ops/device.py)
    host_d, host_v, lens = jax.device_get((out_d, out_v, lens))
    memledger.record_transfer(
        "dq/ici.py::exchange",
        memledger.deep_nbytes((host_d, host_v)))
    out_dfs = []
    for d in range(ndev):
        n = int(lens[d])
        cols = {c: _decode(specs[c], host_d[c][d][:n], host_v[c][d][:n])
                for c in columns}
        out_dfs.append(pd.DataFrame(cols, columns=columns))

    # wire accounting: what the collective actually moved — every
    # (src, dst) pair carries one seg-row segment per column (payload +
    # valid mask; broadcast replicates each producer's full cap-row
    # buffer to every device), plus the per-segment row counts
    per_row = sum(_wire_bytes_per_row(specs[c], c in quant_names)
                  for c in columns)
    exact_row = sum(_wire_bytes_per_row(specs[c], False)
                    for c in columns)
    segs = ndev * ndev
    # padding-waste account: the live rows that actually crossed (the
    # per-consumer landed totals) vs the capacity-padded segment frames
    # the collective shipped — the MULTICHIP_r06 ~3.5× waste, measured
    # per channel instead of estimated
    live_rows = int(sum(int(lens[d]) for d in range(ndev)))
    padded_rows = segs * seg
    padded_wire = int(segs * seg * per_row + segs * 4)
    live_wire = int(live_rows * per_row)
    memledger.record_alloc("collective", memledger.deep_nbytes(
        (arrays, valids)))
    memledger.record_pad("ici_frames", live_rows, padded_rows,
                         live_wire, padded_wire)
    stats = {
        "ici_bytes": padded_wire,
        "ici_frames": segs,
        "quant_bytes_saved": int(segs * seg * (exact_row - per_row)),
        "quant_cols": list(quant_names),
        "quant_refused": list(refused),
        "pad_live_bytes": live_wire,
        "pad_padded_bytes": padded_wire,
        "pad_efficiency": round(live_wire / padded_wire, 3)
        if padded_wire else None,
    }
    return out_dfs, stats


# -- planned redistribution (device blocks by reference) -------------------


def _build_counts_fn(ndev: int, cap: int):
    """Compile the planned path's count exchange: per (producer, target)
    live-row counts from the bucket plane. The [ndev, ndev] int32 result
    is the ONE small sizing message the host reads before any row moves
    — dropped/NULL rows already carry bucket -1, so a plain equality
    reduction is the whole program."""
    import jax
    import jax.numpy as jnp

    def counts(bucket):
        return jnp.stack(
            [jnp.sum(bucket == d, axis=1) for d in range(ndev)],
            axis=1).astype(jnp.int32)

    return jax.jit(counts)


def _build_counts_batched_fn(ndev: int, nch: int, cap: int):
    """The stage-level twin of `_build_counts_fn`: ONE fused program
    over EVERY hash-shuffle edge's bucket plane (`[nch, ndev, cap]`,
    planes padded to the widest capacity with -1 — pad rows route
    nowhere), so a stage with several outgoing edges pays ONE host
    round trip for all its sizing messages instead of one per channel
    (ROADMAP 1c)."""
    import jax
    import jax.numpy as jnp

    def counts(buckets):                     # [nch, ndev, cap]
        return jnp.stack(
            [jnp.sum(buckets == d, axis=2) for d in range(ndev)],
            axis=2).astype(jnp.int32)        # [nch, ndev, ndev]

    return jax.jit(counts)


def _device_specs(ch, blocks, columns):
    """One (codec_tag, numpy dtype) per column, decided over every
    producer SCHEMA (no pandas, no sync) — the planned twin of
    `_classify`. Strings ride as int32 dictionary codes (`_DICT`),
    everything else as its schema dtype (`_NUM`); validity always rides
    as a mask plane next to the data."""
    specs = {}
    for c in columns:
        dts, is_str = set(), False
        for b in blocks:
            if b.schema.has(c):
                dt = b.schema.dtype(c)
                is_str = is_str or dt.is_string
                dts.add(np.dtype(dt.np).str)
        if not dts:
            raise IciPlaneError(f"channel {ch.id}: column {c!r} missing "
                                "from every producer")
        if len(dts) > 1:
            raise IciPlaneError(f"column {c!r}: producers disagree on "
                                f"dtype ({sorted(dts)})")
        np_dt = np.dtype(next(iter(dts)))
        if np_dt.kind not in "iufb":
            raise IciPlaneError(f"column {c!r}: dtype {np_dt} is not "
                                "ICI-encodable")
        specs[c] = (_DICT, np.dtype(np.int32)) if is_str \
            else (_NUM, np_dt)
    return specs


def _union_dictionaries(ch, columns, specs, devs):
    """Shared consumer dictionaries for string columns: one union
    `Dictionary` per column over every producer's values (host METADATA
    — never a device readback), plus per-producer code-remap LUTs
    (old code → union code) applied device-side via `jnp.take`."""
    from ydb_tpu.core.dictionary import Dictionary
    unions, luts = {}, {}
    for c in columns:
        if specs[c][0] != _DICT:
            continue
        u = Dictionary()
        per = []
        for (dev, n) in devs:
            d = dev.dictionaries.get(c)
            if d is None:
                if n > 0 and c in dev.arrays:
                    raise IciPlaneError(
                        f"channel {ch.id}: string column {c!r} has rows "
                        "but no dictionary on a producer")
                per.append(None)
                continue
            vals = d.values_array()
            per.append(u.encode_bulk(vals).astype(np.int32) if len(vals)
                       else np.zeros(0, np.int32))
        unions[c] = u
        luts[c] = per
    return unions, luts


def exchange_blocks(ch, blocks: list, key_kind: str = None,
                    counters=None) -> tuple:
    """Planned device-resident redistribution — the stage spine's data
    plane. Producers and consumers speak device blocks BY REFERENCE:
    `blocks[d]` is mesh device d's stage output (a `DeviceStageBlock`
    stays on the accelerator; a plain `HostBlock` from a non-fused
    stage is uploaded once), and the landed per-consumer partitions
    come back as `DeviceStageBlock`s — no pandas, no npz, no host sync
    on the row plane.

    Segment sizing is PLANNED instead of guessed: a compiled count
    exchange ships the per-(producer, target) live-row counts ([ndev,
    ndev] int32 — the one small sizing message), and the collective's
    segment size is the measured max bucketed UP onto the fine quarter-
    octave ladder (`progstore/buckets.bucket_segment`, overshoot
    <= 1.25x) so the compiled-program cache stays a handful of rungs —
    retiring the legacy 2x power-of-two padding tax. `Channel.out_bound`
    (the planner's bounds lattice) caps the sizing; a bound that
    undercuts the measured counts trips the overflow escape hatch — ONE
    rerun at full capacity, which cannot overflow. The device overflow
    flag is NEVER fetched: sizing is host-known before dispatch.

    Returns `(out_blocks, stats)`; raises `IciPlaneError` when the edge
    cannot run device-resident (the runner falls back to the host
    plane)."""
    st = _prepare_exchange(ch, blocks, key_kind, counters)
    counts_host, ce_bytes = None, 0
    if st["bucket"] is not None:
        counts_host = _exchange_counts(st)
        ce_bytes = st["ndev"] * st["ndev"] * 4
    return _finish_exchange(st, counts_host, ce_bytes, counters)


def exchange_blocks_batched(chans: list, blocks: list, key_kinds=None,
                            counters=None) -> list:
    """Stage-level batched count exchange (ROADMAP 1c): prepare EVERY
    outgoing ICI edge of the stage, ship ALL their sizing counts as ONE
    fused program + ONE `[nch, ndev, ndev]` device_get — one host round
    trip per STAGE instead of one per channel — then finish each
    collective with its own counts slice. Bucket planes pad to the
    widest channel's capacity with -1, and pad rows route nowhere, so
    each slice equals the channel's solo counts exactly. Broadcast
    edges need no counts and ride along untouched; a stage with at most
    one shuffle edge degenerates to the solo exchange. Any preparation
    failure raises `IciPlaneError` for the WHOLE stage (the runner's
    host-plane fallback re-runs every edge).

    Returns `[(out_blocks, stats)]` in channel order."""
    import jax
    import jax.numpy as jnp

    from ydb_tpu.utils import memledger

    kks = list(key_kinds) if key_kinds is not None \
        else [None] * len(chans)
    sts = [_prepare_exchange(ch, blocks, kk, counters)
           for ch, kk in zip(chans, kks)]
    shuf = [st for st in sts if st["bucket"] is not None]
    if len(shuf) > 1:
        ndev = shuf[0]["ndev"]
        capmax = max(st["cap"] for st in shuf)
        planes = [st["bucket"] if st["cap"] == capmax else jnp.pad(
            st["bucket"], ((0, 0), (0, capmax - st["cap"])),
            constant_values=-1) for st in shuf]
        csig = ("counts_batched", ndev, len(shuf), capmax)
        # lint: allow-cache-key(batched counts depend only on the geometry (ndev, nch, cap) — no tuning lever feeds them)
        cfn = _FNS.get(csig)
        if cfn is None:
            cfn = _FNS[csig] = _build_counts_batched_fn(
                ndev, len(shuf), capmax)
        all_counts = jax.device_get(cfn(jnp.stack(planes)))
        memledger.record_transfer(
            "dq/ici.py::count_exchange_batched",
            len(shuf) * ndev * ndev * 4, boundary=True)
        if counters is not None:
            counters.inc("dq/count_exchange_batched")
        for st, cm in zip(shuf, all_counts):
            st["_counts"] = cm           # already host numpy (device_get)
    elif shuf:
        shuf[0]["_counts"] = _exchange_counts(shuf[0])
    out = []
    for st in sts:
        ce = st["ndev"] * st["ndev"] * 4 \
            if st["bucket"] is not None else 0
        out.append(_finish_exchange(st, st.pop("_counts", None), ce,
                                    counters))
    return out


def _prepare_exchange(ch, blocks: list, key_kind: str = None,
                      counters=None) -> dict:
    """Upload/align every producer's buffers and compute the hash-
    shuffle bucket plane — everything `exchange_blocks` does BEFORE the
    count exchange. Split out so the stage-level batched count exchange
    (`exchange_blocks_batched`) prepares every edge once and the SAME
    code computes both the solo and the batched routing — the two can
    never drift."""
    import jax.numpy as jnp

    from ydb_tpu.dq.graph import BROADCAST, HASH_SHUFFLE
    from ydb_tpu.ops.device import DeviceStageBlock, to_device
    from ydb_tpu.progstore.buckets import bucket_segment
    from ydb_tpu.utils.hashing import splitmix64

    ndev = len(blocks)
    if ndev < 2:
        raise IciPlaneError("ICI plane needs at least 2 producers")
    mesh = _mesh(ndev)
    if ch.kind not in (HASH_SHUFFLE, BROADCAST):
        raise IciPlaneError(f"channel kind {ch.kind!r} has no ICI form")

    columns = None
    for b in blocks:
        if list(b.schema.names):
            columns = list(b.schema.names)
            break
    if columns is None:
        columns = list(ch.columns)
    if not columns:
        raise IciPlaneError(f"channel {ch.id}: no columns to exchange")
    specs = _device_specs(ch, blocks, columns)

    # quantization: same contract as the legacy path — only lowering-
    # proven columns, only plain (mask-free) floats, lever-gated;
    # refusals are loud, never silently lossy
    quant_names: list = []
    refused: list = []

    # producer buffer capacity on the fine ladder (not the legacy pow2)
    max_len = max(max((b.length for b in blocks), default=0), 1)
    cap = bucket_segment(max_len, minimum=1)

    devs = []                           # (DeviceBlock view, host length)
    for b in blocks:
        if isinstance(b, DeviceStageBlock) and not b.materialized:
            devs.append((b.device, b.length))
        else:
            devs.append((to_device(b, capacity=max(cap, b.length)),
                         b.length))

    def _masked(c):
        return any(c in dev.valids for (dev, _n) in devs)

    if quant_enabled():
        for c in ch.quant_cols:
            spec = specs.get(c)
            if spec is not None and spec[0] == _NUM \
                    and spec[1].kind == "f" and not _masked(c):
                quant_names.append(c)
            elif spec is not None:
                refused.append(c)
        if refused and counters is not None:
            counters.inc("dq/quant_refused", len(refused))
    if quant_names:
        cap = -(-cap // QUANT_BLOCK) * QUANT_BLOCK

    unions, luts = _union_dictionaries(ch, columns, specs, devs)

    def _fit(a, want, fill=None):
        m = int(a.shape[0])
        if m == want:
            return a
        if m > want:
            return a[:want]
        pad = jnp.zeros((want - m,), a.dtype) if fill is None \
            else jnp.full((want - m,), fill, a.dtype)
        return jnp.concatenate([a, pad])

    lengths = np.array([n for (_dev, n) in devs], np.int32)
    lengths_col = jnp.asarray(lengths)[:, None]
    idx_row = jnp.arange(cap, dtype=jnp.int32)[None, :]
    arrays, valids = {}, {}
    for c in columns:
        want_dt = specs[c][1]
        per_d, per_v = [], []
        for di, (dev, n) in enumerate(devs):
            if c not in dev.arrays:
                raise IciPlaneError(f"channel {ch.id}: column {c!r} "
                                    f"missing on producer {di}")
            a = dev.arrays[c]
            if specs[c][0] == _DICT:
                lut_np = luts[c][di]
                if lut_np is not None and len(lut_np):
                    lut = jnp.asarray(lut_np)
                    a = jnp.take(lut, jnp.clip(a.astype(jnp.int32), 0,
                                               len(lut_np) - 1))
            if a.dtype != want_dt:
                a = a.astype(want_dt)
            per_d.append(_fit(a, cap))
            v = dev.valids.get(c)
            per_v.append(jnp.ones((cap,), jnp.bool_) if v is None
                         else _fit(v, cap))
        arrays[c] = jnp.stack(per_d)
        valids[c] = jnp.stack(per_v)
    for c in quant_names:
        # zero the inactive tail: capture-time pad rows may hold garbage
        # whose magnitude would poison the per-block quant scales
        arrays[c] = jnp.where(idx_row < lengths_col, arrays[c], 0)

    names = tuple(columns)
    dt_sig = tuple((c, specs[c][0], str(specs[c][1])) for c in names)
    bucket = None
    if ch.kind == HASH_SHUFFLE:
        key = ch.key
        if not key or key not in columns:
            raise IciPlaneError(f"channel {ch.id}: shuffle key {key!r} "
                                "is not an exchanged column")
        kspec = specs[key]
        kind = key_kind or ("string" if kspec[0] == _DICT
                            else "float" if kspec[1].kind == "f"
                            else "int")
        if kind == "float":
            raise IciPlaneError(
                f"channel {ch.id} key {key!r}: float join keys are not "
                "hash-partitionable")
        # the bucket plane: the SAME per-row route the host plane's
        # `key_buckets` computes — splitmix64 for ints (x64 bit parity),
        # a host crc32 LUT over the union values for strings — with
        # NULL/pad rows at -1 (dropped: inner-shuffle semantics)
        if kind == "string":
            import zlib
            uvals = unions[key].values_array()
            blut_np = np.array(
                [int(np.uint64(zlib.crc32(str(v).encode())) %
                     np.uint64(ndev)) for v in uvals],
                np.int32) if len(uvals) else np.zeros(1, np.int32)
            blut = jnp.asarray(blut_np)
            bucket = jnp.take(blut, jnp.clip(
                arrays[key].astype(jnp.int32), 0, len(blut_np) - 1))
        else:
            h = splitmix64(jnp, arrays[key].astype(jnp.int64))
            bucket = (h % jnp.uint64(ndev)).astype(jnp.int32)
        active = (idx_row < lengths_col) & valids[key]
        bucket = jnp.where(active, bucket, jnp.int32(-1))

    return {
        "ch": ch, "blocks": blocks, "mesh": mesh, "ndev": ndev,
        "columns": columns, "specs": specs, "quant_names": quant_names,
        "refused": refused, "cap": cap, "lengths": lengths,
        "arrays": arrays, "valids": valids, "unions": unions,
        "names": names, "dt_sig": dt_sig, "bucket": bucket,
        "masked": {c: _masked(c) for c in columns},
    }


def _exchange_counts(st: dict):
    """The solo count exchange for ONE prepared hash-shuffle channel:
    the planned path's single host round trip — ndev^2 int32, counted
    as the blessed sizing message (the legacy row-plane device_get
    disappears entirely)."""
    import jax

    from ydb_tpu.utils import memledger

    ndev, cap = st["ndev"], st["cap"]
    csig = ("counts", ndev, cap)
    # lint: allow-cache-key(the counts program depends only on (ndev, cap) — no tuning lever feeds it)
    cfn = _FNS.get(csig)
    if cfn is None:
        cfn = _FNS[csig] = _build_counts_fn(ndev, cap)
    counts_host = jax.device_get(cfn(st["bucket"]))
    memledger.record_transfer("dq/ici.py::count_exchange",
                              ndev * ndev * 4, boundary=True)
    return counts_host


def _finish_exchange(st: dict, counts_host, ce_bytes: int,
                     counters=None) -> tuple:
    """Size, compile and run the collective from prepared state plus
    the already-exchanged sizing counts, then build the landed consumer
    blocks and the wire/padding account. `counts_host` is None exactly
    for broadcast edges (they gather full buffers — no sizing
    message)."""
    from ydb_tpu.core.schema import Column, Schema
    from ydb_tpu.ops.device import DeviceBlock, DeviceStageBlock
    from ydb_tpu.progstore.buckets import bucket_segment
    from ydb_tpu.utils import memledger

    ch, blocks, mesh = st["ch"], st["blocks"], st["mesh"]
    ndev, cap = st["ndev"], st["cap"]
    columns, specs, names = st["columns"], st["specs"], st["names"]
    dt_sig, quant_names = st["dt_sig"], st["quant_names"]
    arrays, valids = st["arrays"], st["valids"]
    lengths, unions = st["lengths"], st["unions"]
    if st["bucket"] is not None:
        max_pair = int(counts_host.max()) if counts_host.size else 0
        seg = bucket_segment(max(max_pair, 1), minimum=1)
        bound = getattr(ch, "out_bound", None)
        if bound:
            bseg = bucket_segment(int(bound), minimum=1)
            if bseg < seg:
                seg = bseg
        if max_pair > seg:
            # an unsound (or forged) bound undercut the measured counts:
            # the overflow escape hatch — ONE rerun at full capacity,
            # which cannot overflow (a target receives at most one
            # producer's full row count)
            if counters is not None:
                counters.inc("dq/planned_overflow_reruns")
            seg = cap
        if quant_names:
            seg = -(-seg // QUANT_BLOCK) * QUANT_BLOCK
        seg = min(seg, cap)

        sig = ("shuffle", ndev, cap, seg, dt_sig, tuple(quant_names))
        # lint: allow-cache-key(the quant lever rides in quant_names above — flipping YDB_TPU_DQ_QUANT changes the tuple, never serves a stale program)
        fn = _FNS.get(sig)
        if fn is None:
            dtypes = {c: specs[c][1] for c in names}
            fn = _FNS[sig] = _build_shuffle_fn(
                mesh, ndev, cap, seg, names, dtypes, tuple(quant_names))
        out_d, out_v, _lens, _ovf = fn(arrays, valids, st["bucket"],
                                       lengths)
        # _lens/_ovf are NEVER fetched: the landed totals and the
        # no-overflow verdict are host-known from the count exchange
        landed = [int(counts_host[:, d].sum()) for d in range(ndev)]
        out_cap = ndev * seg
    else:
        seg = cap                       # broadcast gathers full buffers
        sig = ("broadcast", ndev, cap, dt_sig)
        # lint: allow-cache-key(broadcast edges never quantize — quant_cols apply only to hash-shuffle segments)
        fn = _FNS.get(sig)
        if fn is None:
            fn = _FNS[sig] = _build_broadcast_fn(mesh, ndev, cap, names)
        out_d, out_v, _lens = fn(arrays, valids, lengths)
        landed = [int(lengths.sum())] * ndev
        out_cap = ndev * cap

    # landed per-consumer blocks: array REFERENCES into the collective's
    # output, wrapped with host-known lengths — the consumer stage's
    # fused scan stacks them without any readback
    out_cols, out_dicts = [], {}
    for c in columns:
        sdt = next(b.schema.dtype(c) for b in blocks if b.schema.has(c))
        out_cols.append(Column(c, sdt))
        if c in unions:
            out_dicts[c] = unions[c]
    out_schema = Schema(out_cols)
    masked = st["masked"]
    out_blocks = []
    for d in range(ndev):
        dev = DeviceBlock(
            out_schema, {c: out_d[c][d] for c in columns},
            {c: out_v[c][d] for c in columns if masked[c]},
            landed[d], out_cap, dict(out_dicts))
        out_blocks.append(DeviceStageBlock(dev, landed[d]))

    # wire + padding account: planned segments on the ladder vs the live
    # rows that actually crossed, plus the sizing messages (per-segment
    # counts and the count exchange itself)
    per_row = sum(_wire_bytes_per_row(specs[c], c in quant_names)
                  for c in columns)
    exact_row = sum(_wire_bytes_per_row(specs[c], False)
                    for c in columns)
    segs = ndev * ndev
    live_rows = int(sum(landed))
    padded_rows = segs * seg
    padded_wire = int(segs * seg * per_row + segs * 4 + ce_bytes)
    live_wire = int(live_rows * per_row)
    memledger.record_alloc("collective", memledger.deep_nbytes(
        (arrays, valids)))
    memledger.record_pad("ici_frames", live_rows, padded_rows,
                         live_wire, padded_wire)
    stats = {
        "ici_bytes": padded_wire,
        "ici_frames": segs,
        "quant_bytes_saved": int(segs * seg * (exact_row - per_row)),
        "quant_cols": list(quant_names),
        "quant_refused": list(st["refused"]),
        "pad_live_bytes": live_wire,
        "pad_padded_bytes": padded_wire,
        "pad_efficiency": round(live_wire / padded_wire, 3)
        if padded_wire else None,
        "planned": True,
        "seg": int(seg),
        "count_exchange_bytes": ce_bytes,
    }
    return out_blocks, stats
