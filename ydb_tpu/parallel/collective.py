"""Shared device-collective building blocks for every ICI exchange.

ONE implementation of the bucketize → segment → `lax.all_to_all` →
compact redistribution (and its broadcast sibling, all-gather +
compact), consumed by three call sites:

  * `parallel/shuffle.py`      — distributed two-phase aggregation;
  * `parallel/shuffle_join.py` — probe-row exchange of the shuffle join;
  * `dq/ici.py`                — the DQ channel ICI data plane.

The formulation follows the portable-collective shuffle of arxiv
2112.01075 (memory-efficient redistribution as fixed-capacity segments
over one all_to_all) — everything static-shape, row counts ride along,
overflow detected on device.

Also here: the EQuARX-style block quantizer (arxiv 2506.17615) for
collective payloads — per-block scale + int8 codes, so an
aggregation-tolerant float column crosses the interconnect at ~1/8 the
bytes. NaN is preserved through a reserved code (-128, outside the
symmetric [-127, 127] quant range).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ydb_tpu.utils.hashing import hash_combine, splitmix64

AXIS = "shards"

# EQuARX block granularity: one float32 scale per QUANT_BLOCK int8 codes
# (overhead 4/QUANT_BLOCK bytes/row on top of the 1-byte code)
QUANT_BLOCK = 128
_NAN_CODE = -128                     # outside the symmetric quant range


def record_exchange_bytes(kind: str, ndev: int, seg: int, row_bytes: int,
                          crossing_rows: int) -> None:
    """Book one exchange's bytes, from shapes and row counts the host
    already holds: `mesh/exchange_bytes/<kind>` is what the fixed-capacity
    segments put on the wire — `ndev`² segments of `seg` rows of
    `row_bytes` (summed column and validity widths), less the segment a
    device keeps for itself, (ndev-1)/ndev — and
    `mesh/exchange_live_bytes/<kind>` the part of it that was
    `crossing_rows` live rows bound for another device. The difference
    is padding."""
    from ydb_tpu.utils.metrics import GLOBAL
    # lint: allow-counters(mesh/exchange_bytes/* registered)
    GLOBAL.inc(f"mesh/exchange_bytes/{kind}",
               ndev * seg * row_bytes * (ndev - 1))
    # lint: allow-counters(mesh/exchange_live_bytes/* registered)
    GLOBAL.inc(f"mesh/exchange_live_bytes/{kind}",
               crossing_rows * row_bytes)


def env_row_bytes(env, names) -> int:
    """Bytes one row of `env` takes in an exchange: every column's data
    width plus its validity plane's byte."""
    return sum(env[n][0].dtype.itemsize + 1 for n in names)


def bucket_of(env, key_names, ndev):
    """Hash-partition bucket id per row (device-side, same hash family
    as host shard routing — `ydb_tpu/utils/hashing.py`)."""
    with jax.named_scope("exchange/bucket"):
        return _bucket_of(env, key_names, ndev)


def _bucket_of(env, key_names, ndev):
    h = None
    for k in key_names:
        d, v = env[k]
        # value-truncating int64 coercion for all key dtypes (float keys
        # hash by truncated value — bitcast encodings are unavailable
        # under TPU x64 emulation)
        x = splitmix64(jnp, d.astype(jnp.int64))
        if v is not None:
            x = jnp.where(v, x, jnp.uint64(0))
        h = x if h is None else hash_combine(jnp, h, x)
    if h is None:
        return None
    return (h % jnp.uint64(ndev)).astype(jnp.int32)


def bucket_segments(env, bucket, length, cap, seg, ndev, names):
    """Build the per-target send segments of one device's rows.

    `env[name] = (data[cap], valid[cap]|None)`; `bucket[cap]` is the
    target device per row. Returns `(stacked_d, stacked_v, counts,
    overflow)` — per-column `[ndev, seg]` segment stacks, per-target row
    counts `[ndev]` (clamped to `seg`), and the overflow flag (any
    target bucket held more than `seg` rows — caller reruns with
    full-capacity segments, which cannot overflow)."""
    with jax.named_scope("exchange/bucket"):
        return _bucket_segments(env, bucket, length, cap, seg, ndev, names)


def _bucket_segments(env, bucket, length, cap, seg, ndev, names):
    from ydb_tpu.ops.xla_exec import compress
    iota = jnp.arange(cap, dtype=jnp.int32)
    active = iota < length
    seg_d = {n: [] for n in names}
    seg_v = {n: [] for n in names}
    counts = []
    overflow = jnp.bool_(False)
    for d_t in range(ndev):
        mask = active & (bucket == d_t)
        env_c, cnt = compress(env, length, mask, cap)
        overflow = overflow | (cnt > seg)
        counts.append(jnp.minimum(cnt, seg))
        for n in names:
            seg_d[n].append(env_c[n][0][:seg])
            v = env_c[n][1]
            seg_v[n].append(v[:seg] if v is not None
                            else jnp.ones((seg,), jnp.bool_))
    stacked_d = {n: jnp.stack(seg_d[n]) for n in names}        # (D, S)
    stacked_v = {n: jnp.stack(seg_v[n]) for n in names}
    return stacked_d, stacked_v, jnp.stack(counts), overflow


def exchange_segments(stacked_d, stacked_v, cnts, names, axis=AXIS):
    """The ICI hop: segment d of device s → device d segment s, for
    every column's data + valid stacks plus the row counts."""
    with jax.named_scope("exchange/all_to_all"):
        recv_d = {n: jax.lax.all_to_all(stacked_d[n], axis, 0, 0,
                                        tiled=False) for n in names}
        recv_v = {n: jax.lax.all_to_all(stacked_v[n], axis, 0, 0,
                                        tiled=False) for n in names}
        recv_c = jax.lax.all_to_all(cnts[:, None], axis, 0, 0,
                                    tiled=False)[:, 0]          # (D,)
    return recv_d, recv_v, recv_c


def compact_segments(recv_d, recv_v, recv_c, seg, ndev, names,
                     out_cap=None):
    """Flatten the received `[ndev, seg]` segment stacks and compact the
    live rows to the front. Returns `(env, total)` over `[ndev * seg]`
    buffers — or over `[out_cap]` where the caller KNOWS no device
    receives more rows than that (it counted them): each kept slot's
    source row is found once and every column gathered at `out_cap`
    (`xla_exec.compact_env`), so nothing downstream works on ndev
    segments' worth of padding."""
    from ydb_tpu.ops.xla_exec import compact_env, compress
    flat = ndev * seg
    with jax.named_scope("exchange/compact"):
        jrow = jnp.arange(seg, dtype=jnp.int32)
        seg_mask = (jrow[None, :] < recv_c[:, None]).reshape(-1)
        env = {n: (recv_d[n].reshape(-1), recv_v[n].reshape(-1))
               for n in names}
        if out_cap is None or out_cap >= flat:
            return compress(env, jnp.int32(flat), seg_mask, flat)
        env, total, _sel, _live, _ovf = compact_env(
            env, jnp.int32(flat), seg_mask, flat, out_cap)
        return env, total


def gather_all(stacked_d, stacked_v, cnts, seg, ndev, names, axis=AXIS):
    """Broadcast sibling of the shuffle: every device receives EVERY
    device's `[seg]` buffer (all-gather over ICI) and compacts the live
    rows. Inputs are per-device `[seg]` buffers (not per-target stacks).
    Returns `(env, total)` over `[ndev * seg]`."""
    with jax.named_scope("exchange/all_gather"):
        recv_d = {n: jax.lax.all_gather(stacked_d[n], axis) for n in names}
        recv_v = {n: jax.lax.all_gather(stacked_v[n], axis) for n in names}
        recv_c = jax.lax.all_gather(cnts, axis)                 # (D,)
    return compact_segments(recv_d, recv_v, recv_c, seg, ndev, names)


# -- padding-waste accounting ----------------------------------------------
#
# Every exchange built from these blocks ships FIXED-capacity segments
# (the arxiv 2112.01075 static-shape stance), so the wire carries
# padded_rows = ndev² · seg rows regardless of how many are live. The
# account below is the shared host-side arithmetic the three call sites
# (dq/ici.py, parallel/shuffle.py, parallel/shuffle_join.py) report into
# the resource ledger — the measured form of the "~3.5× the live bytes"
# MULTICHIP_r06 waste ROADMAP item 1 exists to delete.


def segment_pad_account(kind: str, ndev: int, seg: int, live_rows: int,
                        bytes_per_row: float) -> dict:
    """Ledger + return the live-vs-padded account of one fixed-capacity
    segment exchange: `ndev²` segments of `seg` rows each on the wire,
    `live_rows` of them real."""
    from ydb_tpu.utils import memledger
    padded_rows = ndev * ndev * seg
    live_bytes = int(live_rows * bytes_per_row)
    padded_bytes = int(padded_rows * bytes_per_row)
    memledger.record_pad(kind, live_rows, padded_rows, live_bytes,
                         padded_bytes)
    return {"live_rows": live_rows, "padded_rows": padded_rows,
            "live_bytes": live_bytes, "padded_bytes": padded_bytes,
            "efficiency": round(live_bytes / padded_bytes, 3)
            if padded_bytes else None}


# -- EQuARX block quantization (collective payload codec) ------------------


def quantize_blocked(x, block=QUANT_BLOCK):
    """Per-block symmetric int8 quantization of a float array whose last
    axis is a multiple of `block`. Returns `(codes int8, scales
    float32)` with `scales.shape = x.shape[:-1] + (last // block,)`.
    NaN encodes as the reserved code -128 and survives the round trip;
    a block's scale comes from its NaN-masked max-abs."""
    shape = x.shape
    xb = x.reshape(shape[:-1] + (shape[-1] // block, block))
    finite = ~jnp.isnan(xb)
    mag = jnp.max(jnp.where(finite, jnp.abs(xb), 0.0), axis=-1)
    scale = jnp.where(mag > 0, mag / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(xb / scale[..., None]), -127, 127)
    q = jnp.where(finite, q, jnp.full_like(xb, _NAN_CODE))
    return q.astype(jnp.int8).reshape(shape), scale


def dequantize_blocked(codes, scales, dtype, block=QUANT_BLOCK):
    """Inverse of `quantize_blocked`: int8 codes + per-block scales →
    float array of `dtype` (reserved code -128 → NaN)."""
    shape = codes.shape
    qb = codes.reshape(shape[:-1] + (shape[-1] // block, block))
    x = qb.astype(dtype) * scales[..., None].astype(dtype)
    x = jnp.where(qb == _NAN_CODE, jnp.nan, x)
    return x.reshape(shape)
