"""Device-side window functions.

The r4 engine evaluated every window spec in a pandas host lane
(`query/window.py`) — honest but single-core, and the host-lane guard
simply REFUSED large frames. This module evaluates the common specs as
ONE scatter-free jitted program over the whole frame, the TPU-native
shape of the reference's block window kernels (`mkql_block_top.cpp`,
peephole window rewrites `yql_opt_peephole_physical.cpp:5810`):

  * one `lax.sort` per distinct (PARTITION BY, ORDER BY) clause —
    partition keys hash-combined into ONE u64 operand (equality only),
    order keys encoded into order-preserving operands, the row id riding
    along as the permutation (never value columns: sort operand count is
    the compile-time cliff, PERF.md);
  * partition/order boundaries by adjacent comparison; segment starts /
    ends via cummax over flipped/unflipped iotas;
  * row_number / rank / dense_rank from boundary cumsums;
  * running and whole-partition SUM/COUNT/AVG from prefix sums against
    the segment-start prefix (NULLs excluded via a parallel validity
    cumsum);
  * running MIN/MAX as a segmented prefix scan (`lax.associative_scan`
    with a reset-at-boundary combiner);
  * ROWS BETWEEN frames for sum/count/avg from the same prefix sums at
    clipped offsets;
  * LEAD/LAG as clipped in-segment gathers;
  * results return to source row order through one inverse permutation
    (argsort of the sort permutation — a 2-operand sort) and ONE
    device→host transfer for all outputs.

Unsupported shapes (float partition keys, bounded min/max frames,
exotic funcs) decline → the caller keeps the pandas lane.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ydb_tpu.ops.xla_exec import cumsum, sort_total
from ydb_tpu.utils.hashing import hash_combine, splitmix64

DEVICE_FUNCS = {"row_number", "rank", "dense_rank", "sum", "min", "max",
                "count", "avg", "lead", "lag"}

_I64MAX = np.iinfo(np.int64).max


# ---------------------------------------------------------------------------
# host-side spec compilation: which specs can run on device, key encodings
# ---------------------------------------------------------------------------


def _sort_group_key(spec) -> tuple:
    return (tuple(spec["part"]), tuple(spec["order"]), tuple(spec["asc"]))


def spec_supported(spec, block) -> bool:
    fn = spec["func"]
    if fn not in DEVICE_FUNCS:
        return False
    frame = spec.get("frame")
    if frame is not None:
        if fn in ("min", "max"):
            return False              # bounded sliding min/max: host lane
        if fn in ("row_number", "rank", "dense_rank", "lead", "lag"):
            return False              # frame is meaningless / unsupported
        _tag, lo, hi = frame
        for b in (lo, hi):
            if not isinstance(b, (int, tuple)):
                return False
    if fn in ("lead", "lag"):
        # arg 0 = value, optional arg 1 = offset literal (inner select
        # materializes it as a column; constant columns only). The
        # 3-arg DEFAULT form stays on the host lane.
        if not spec["args"] or len(spec["args"]) > 2:
            return False
    for name in spec["part"]:
        cd = block.columns[name]
        if np.issubdtype(cd.data.dtype, np.floating):
            return False              # no f64 bitcast on this platform
    return True


def _encode_part_host(block, names):
    """Partition keys → (arrays to hash, validity ints). Equality-only."""
    out = []
    for n in names:
        cd = block.columns[n]
        out.append((cd.data.astype(np.int64),
                    None if cd.valid is None
                    else cd.valid.astype(np.int64)))
    return out


def _final_key_ok(cd) -> bool:
    d = cd.data
    return (cd.dictionary is not None
            or np.issubdtype(d.dtype, np.floating)
            or np.issubdtype(d.dtype, np.integer)
            or d.dtype == np.bool_)


def _encode_final_key(cd, ascending):
    """Final ORDER BY key → order-preserving operand with the ENGINE's
    NULL placement (YQL null-smallest: first when ascending, last when
    descending — matching `apply_order_limit`'s defaults)."""
    d = cd.data
    if cd.dictionary is not None:
        ranks = cd.dictionary.sort_ranks()
        enc = ranks[np.clip(d, 0, None)].astype(np.int64)
        enc = np.where(d < 0, 0, enc)
        valid = (d >= 0) if cd.valid is None else (cd.valid & (d >= 0))
    else:
        valid = cd.valid
        if np.issubdtype(d.dtype, np.floating):
            enc = d.astype(np.float64)
            if not ascending:
                enc = -enc
            if valid is not None:
                enc = np.where(valid, enc,
                               -np.inf if ascending else np.inf)
            return np.where(np.isnan(enc),
                            -np.inf if ascending else np.inf, enc)
        elif np.issubdtype(d.dtype, np.integer) or d.dtype == np.bool_:
            enc = d.astype(np.int64)
        else:
            return None
    # INT64_MIN cannot negate (wraps to itself) and collides with the
    # ascending NULL sentinel — decline such rows to the host tail
    if len(enc) and int(enc.min()) == np.iinfo(np.int64).min:
        return None
    enc = enc if ascending else -enc
    if valid is not None:
        sent = np.iinfo(np.int64).min if ascending else _I64MAX
        enc = np.where(valid, enc, sent)
    return enc


def _encode_order_host(block, name, ascending):
    """One order key → an order-preserving f64/i64 array with NULLs
    mapped last (pandas na_position='last' parity)."""
    cd = block.columns[name]
    d = cd.data
    if cd.dictionary is not None:
        ranks = cd.dictionary.sort_ranks()
        d = ranks[np.clip(d, 0, None)].astype(np.int64)
        d = np.where(cd.data < 0, 0, d)
    if np.issubdtype(d.dtype, np.floating):
        enc = d.astype(np.float64)
        if not ascending:
            enc = -enc
        if cd.valid is not None:
            enc = np.where(cd.valid, enc, np.inf)
        enc = np.where(np.isnan(enc), np.inf, enc)
        return enc
    enc = d.astype(np.int64)
    if not ascending:
        enc = -enc
    if cd.valid is not None:
        enc = np.where(cd.valid, enc, _I64MAX)
    return enc


# ---------------------------------------------------------------------------
# traced helpers
# ---------------------------------------------------------------------------


def _seg_starts(boundary, iota):
    """Index of each row's segment start (boundary[0] must be True)."""
    return jax.lax.cummax(jnp.where(boundary, iota, 0))


def _seg_ends(boundary, iota, n):
    """Index of each row's segment END (inclusive). boundary marks
    segment STARTS; a start at i+1 means i is an end."""
    nxt = jnp.concatenate([boundary[1:], jnp.ones((1,), bool)])
    rev = jnp.flip(jnp.where(nxt, iota, n - 1))
    return jnp.flip(jax.lax.cummin(rev))


def _segmented_scan_minmax(v, boundary, is_min):
    """Running min/max with reset at segment boundaries."""
    def combine(a, b):
        ab, av = a
        bb, bv = b
        merged = jnp.where(bb, bv,
                           jnp.minimum(av, bv) if is_min
                           else jnp.maximum(av, bv))
        return (ab | bb, merged)
    _b, out = jax.lax.associative_scan(combine, (boundary, v))
    return out


def _prefix(v):
    """Exclusive prefix sums of shape (n+1,): P[i] = sum(v[:i])."""
    return jnp.concatenate([jnp.zeros((1,), v.dtype), cumsum(v)])


def _build_window_fn(struct):
    """Trace one jitted program computing every spec in `struct`:
    {"groups": [{"n_part_ops": int, "n_order": int,
                 "specs": [{"func","frame","has_arg","arg_float",
                            "offset","alias"}]}], "cap": int}"""

    @jax.jit
    def fn(inputs):
        L = inputs["length"]
        cap = inputs["iota"].shape[0]
        iota = inputs["iota"]
        active = iota < L
        outs = {}
        for gi, grp in enumerate(struct["groups"]):
            # --- one sort per clause group
            #
            # PARTITION BY keys are hash-combined into ONE u64 sort
            # operand — a deliberate correctness/compile-time tradeoff:
            # two DISTINCT partitions whose combined splitmix64 hashes
            # collide in the surviving 63 bits (the top bit is the
            # padding sentinel) would silently merge, corrupting every
            # windowed value in both. The per-pair probability is 2^-63
            # (~1e-19; even 1M partitions give ~5e7 pairs ≈ 5e-12 per
            # query), while the alternative — one sort operand per key
            # column — rides the lax.sort compile cliff (operand count
            # is the compile-time driver: 6M×8 operands ≈ 218 s,
            # PERF.md). A second independent hash operand would square
            # the collision odds at +1 operand; revisit if this lane
            # ever feeds billing-grade aggregation instead of analytics.
            phash = jnp.zeros(cap, jnp.uint64)
            for pi in range(grp["n_part_ops"]):
                phash = hash_combine(
                    jnp, phash,
                    splitmix64(jnp, inputs[f"g{gi}p{pi}"]))
            # padded rows sort to the back as their own partition
            phash = jnp.where(active, phash >> jnp.uint64(1),
                              jnp.uint64(np.uint64(2**64 - 1)))
            operands = [phash]
            for oi in range(grp["n_order"]):
                operands.append(inputs[f"g{gi}o{oi}"])
            sorted_ops = sort_total(operands, iota)
            perm = sorted_ops[-1]
            s_hash = sorted_ops[0]
            # --- boundaries
            first = jnp.zeros(cap, bool).at[0].set(True)  # static index
            b_part = jnp.concatenate(
                [jnp.ones((1,), bool), s_hash[1:] != s_hash[:-1]])
            b_order = b_part
            for oi in range(grp["n_order"]):
                so = sorted_ops[1 + oi]
                b_order = b_order | jnp.concatenate(
                    [jnp.ones((1,), bool), so[1:] != so[:-1]])
            del first
            seg_start = _seg_starts(b_part, iota)
            seg_end = _seg_ends(b_part, iota, cap)
            # perm is a permutation: no ties, stability buys nothing
            inv = jax.lax.sort((perm, iota), num_keys=1,
                               is_stable=False)[1]

            def unsort(x):
                return x[inv]

            # dense-rank prefix over order boundaries (shared)
            corder = jnp.cumsum(b_order.astype(jnp.int64))

            for si, spec in enumerate(grp["specs"]):
                fnname = spec["func"]
                if fnname == "row_number":
                    out = iota - seg_start + 1
                    outs[spec["alias"]] = (unsort(out), None)
                    continue
                if fnname == "rank":
                    grp_start = jax.lax.cummax(
                        jnp.where(b_order, iota, 0))
                    out = grp_start - seg_start + 1
                    outs[spec["alias"]] = (unsort(out), None)
                    continue
                if fnname == "dense_rank":
                    out = corder - corder[seg_start] + 1
                    outs[spec["alias"]] = (unsort(out), None)
                    continue
                if fnname in ("lead", "lag"):
                    v = inputs[f"g{gi}s{si}a"][perm]
                    valid_in = inputs.get(f"g{gi}s{si}av")
                    sv = valid_in[perm] if valid_in is not None else None
                    off = spec["offset"]
                    tgt = iota + off if fnname == "lead" else iota - off
                    inside = (tgt >= seg_start) & (tgt <= seg_end) \
                        & (tgt >= 0) & (tgt < cap)
                    tgt_c = jnp.clip(tgt, 0, cap - 1)
                    out = v[tgt_c]
                    ov = inside if sv is None else (inside & sv[tgt_c])
                    outs[spec["alias"]] = (unsort(out), unsort(ov))
                    continue
                # aggregates --------------------------------------------
                has_arg = spec["has_arg"]
                if has_arg:
                    v = inputs[f"g{gi}s{si}a"][perm]
                    valid_in = inputs.get(f"g{gi}s{si}av")
                    sv = valid_in[perm] if valid_in is not None \
                        else jnp.ones(cap, bool)
                else:                     # count(*)
                    v = jnp.ones(cap, jnp.int64)
                    sv = jnp.ones(cap, bool)
                sv = sv & (perm < L)
                filled = jnp.where(sv, v, jnp.zeros((), v.dtype))
                frame = spec["frame"]
                if fnname in ("min", "max"):
                    ident = jnp.array(
                        np.inf if fnname == "min" else -np.inf, v.dtype) \
                        if jnp.issubdtype(v.dtype, jnp.floating) else \
                        jnp.array(_I64MAX if fnname == "min"
                                  else -_I64MAX - 1, v.dtype)
                    vm = jnp.where(sv, v, ident)
                    if spec["running"]:
                        out = _segmented_scan_minmax(vm, b_part,
                                                     fnname == "min")
                        nn = jnp.cumsum(sv.astype(jnp.int64))
                        nnrun = nn - nn[seg_start] \
                            + sv[seg_start].astype(jnp.int64)
                        ov = nnrun > 0
                    else:
                        run = _segmented_scan_minmax(vm, b_part,
                                                     fnname == "min")
                        out = run[seg_end]
                        nn = jnp.cumsum(sv.astype(jnp.int64))
                        tot = nn[seg_end] - nn[seg_start] \
                            + sv[seg_start].astype(jnp.int64)
                        ov = tot > 0
                    outs[spec["alias"]] = (unsort(out), unsort(ov))
                    continue
                cs = _prefix(filled)
                cn = _prefix(sv.astype(jnp.int64))
                if frame is not None:
                    _tag, lo, hi = frame
                    lo_unb = not isinstance(lo, int)
                    hi_unb = not isinstance(hi, int)
                    start = seg_start if lo_unb \
                        else jnp.clip(iota + lo, seg_start, seg_end + 1)
                    end1 = seg_end + 1 if hi_unb \
                        else jnp.clip(iota + hi + 1, seg_start,
                                      seg_end + 1)
                    start = jnp.minimum(start, end1)
                elif spec["running"]:
                    start, end1 = seg_start, iota + 1
                else:
                    start, end1 = seg_start, seg_end + 1
                ssum = cs[end1] - cs[start]
                scnt = cn[end1] - cn[start]
                if fnname == "count":
                    outs[spec["alias"]] = (unsort(scnt), None)
                elif fnname == "sum":
                    outs[spec["alias"]] = (unsort(ssum),
                                           unsort(scnt > 0))
                else:                     # avg
                    a = ssum.astype(jnp.float64) / jnp.maximum(scnt, 1)
                    outs[spec["alias"]] = (unsort(a), unsort(scnt > 0))

        fin = struct.get("final")
        if fin is None:
            return outs
        # final ORDER BY + LIMIT device-side: one more sort (keys +
        # row id), then every output leaves sliced to K rows
        ops_l = [jnp.where(active, jnp.int64(0), jnp.int64(1))]
        for key_spec in fin["keys"]:
            src, name, asc = key_spec[0], key_spec[1], key_spec[2]
            if src == "col":
                ops_l.append(inputs[name])
                continue
            if src == "winstr":
                # string window output: sort by lexicographic rank LUT;
                # NULL (code < 0 or invalid) takes the engine's
                # null-smallest placement
                v, vv = outs[name]
                ranks = inputs[key_spec[3]]
                code = v.astype(jnp.int64)
                enc = ranks[jnp.clip(code, 0, ranks.shape[0] - 1)]
                invalid = code < 0
                if vv is not None:
                    invalid = invalid | ~vv
                enc = enc if asc else -enc
                sent = jnp.int64(np.iinfo(np.int64).min) if asc \
                    else jnp.int64(_I64MAX)
                ops_l.append(jnp.where(invalid, sent, enc))
                continue
            v, vv = outs[name]
            enc = v.astype(jnp.int64) if v.dtype == jnp.bool_ else v
            if jnp.issubdtype(enc.dtype, jnp.floating):
                enc = enc if asc else -enc
                if vv is not None:
                    enc = jnp.where(vv, enc,
                                    -jnp.inf if asc else jnp.inf)
            else:
                enc = enc.astype(jnp.int64)
                enc = enc if asc else -enc
                if vv is not None:
                    sent = jnp.int64(np.iinfo(np.int64).min) if asc \
                        else jnp.int64(_I64MAX)
                    enc = jnp.where(vv, enc, sent)
            ops_l.append(enc)
        sout = sort_total(ops_l, iota)
        perm_f = sout[-1][:fin["K"]]
        n_out = jnp.minimum(L, jnp.int64(fin["K"]))
        final_outs = {}
        for alias, (v, vv) in outs.items():
            final_outs[alias] = (v[perm_f],
                                 None if vv is None else vv[perm_f])
        for name in fin["pass_cols"]:
            v = inputs[f"out_{name}"][perm_f]
            vvin = inputs.get(f"outv_{name}")
            final_outs[name] = (v, None if vvin is None
                                else vvin[perm_f])
        return final_outs, n_out

    return fn


_FN_CACHE = None


def _fn_cache():
    global _FN_CACHE
    if _FN_CACHE is None:
        from ydb_tpu.ops.exec_cache import ExecCache
        _FN_CACHE = ExecCache("window")
    return _FN_CACHE


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def compute_windows_device(block, outer, final_sort=None, limit=None,
                           offset=0):
    """Evaluate every window spec of `outer` on device. Returns
    {alias: (np values, np valid|None)} or None when any spec (or key
    encoding) requires the host lane.

    `final_sort`/`limit`: when given ([(name, ascending, win_output?)],
    row limit), the program ALSO sorts the full result by those keys and
    slices to offset+limit rows device-side before transfer — the
    output egress is then O(limit) instead of O(rows) for EVERY column
    (the D2H link is the dominant window cost post-readout, PERF.md r5).
    Returns ({alias_or_col: (values, valid|None, dict|None)}, n_rows)
    in that mode, covering passthrough columns too."""
    from ydb_tpu.ops.device import bucket_capacity

    specs = [p for k, p in outer if k == "win"]
    if not specs or block.length == 0:
        return None
    for s in specs:
        if not spec_supported(s, block):
            return None

    # pre-validate the final-sort keys BEFORE any encoding/upload work:
    # an ineligible key must cost a cheap decline, not a fully-prepared
    # program thrown away (review r5)
    win_aliases_pre = {s["alias"] for s in specs}
    if final_sort is not None and limit is not None:
        for (name, _asc) in final_sort:
            if name in win_aliases_pre:
                continue
            cd = block.columns.get(name)
            if cd is None or not _final_key_ok(cd):
                return None
    else:
        final_sort = None             # offset/limit without both: plain

    # group by sort clause; build the static structure + input arrays
    groups: dict = {}
    order = []
    for s in specs:
        k = _sort_group_key(s)
        if k not in groups:
            groups[k] = []
            order.append(k)
        groups[k].append(s)

    L = block.length
    cap = bucket_capacity(max(L, 1))
    pad = cap - L

    def up(a, fill=0):
        if pad:
            a = np.concatenate(
                [a, np.full(pad, fill, dtype=a.dtype)])
        return jnp.asarray(a)

    inputs = {"length": jnp.int64(L),
              "iota": jnp.arange(cap, dtype=jnp.int64)}
    struct = {"groups": [], "cap": cap}
    for gi, k in enumerate(order):
        part, onames, asc = k
        gspecs = groups[k]
        pi = 0
        for name in part:
            for arr in _encode_part_host(block, [name])[0]:
                if arr is None:
                    continue
                inputs[f"g{gi}p{pi}"] = up(arr)
                pi += 1
        for oi, name in enumerate(onames):
            enc = _encode_order_host(block, name, asc[oi])
            inputs[f"g{gi}o{oi}"] = up(
                enc, fill=np.inf if enc.dtype == np.float64 else _I64MAX)
        sspecs = []
        for si, s in enumerate(gspecs):
            fn = s["func"]
            has_arg = bool(s["args"]) and not (
                fn == "count" and not s["args"])
            off_n = 1
            if fn in ("lead", "lag") and len(s["args"]) > 1:
                off_cd = block.columns[s["args"][1]]
                off_n = int(off_cd.data[0])
                if not (off_cd.data[:L] == off_cd.data[0]).all():
                    return None       # non-constant offset: host lane
            if has_arg:
                cd = block.columns[s["args"][0]]
                if cd.dictionary is not None and fn in (
                        "sum", "avg", "min", "max", "count"):
                    return None       # string aggregates: host lane
                d = cd.data
                if d.dtype == np.bool_:
                    d = d.astype(np.int64)
                inputs[f"g{gi}s{si}a"] = up(d)
                if cd.valid is not None:
                    inputs[f"g{gi}s{si}av"] = up(
                        cd.valid, fill=False)
            sspecs.append({
                "func": fn, "frame": s.get("frame"),
                "has_arg": has_arg,
                "running": bool(s["order"]),
                "offset": off_n, "alias": s["alias"],
                "dict": (block.columns[s["args"][0]].dictionary
                         if has_arg and fn in ("lead", "lag") else None),
            })
        struct["groups"].append({
            "n_part_ops": pi, "n_order": len(onames), "specs": sspecs})

    # final ORDER BY + LIMIT pushed into the program: passthrough
    # columns upload once, every output leaves the device sliced to
    # offset+limit rows
    win_aliases = {s["alias"] for s in specs}
    if final_sort is not None:
        K = min(int(offset) + int(limit), cap)
        dict_of_alias = {s2["alias"]: s2["dict"]
                         for g in struct["groups"] for s2 in g["specs"]}
        fkeys = []
        for fi, (name, ascending) in enumerate(final_sort):
            if name in win_aliases:
                dic = dict_of_alias.get(name)
                if dic is not None:
                    # string-valued window output (lead/lag of a dict
                    # column): sort by LEXICOGRAPHIC rank, not raw
                    # insertion-order codes — ranks upload as a LUT the
                    # program gathers through
                    ranks = dic.sort_ranks().astype(np.int64)
                    inputs[f"frank{fi}"] = jnp.asarray(
                        ranks if len(ranks) else np.zeros(1, np.int64))
                    fkeys.append(("winstr", name, ascending,
                                  f"frank{fi}"))
                else:
                    fkeys.append(("win", name, ascending))
            else:
                cd = block.columns.get(name)
                if cd is None:
                    return None
                enc = _encode_final_key(cd, ascending)
                if enc is None:
                    return None
                inputs[f"fs{fi}"] = up(
                    enc, fill=np.inf if enc.dtype == np.float64
                    else _I64MAX)
                fkeys.append(("col", f"fs{fi}", ascending))
        pass_cols = [p for k2, p in outer if k2 == "col"]
        pass_dicts = {}
        for name in pass_cols:
            cd = block.columns[name]
            d = cd.data
            inputs[f"out_{name}"] = up(d)
            if cd.valid is not None:
                inputs[f"outv_{name}"] = up(cd.valid, fill=False)
            if cd.dictionary is not None:
                pass_dicts[name] = cd.dictionary
        struct["final"] = {"keys": fkeys, "K": K,
                           "pass_cols": list(pass_cols)}

    skey = (cap, repr([(g["n_part_ops"], g["n_order"],
                        [(s["func"], s["frame"], s["has_arg"],
                          s["running"], s["offset"], s["alias"])
                         for s in g["specs"]])
                       for g in struct["groups"]]),
            repr(struct.get("final")),
            tuple(sorted((k, str(v.dtype)) for k, v in inputs.items()
                         if hasattr(v, "dtype"))))
    cache = _fn_cache()
    fn = cache.get(skey)
    if fn is None:
        fn = _build_window_fn(struct)
        cache[skey] = fn
    dicts = {s2["alias"]: s2["dict"]
             for g in struct["groups"] for s2 in g["specs"]}
    if struct.get("final") is not None:
        dev, n_dev = fn(inputs)
        host, n = jax.device_get((dev, n_dev))
        n = int(n)
        dicts.update(pass_dicts)
        out = {}
        # device_get above already landed host ndarrays — slice directly
        for name, (vals, valid) in host.items():
            out[name] = (vals[:n],
                         None if valid is None else valid[:n],
                         dicts.get(name))
        return out, n
    dev = fn(inputs)
    host = jax.device_get(dev)

    out = {}
    for alias, (vals, valid) in host.items():
        out[alias] = (vals[:L],
                      None if valid is None else valid[:L],
                      dicts.get(alias))
    return out
