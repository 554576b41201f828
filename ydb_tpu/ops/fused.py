"""Whole-query fused execution — ONE XLA dispatch per query.

The reference streams blocks through a chain of separately-scheduled
operators (scan actor → block comp nodes → channels,
`dq_compute_actor_impl.h:295`). Every dispatch and every device→host
readout is a fixed round trip (what it costs on the current chip is not
measured yet, PERF.md round 22), so the fused path compiles the ENTIRE single-node query — scan over all
portions, pushdown filters, broadcast-join probes, aggregation, HAVING,
output expressions, ORDER BY, LIMIT — into one `jax.jit` program:

  * scan sources arrive as stacked (K, CAP) "superblocks" per column
    (`DeviceColumnCache.superblock`), flattened to one K·CAP row vector
    with a per-row activity mask (no data-dependent shapes);
  * filters thread a selection mask between programs (`TColumnFilter`
    semantics) — nothing compresses until after aggregation;
  * joins probe via direct-address LUTs (`ops/join.py:probe_lut_traced`) —
    one fused gather per probe, no binary-search loops;
  * GroupBy uses the scatter-free paths of `ops/xla_exec.py`.

A query therefore costs one dispatch + one result readout in the steady
state, versus O(portions × operators) dispatches for the unfused path.
"""

from __future__ import annotations

import hashlib
import re
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ydb_tpu.core.schema import Column, Schema
from ydb_tpu.ops import ir
from ydb_tpu.ops.device import bucket_capacity
from ydb_tpu.ops.join import probe_lut_traced
from ydb_tpu.ops.sort import sort_env
from ydb_tpu.ops.xla_exec import _eval, _trace_program, compress

# the executor-lifted LIMIT+OFFSET device input (companion of the
# query/paramlift.py literal lift; defined here because the ops layer
# must not import the query layer at trace time)
LIMIT_PARAM = "__lim2"


def apply_join_schema(schema: Schema, payload_cols: list) -> Schema:
    """Schema effect of a join probe: payload columns replace any existing
    columns with the same names and append at the end (the single source
    of truth for fused schema threading)."""
    taken = {p.name for p in payload_cols}
    return Schema([c for c in schema.columns if c.name not in taken]
                  + list(payload_cols))


LM_POS = "__lmpos"                       # deferred-scan row-position column


def program_name(pipe, final_program: Optional[ir.Program],
                 join_metas: list, rank_assigns: list, sort_spec: tuple,
                 limit: Optional[int], keep: tuple,
                 compact: bool = False, lane: str = "") -> str:
    """What the fused program is CALLED: the jitted function's
    `__name__`, so XLA's module (`jit_<name>`), the profiler's
    `XLA Modules` line, `.sys/compiled_programs` and EXPLAIN ANALYZE all
    show it — `lineitem_j2_gsc_ab12cd`: root table, joins, marks (g
    group-by, s sort, l limit, c compact; b batched lane, t tile), and a
    digest of the plan's shape (`ir.shape_text`: command kinds, column
    names, kernel ops). At most 40 characters of [a-z0-9_].

    A function of the plan's SHAPE only — no literal, no capacity, no
    row count, no `hash()` or `id()`: the persistent compile cache keys
    on the module's name, so a name that differed between two processes
    would compile again in every one."""
    progs = [pipe.pre_program]
    joins = []
    for kind, step in pipe.steps:
        if kind == "join":
            m = join_metas[len(joins)]
            joins.append(f"join {m['probe_key']} {m['kind']} "
                         f"{','.join(m['payload_names'])}")
        else:
            progs.append(step)
    progs += [pipe.partial, final_program,
              ir.Program(list(rank_assigns)) if rank_assigns else None]
    grouped = any(isinstance(c, ir.GroupBy) for p in progs
                  if p is not None for c in p.commands)
    shape = "\n".join(
        [ir.shape_text(p) for p in progs] + joins
        + [",".join(f"{n}{'+' if asc else '-'}{'f' if nf else 'l'}"
                    for (n, asc, nf) in sort_spec), ",".join(keep)])
    marks = (("g" if grouped else "") + ("s" if sort_spec else "")
             + ("l" if limit is not None else "")
             + ("c" if compact else "") + lane)
    parts = [_table_tag(pipe.scan.table)] \
        + ([f"j{len(joins)}"] if joins else []) \
        + ([marks] if marks else []) + [_shape_digest(shape)]
    return "_".join(parts)


def _table_tag(table: str) -> str:
    # a transient table (the `__` namespace) holds a per-query id
    return "tmp" if table.startswith("__") else \
        re.sub(r"[^a-z0-9_]", "_", table.lower())[:20]


def _shape_digest(shape: str) -> str:
    return hashlib.blake2s(shape.encode(), digest_size=3).hexdigest()


def mesh_program_name(lane: str, table: str, progs, extra=()) -> str:
    """`program_name`'s sibling for a mesh lane's `shard_map` program:
    `mesh_sj_lineitem_ab12cd` — the lane (`sj` the shuffle join's
    exchange, `merge` the partials' merge), the root table, and the same
    digest over the shape of the IR programs it traces and `extra` lines
    (join key and kind, column names). Shape only, as there: no literal,
    no capacity, no device count."""
    shape = "\n".join([ir.shape_text(p) for p in progs] + list(extra))
    tag = _table_tag(table)
    return "_".join(["mesh", lane] + ([tag] if tag else [])
                    + [_shape_digest(shape)])


def _named(fn, name: str):
    fn.__name__ = fn.__qualname__ = name
    return fn


def _prog_refs(prog: ir.Program) -> set:
    """Column names a program actually COMPUTES over (Assign exprs,
    Filter preds, GroupBy keys/carries/agg args). Projection names are
    deliberately excluded: projecting a deferred column keeps it
    deferred (`_trace_program` passthrough) rather than forcing a
    full-capacity gather."""
    refs: set = set()
    for cmd in prog.commands:
        if isinstance(cmd, ir.Assign):
            ir.expr_columns(cmd.expr, refs)
        elif isinstance(cmd, ir.Filter):
            ir.expr_columns(cmd.pred, refs)
        elif isinstance(cmd, ir.GroupBy):
            refs.update(cmd.keys)
            refs.update(cmd.carry_keys)
            refs.update(a.arg for a in cmd.aggs if a.arg is not None)
    return refs


def _fused_body(pipe, final_program: Optional[ir.Program],
                scan_cols: list, K: int, CAP: int,
                sb_valid_names: frozenset, join_metas: list,
                rank_assigns: list, sort_spec: tuple,
                limit: Optional[int], offset: Optional[int],
                keep: tuple, lift_limit: bool = False,
                late_scan: frozenset = frozenset(),
                compact_prog: Optional[ir.Program] = None,
                compact_at: Optional[int] = None):
    """Un-jitted trace body shared by the single-query fused program
    (`build_fused_fn`) and the multi-query batched lane
    (`build_fused_batched_fn`, which vmaps it over stacked params).

    `lift_limit`: LIMIT+OFFSET arrives as the `__lim2` device input
    (paramlift.LIMIT_PARAM) instead of a baked constant — the length
    clamp becomes runtime, while the output slice stays static at the
    limit's capacity bucket (identical to the baked path's bucket, so
    results are byte-equal); callers key the compiled program on the
    bucket, and every limit inside it shares one executable.

    Late materialization (`YDB_TPU_LATE_MAT`, `xla_exec.late_mat_enabled`):
    `late_scan` names scan columns that are NOT loaded into the row env
    up front — a single int32 row-position column (`__lmpos`) rides the
    pipeline instead, and each deferred column gathers from the
    superblock at its first compute reference or at the bound-sized
    tail. A column first referenced while `__lmpos` is still the iota
    the body created (nothing has compacted, grouped, compressed, sorted
    or sliced the env yet: a fact of the trace) is read in place, as an
    eager column is loaded; `layout_box["latemat"]` counts both ways
    (`latemat/direct_cols`, `latemat/gathered_cols`).
    Joins whose meta carries `late` likewise thread a
    (build row-id, match) pair (`ops/join.probe_lut_traced`) in place of
    their payload widths. `compact_prog` (an `ir.Compact` wrapper built
    by the executor; None where the rows do not collapse, and where what
    follows reads them in place: a keyless aggregate such as Q6's sums
    over the selection mask, `latemat.tail_reads_in_place`) shrinks the
    working capacity to a ladder-quantized
    bound after the last reducing join — before `pipe.steps[compact_at]`
    (`Executor._compact_sizing`; None: after the last step) — so every
    later probe, deferred gather and the partial group-by run at the
    small shape: one int32 sort of the live positions, then
    one bound-sized gather per column still in the env
    (`xla_exec.compact_env`: `__lmpos` and the `__lmr<i>` / `__lmf<i>` of
    the joins already probed among them; deferred columns are not in it
    and gather from the superblock, or their build, through the
    compacted row ids). Its live/overflow scalars come back in the 4th
    return element (the executor's loud-rerun input)."""
    lim2 = None if limit is None else limit + (offset or 0)
    layout_box: dict = {}

    def fn(sb, sbv, lengths, builds, params):
        cap0 = K * CAP
        cap = cap0
        aux: dict = {}
        env = {}
        lm_count = {"direct": 0, "gathered": 0}
        deferred: dict = {}              # out name -> ("scan", src) |
        #                                  ("join", join_idx, src)
        # `jax.named_scope`s below are HLO metadata only (`op_name`): a
        # device operation then says which step of the plan it came
        # from; names hold command kinds and column names, no literal
        with jax.named_scope("scan"):
            for c in scan_cols:
                if c.name in late_scan:
                    deferred[c.name] = ("scan", c.name)
                else:
                    d = sb[c.name].reshape(cap0)
                    v = sbv[c.name].reshape(cap0) \
                        if c.name in sb_valid_names else None
                    env[c.name] = (d, v)
            lm_iota = None
            if deferred:
                lm_iota = jnp.arange(cap0, dtype=jnp.int32)
                env[LM_POS] = (lm_iota, None)
            sel = (jnp.arange(CAP, dtype=jnp.int32)[None, :]
                   < lengths[:, None]).reshape(cap0)
            length = jnp.int32(cap0)
        schema = Schema(list(scan_cols))

        def helper_names() -> tuple:
            return tuple(n for n in env if n.startswith("__lm"))

        def gc():
            # drop row-id helper columns whose deferrals are all
            # materialized — they must not ride sorts/compresses for free
            if not any(s[0] == "scan" for s in deferred.values()):
                env.pop(LM_POS, None)
            live_joins = {s[1] for s in deferred.values()
                          if s[0] == "join"}
            for j, m in enumerate(join_metas):
                if m.get("late") and j not in live_joins:
                    env.pop(m["row_col"], None)
                    env.pop(m["found_col"], None)

        def materialize(names):
            # the deferred gather: runs at the CURRENT capacity — after a
            # compact/limit slice that is the bound, not the scan
            for nm in names:
                src = deferred.pop(nm, None)
                if src is None:
                    continue
                if src[0] == "scan":
                    # read in place while nothing has moved or dropped
                    # a row (a Compact, compress, sort or limit slice
                    # each put a NEW array under `__lmpos`): the TPU
                    # compiler does not fold `x[arange(n)]`, 54 ms an
                    # array at SF1
                    pos = env[LM_POS][0]
                    moved = pos is not lm_iota
                    lm_count["gathered" if moved else "direct"] += 1
                    with jax.named_scope(f"latemat[{nm}]"):
                        d = sb[src[1]].reshape(cap0)
                        v = (sbv[src[1]].reshape(cap0)
                             if src[1] in sb_valid_names else None)
                        if moved:
                            d = d[pos]
                            v = v[pos] if v is not None else None
                    env[nm] = (d, v)
                else:
                    _k, j, s = src
                    with jax.named_scope(f"join{j}.payload[{nm}]"):
                        m = join_metas[j]
                        row = env[m["row_col"]][0]
                        ok = env[m["found_col"]][0]
                        pv = builds[j]["pvalid"].get(s)
                        d = builds[j]["payload"][s][row]
                        v = ok if pv is None else (ok & pv[row])
                    env[nm] = (d, v)
            gc()

        def run(prog):
            nonlocal env, length, sel, schema, cap
            materialize(sorted(_prog_refs(prog) & set(deferred)))
            env, length, sel, schema = _trace_program(
                prog, schema.columns, cap, env, length, params, sel=sel,
                aux=aux, passthrough=helper_names())
            if env:
                cap = next(iter(env.values()))[0].shape[0]
            elif sel is not None:
                # a column-free env (count(*) plans) still changes
                # capacity through a Compact — the mask carries it
                cap = sel.shape[0]
            # a GroupBy/Projection that dropped a deferred column from
            # the schema retires its deferral (it no longer exists)
            for nm in [n for n in deferred if not schema.has(n)]:
                del deferred[nm]
            gc()

        if pipe.pre_program is not None:
            run(pipe.pre_program)
        steps = list(pipe.steps)
        if compact_prog is not None:
            steps.insert(len(steps) if compact_at is None else compact_at,
                         ("compact", compact_prog))
        bi = 0
        for kind, step in steps:
            if kind == "join":
                meta = join_metas[bi]
                if meta["probe_key"] in deferred:
                    materialize([meta["probe_key"]])
                with jax.named_scope(f"join{bi}.probe"):
                    env, sel = probe_lut_traced(env, sel, builds[bi], meta)
                if meta.get("late") and meta["kind"] in ("inner", "left"):
                    for src, out in zip(meta["src_names"],
                                        meta["payload_names"]):
                        env.pop(out, None)   # replaced by this probe
                        deferred[out] = ("join", bi, src)
                bi += 1
                schema = apply_join_schema(schema, meta["payload_cols"])
            else:
                run(step)
        if pipe.partial is not None:
            run(pipe.partial)
        if final_program is not None:
            run(final_program)
        if sel is not None:
            with jax.named_scope("compress"):
                env, length = compress(env, length, sel, cap)
            sel = None

        need: set = set()
        for a in rank_assigns:
            ir.expr_columns(a.expr, need)
        need.update(n for (n, _asc, _nf) in sort_spec)
        materialize(sorted(need & set(deferred)))
        if sort_spec:
            with jax.named_scope("sort"):
                for a in rank_assigns:
                    env[a.name] = _eval(a.expr, env, params, cap)
                arrays = {n: d for n, (d, _v) in env.items()}
                valids = {n: v for n, (d, v) in env.items()
                          if v is not None}
                arrays2, valids2, length = sort_env(
                    arrays, valids, length, None, sort_spec,
                    tuple(arrays.keys()))
                env = {n: (arrays2[n], valids2.get(n)) for n in arrays2}
        if lim2 is not None:
            with jax.named_scope("limit"):
                bound = params[LIMIT_PARAM] if lift_limit \
                    else jnp.int32(lim2)
                length = jnp.minimum(length, bound)
                out_cap = min(bucket_capacity(lim2, minimum=128), cap)
                env = {n: (d[:out_cap],
                           v[:out_cap] if v is not None else None)
                       for n, (d, v) in env.items()}
        # the tail gather: whatever is still deferred materializes HERE,
        # at the post-limit capacity — a LIMIT-K plan gathers its payload
        # widths for K-bucket rows, not scan capacity
        want = [n for n in keep if n in env or n in deferred]
        if want:
            materialize([n for n in want if n in deferred])
        else:
            materialize(sorted(deferred))
            want = [n for n in env if not n.startswith("__lm")]
        out_names = [n for n in want if n in env]
        groups: dict = {}
        data_layout = []
        for n in out_names:
            d = env[n][0]
            key = str(d.dtype)
            groups.setdefault(key, []).append(d)
            data_layout.append((n, key, len(groups[key]) - 1))
        valid_names = [n for n in out_names if env[n][1] is not None]
        layout_box["data"] = data_layout
        layout_box["valids"] = valid_names
        layout_box["latemat"] = lm_count
        with jax.named_scope("output"):
            data_stacks = {k: jnp.stack(v) for k, v in groups.items()}
            valid_stack = (jnp.stack([env[n][1] for n in valid_names])
                           if valid_names else None)
        return data_stacks, valid_stack, length, aux

    return fn, layout_box


def build_fused_fn(pipe, final_program: Optional[ir.Program],
                   scan_cols: list, K: int, CAP: int,
                   sb_valid_names: frozenset, join_metas: list,
                   rank_assigns: list, sort_spec: tuple,
                   limit: Optional[int], offset: Optional[int],
                   keep: tuple, lift_limit: bool = False,
                   late_scan: frozenset = frozenset(),
                   compact_prog: Optional[ir.Program] = None,
                   compact_at: Optional[int] = None):
    """Compile the full single-node query pipeline into one jitted fn.

    scan_cols: [Column] of the flattened scan env (internal names).
    join_metas: per join step, the static meta dict for
    `probe_lut_traced` plus "payload_cols" ([Column] appended to the
    schema by the probe).

    Returns (fn, layout_box); fn(sb, sbv, lengths, builds, params) →
    (data_stacks {dtype: (k, cap)}, valid_stack (m, cap) | None, length,
    aux) — `aux` is empty unless `compact_prog` ran (then it carries the
    compact live count + overflow flag; the executor consumes it before
    any result use). Outputs are STACKED by dtype so the result crosses
    the link in a handful of transfers instead of one per column (each
    device→host round trip costs ~15 ms on this platform — PERF.md);
    `layout_box` is filled at trace time with
    {"data": [(name, dtype_str, row)], "valids": [name]} describing the
    stacking."""
    fn, layout_box = _fused_body(pipe, final_program, scan_cols, K, CAP,
                                 sb_valid_names, join_metas, rank_assigns,
                                 sort_spec, limit, offset, keep,
                                 lift_limit=lift_limit,
                                 late_scan=late_scan,
                                 compact_prog=compact_prog,
                                 compact_at=compact_at)
    name = program_name(pipe, final_program, join_metas, rank_assigns,
                        sort_spec, limit, keep,
                        compact=compact_prog is not None)
    return jax.jit(_named(fn, name)), layout_box


def build_fused_batched_fn(pipe, final_program: Optional[ir.Program],
                           scan_cols: list, K: int, CAP: int,
                           sb_valid_names: frozenset, join_metas: list,
                           rank_assigns: list, sort_spec: tuple,
                           limit: Optional[int], offset: Optional[int],
                           keep: tuple, param_axes: dict, axis_size: int,
                           lift_limit: bool = False,
                           late_scan: frozenset = frozenset()):
    """The multi-query batched dispatch program: ONE executable running
    `axis_size` same-shape queries as a vmap over their stacked lifted
    params (DrJAX's mapped-over-a-fixed-program composition, arxiv
    2403.07128). Scan superblock, build tables, and any param whose
    value is batch-invariant broadcast (in_axes None); only the
    per-member params carry the leading batch axis (`param_axes`:
    {name: 0 | None}). Outputs gain a leading batch axis; each client's
    result is its slice (`fetch_fused_batch`). Late materialization rides
    the vmapped trace unchanged (row-id gathers batch like any other op);
    the compact step stays single-query-only, so `aux` is always empty
    here."""
    fn, layout_box = _fused_body(pipe, final_program, scan_cols, K, CAP,
                                 sb_valid_names, join_metas, rank_assigns,
                                 sort_spec, limit, offset, keep,
                                 lift_limit=lift_limit, late_scan=late_scan)
    batched = jax.vmap(fn, in_axes=(None, None, None, None, param_axes),
                       axis_size=axis_size)
    name = program_name(pipe, final_program, join_metas, rank_assigns,
                        sort_spec, limit, keep, lane="b")
    return jax.jit(_named(batched, name)), layout_box


def _unpack_fused_host(host_stacks, host_valids, n: int, layout_box: dict,
                       out_schema: Schema, out_dicts: dict):
    """Host-side assembly of one query's result from already-transferred
    dtype-stacked arrays (shared by the single-query fetch and each
    member slice of a batched fetch)."""
    from ydb_tpu.core.block import HostBlock
    from ydb_tpu.ops.device import host_column

    valid_row = {nm: i for i, nm in enumerate(layout_box["valids"])}
    cols = {}
    out_cols = []
    for (name, dtype_key, row) in layout_box["data"]:
        if not out_schema.has(name):
            continue
        valid = (host_valids[valid_row[name]][:n]
                 if name in valid_row and host_valids is not None
                 else None)
        cols[name] = host_column(host_stacks[dtype_key][row][:n], valid,
                                 out_schema.dtype(name),
                                 out_dicts.get(name))
        out_cols.append(out_schema.col(name))
    return HostBlock(Schema(out_cols), cols, n)


def fetch_fused_result(data_stacks, valid_stack, length, layout_box: dict,
                       out_schema: Schema, out_dicts: dict):
    """Device→host readout of one fused dispatch: ONE `jax.device_get`
    for the whole result (length included) — per-column fetches pay a
    full link round trip each (PERF.md). Large row-level outputs sync
    the length first and slice device-side so padding doesn't cross the
    link. This is the deferred half of the device-result future: the
    dispatch returns immediately and this runs when the result is
    consumed, so concurrent queries overlap compute with D2H drains."""
    from ydb_tpu.utils import memledger
    cap_out = (next(iter(data_stacks.values())).shape[1]
               if data_stacks else 0)
    padded_bytes = memledger.deep_nbytes((data_stacks, valid_stack))
    if cap_out > (1 << 16):
        n = int(length)
        m = max(n, 1)
        data_stacks = {k: v[:, :m] for k, v in data_stacks.items()}
        if valid_stack is not None:
            valid_stack = valid_stack[:, :m]
        # lint: transfer-ok(result egress — padding sliced off device-side first)
        host_stacks, host_valids = jax.device_get(
            (data_stacks, valid_stack))
    else:
        # lint: transfer-ok(result egress — the fused path's ONE pytree readback)
        host_stacks, host_valids, n = jax.device_get(
            (data_stacks, valid_stack, length))
        n = int(n)
    # capacity-sized outputs (group-by buckets, LIMIT buckets): the live
    # result rows vs the power-of-two output capacity the program wrote
    if cap_out:
        memledger.record_pad(
            "result_capacity", n, cap_out,
            int(padded_bytes * min(n, cap_out) / cap_out), padded_bytes)
    memledger.record_transfer(
        "ops/fused.py::fetch_fused_result",
        memledger.deep_nbytes((host_stacks, host_valids)), boundary=True)
    return _unpack_fused_host(host_stacks, host_valids, n, layout_box,
                              out_schema, out_dicts)


def capture_fused_device(data_stacks, valid_stack, length, layout_box: dict,
                         out_schema: Schema, out_dicts: dict):
    """Device-resident view of one fused dispatch: the stage-spine
    capture. Slices the dtype-stacked output rows back into per-column
    device arrays BY REFERENCE — zero transfers, zero copies — so a DQ
    stage can hand the result to the next stage (or the planned ICI
    exchange) without the host round-trip `fetch_fused_result` pays.
    `length` stays whatever scalar the caller holds (host int at the
    capture seam); padding above it is dead rows the consumer masks."""
    from ydb_tpu.ops.device import DeviceBlock

    valid_row = {nm: i for i, nm in enumerate(layout_box["valids"])}
    arrays, valids, dicts = {}, {}, {}
    out_cols = []
    for (name, dtype_key, row) in layout_box["data"]:
        if not out_schema.has(name):
            continue
        arrays[name] = data_stacks[dtype_key][row]
        if name in valid_row and valid_stack is not None:
            valids[name] = valid_stack[valid_row[name]]
        if out_dicts.get(name) is not None:
            dicts[name] = out_dicts[name]
        out_cols.append(out_schema.col(name))
    cap = int(next(iter(arrays.values())).shape[0]) if arrays else 0
    return DeviceBlock(Schema(out_cols), arrays, valids, length, cap,
                       dicts)


def fetch_fused_batch(data_stacks, valid_stack, lengths, layout_box: dict,
                      out_schema: Schema, out_dicts: dict,
                      member_rows: list):
    """Device→host readout of one BATCHED dispatch: still ONE
    `jax.device_get` — for the whole batch — then each member unpacks
    its slice host-side. `member_rows[i]` is member i's batch-axis row
    (identical-query dedup maps every member to row 0; padded rows are
    never read). Returns [HostBlock], one per member."""
    from ydb_tpu.utils import memledger
    # lint: transfer-ok(result egress — one readback for the whole batch)
    host_stacks, host_valids, ns = jax.device_get(
        (data_stacks, valid_stack, lengths))
    memledger.record_transfer(
        "ops/fused.py::fetch_fused_batch",
        memledger.deep_nbytes((host_stacks, host_valids)), boundary=True)
    out = []
    for b in member_rows:
        hs = {k: v[b] for k, v in host_stacks.items()}
        hv = host_valids[b] if host_valids is not None else None
        out.append(_unpack_fused_host(hs, hv, int(ns[b]), layout_box,
                                      out_schema, out_dicts))
    return out


def build_tile_fn(pipe, scan_cols: list, K: int, CAP: int,
                  sb_valid_names: frozenset, join_metas: list):
    """Fused scan→filter→join→partial-agg program for ONE tile of a scan
    too large for HBM (the streaming front half of `build_fused_fn`,
    stopping after `pipe.partial`). The reference streams blocks through
    its combiner the same way before the merge stage
    (`mkql_wide_combine.cpp` InMemory state); here a tile is K stacked
    sources in one dispatch and the partial stays device-resident for the
    finalize/merge stage.

    fn(sb, sbv, lengths, builds, params) → (data {name}, valids {name},
    length) — compressed (active rows at front), NOT transferred. Tiles
    stream and merge host-side, so the late-materialization deferral is
    stripped here (a row-id crossing a tile boundary would dangle)."""
    join_metas = [{**m, "late": False} for m in join_metas]

    def fn(sb, sbv, lengths, builds, params):
        cap = K * CAP
        env = {}
        with jax.named_scope("scan"):
            for c in scan_cols:
                d = sb[c.name].reshape(cap)
                v = sbv[c.name].reshape(cap) \
                    if c.name in sb_valid_names else None
                env[c.name] = (d, v)
            sel = (jnp.arange(CAP, dtype=jnp.int32)[None, :]
                   < lengths[:, None]).reshape(cap)
            length = jnp.int32(cap)
        schema = Schema(list(scan_cols))

        def run(prog, env, length, sel, schema, cap):
            env, length, sel, schema = _trace_program(
                prog, schema.columns, cap, env, length, params, sel=sel)
            if env:
                cap = next(iter(env.values()))[0].shape[0]
            return env, length, sel, schema, cap

        if pipe.pre_program is not None:
            env, length, sel, schema, cap = run(pipe.pre_program, env,
                                                length, sel, schema, cap)
        bi = 0
        for kind, step in pipe.steps:
            if kind == "join":
                meta = join_metas[bi]
                with jax.named_scope(f"join{bi}.probe"):
                    env, sel = probe_lut_traced(env, sel, builds[bi], meta)
                bi += 1
                schema = apply_join_schema(schema, meta["payload_cols"])
            else:
                env, length, sel, schema, cap = run(step, env, length, sel,
                                                    schema, cap)
        if pipe.partial is not None:
            env, length, sel, schema, cap = run(pipe.partial, env, length,
                                                sel, schema, cap)
        if sel is not None:
            with jax.named_scope("compress"):
                env, length = compress(env, length, sel, cap)
        out_d = {n: d for n, (d, _v) in env.items()}
        out_v = {n: v for n, (d, v) in env.items() if v is not None}
        return out_d, out_v, length

    name = program_name(pipe, None, join_metas, (), (), None, (), lane="t")
    return jax.jit(_named(fn, name))


def tile_cache_key(pipe, scan_cols, K, CAP, sb_valid_names, builds_sig,
                   param_names):
    from ydb_tpu.ops.xla_exec import groupby_tuning
    progs = []
    if pipe.pre_program is not None:
        progs.append(pipe.pre_program.fingerprint())
    for kind, step in pipe.steps:
        if kind == "join":
            progs.append(("join", step.probe_key, step.kind,
                          tuple(step.payload), step.mark_col, step.not_in))
        else:
            progs.append(step.fingerprint())
    if pipe.partial is not None:
        progs.append(pipe.partial.fingerprint())
    return ("tile", tuple(progs),
            tuple((c.name, c.dtype.kind.value, c.dtype.nullable)
                  for c in scan_cols),
            K, CAP, tuple(sorted(sb_valid_names)), builds_sig,
            tuple(param_names), groupby_tuning())


def fused_cache_key(plan, scan_cols, K, CAP, sb_valid_names, builds_sig,
                    sort_spec, rank_assigns, param_names, lim_key=None,
                    compact_cap=None, compact_at=None):
    # the plan signature carries the group-by tuning (tile rows, the
    # late-mat lever): the cost gate for the tile count P runs at trace
    # time from (capacity, tuning), so a knob flip must compile a fresh
    # program rather than reuse one tiled differently.
    # `lim_key`: lifted-LIMIT plans key on the limit's capacity bucket
    # (("limB", bucket)) instead of the exact values — every LIMIT inside
    # one bucket shares one executable, the clamp rides in as __lim2
    from ydb_tpu.ops.xla_exec import groupby_tuning
    pipe = plan.pipeline
    progs = []
    if pipe.pre_program is not None:
        progs.append(pipe.pre_program.fingerprint())
    for kind, step in pipe.steps:
        if kind == "join":
            progs.append(("join", step.probe_key, step.kind,
                          tuple(step.payload), step.mark_col, step.not_in))
        else:
            progs.append(step.fingerprint())
    if pipe.partial is not None:
        progs.append(pipe.partial.fingerprint())
    if plan.final_program is not None:
        progs.append(plan.final_program.fingerprint())
    lim = (plan.limit, plan.offset) if lim_key is None else lim_key
    return (tuple(progs),
            tuple((c.name, c.dtype.kind.value, c.dtype.nullable)
                  for c in scan_cols),
            K, CAP, tuple(sorted(sb_valid_names)), builds_sig,
            sort_spec,
            ir.Program(rank_assigns).fingerprint() if rank_assigns else "",
            lim,
            tuple(n for (n, _lbl) in plan.output), tuple(param_names),
            # ladder-quantized compact capacity: a re-sized compact is a
            # different program, and so is one before an earlier step
            # (the position follows the builds' row counts; the end, the
            # only place before PR 31, keeps the key it had); the
            # late-mat LEVER itself rides inside groupby_tuning(), so a
            # flip can never reuse this trace
            ("compact", int(compact_cap or 0))
            + ((int(compact_at),) if compact_at is not None
               and compact_at < len(pipe.steps) else ()),
            groupby_tuning())


def build_inputs_sig(bt) -> tuple:
    """Shape signature of a BuildTable's traced inputs. keys_sorted is
    ALWAYS traced (bsearch probes), so its capacity is always part of
    the signature."""
    return (bt.lut.shape[0] if bt.lut is not None else "bs",
            bt.keys_sorted.shape[0],
            next(iter(bt.payload.values())).shape[0] if bt.payload else 0,
            tuple(sorted(bt.payload)), tuple(sorted(bt.payload_valid)))


def build_traced_inputs(bt) -> dict:
    """The traced-input pytree for one BuildTable."""
    out = {
        "lut_base": jnp.int64(bt.lut_base),
        "n": jnp.int32(bt.n),
        "has_null": jnp.bool_(bt.anti_has_null),
        "keys": bt.keys_sorted,      # bsearch probes (sparse/float keys)
        "payload": dict(bt.payload),
        "pvalid": dict(bt.payload_valid),
    }
    if bt.lut is not None:           # pytree shape is part of the jit sig
        out["lut"] = bt.lut
    return out
