#!/usr/bin/env python
"""CI gate for incremental materialized views (`ydb_tpu/views/`).

Two subprocesses against one durable data dir + one progstore dir
(each with a clean process-global program inventory, the way real
restarts look):

  A. warm: create a group-by view (NULLable string key, count/sum/
     min/max/avg) over a row table, drive seeded randomized insert/
     update/delete batches — after every batch the view read must match
     a full recompute at the same watermark (exact for ints/strings,
     1e-9 rtol for floats), including a targeted min/max-under-delete
     sequence — then `kill -9` ITSELF: the host mirror and the fold
     programs must already be durable;
  B. restart: reopen the same dirs — the view state comes back from the
     host mirror with ZERO counted rebuilds, reads still match
     recompute byte-for-byte vs run A, new deltas fold with
     `prog/compile_ms == 0` (every fold program deserializes from the
     progstore: `prog/store_hits` > 0), and `DROP MATERIALIZED VIEW`
     unsubscribes the changefeed consumer and frees state
     (counter-checked: `view/registered` back to 0, mirror gone,
     auto topic gone).

Prints one JSON line; exit 0 = green.
"""

import hashlib
import json
import os
import signal
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SEED = 0xD1FF
VIEW_SEL = ("select g, count(*) as n, count(b) as nb, sum(a) as s, "
            "min(a) as mn, max(a) as mx, avg(b) as av from t group by g")


def mk_engine(data_dir):
    from ydb_tpu.query import QueryEngine

    eng = QueryEngine(block_rows=1 << 13, data_dir=data_dir)
    if not eng.catalog.has("t"):
        eng.execute("create table t (id Int64 not null, g Utf8, "
                    "a Int64, b Double, primary key (id)) "
                    "with (store = row)")
    return eng


def _canon(df, keys):
    """Sorted, canonically rendered frame — the cross-process digest
    domain (float bits are deterministic for identical folds)."""
    if len(df):
        df = df.sort_values(keys, na_position="first")
    return df.to_csv(index=False, float_format="%.17g")


def digest(df, keys) -> str:
    return hashlib.blake2s(_canon(df, keys).encode(),
                           digest_size=16).hexdigest()


def same(view_df, base_df, keys) -> bool:
    import numpy as np

    if list(view_df.columns) != list(base_df.columns) \
            or len(view_df) != len(base_df):
        return False
    if not len(base_df):
        return True
    a = view_df.sort_values(keys, na_position="first").reset_index(drop=True)
    b = base_df.sort_values(keys, na_position="first").reset_index(drop=True)
    for c in a.columns:
        va, vb = a[c].tolist(), b[c].tolist()
        if any(isinstance(x, float) for x in va + vb):
            fa = np.array([np.nan if x is None else x for x in va], float)
            fb = np.array([np.nan if x is None else x for x in vb], float)
            if not np.allclose(fa, fb, rtol=1e-9, equal_nan=True):
                return False
        elif va != vb:
            return False
    return True


def _dml_round(eng, rng, nxt, live):
    op = int(rng.integers(0, 3))
    if op == 0 or not live:
        vals = []
        for _ in range(int(rng.integers(2, 10))):
            i = nxt[0]
            nxt[0] += 1
            live.add(i)
            g = "null" if rng.random() < 0.25 \
                else f"'g{int(rng.integers(0, 5))}'"
            b = "null" if rng.random() < 0.2 else f"{float(rng.normal()):.6f}"
            vals.append(f"({i}, {g}, {int(rng.integers(-99, 99))}, {b})")
        eng.execute(f"insert into t (id, g, a, b) values {', '.join(vals)}")
    elif op == 1:
        for i in rng.choice(sorted(live), size=min(len(live), 4),
                            replace=False):
            eng.execute(f"update t set a = {int(rng.integers(-99, 99))}, "
                        f"b = {float(rng.normal()):.6f} where id = {int(i)}")
    else:
        for i in rng.choice(sorted(live), size=min(len(live), 3),
                            replace=False):
            live.discard(int(i))
            eng.execute(f"delete from t where id = {int(i)}")


def _drive(eng, rounds, seed):
    """Seeded DML rounds, differential check after every one. Returns
    (all_matched, live_ids)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    live = set(int(x) for x in eng.query("select id from t").id) \
        if eng.catalog.has("t") else set()
    nxt = [max(live) + 1 if live else 0]
    ok = True
    for _ in range(rounds):
        _dml_round(eng, rng, nxt, live)
        ok = ok and same(eng.query("select * from mv"),
                         eng.query(VIEW_SEL), ["g"])
    return ok, live


def _minmax_under_delete(eng) -> bool:
    """Delete the current per-group extreme rows; the view must track
    the next extreme exactly (multiset semantics, no rebuild)."""
    df = eng.query("select g, mn, mx from mv")
    ok = True
    for _, r in df.iterrows():
        gp = "g is null" if r.g is None else f"g = '{r.g}'"
        eng.execute(f"delete from t where {gp} and a = {int(r.mx)}")
    ok = ok and same(eng.query("select * from mv"),
                     eng.query(VIEW_SEL), ["g"])
    return ok


def child_warm() -> int:
    import jax
    jax.config.update("jax_platforms", "cpu")
    from ydb_tpu.utils.metrics import GLOBAL

    eng = mk_engine(os.environ["VIEWS_GATE_DATA"])
    eng.execute(f"create materialized view mv as {VIEW_SEL}")
    ok, _live = _drive(eng, rounds=16, seed=SEED)
    ok = ok and _minmax_under_delete(eng)
    v = eng.views.get("mv")
    v.serve(eng.snapshot())                 # drain + mirror at rest
    eng.query("select id from t")           # warm _drive's seed query too
    out = {
        "diff_ok": ok,
        "digest": digest(eng.query("select * from mv"), ["g"]),
        "rows": int(eng.query("select count(*) as n from t").n[0]),
        "folds": v.folds,
        "rebuilds": v.rebuilds,
        "applied_deltas": GLOBAL.get("view/applied_deltas"),
        "registered": GLOBAL.get("view/registered"),
    }
    out["ok"] = bool(ok and v.folds > 0 and v.rebuilds == 0
                     and out["applied_deltas"] > 0
                     and out["registered"] == 1)
    print(json.dumps(out), flush=True)
    # crash, don't exit: mirror + progstore must already be durable
    os.kill(os.getpid(), signal.SIGKILL)
    return 1                               # unreachable


def child_restart() -> int:
    import jax
    jax.config.update("jax_platforms", "cpu")
    from ydb_tpu.utils.metrics import GLOBAL

    warm = json.loads(os.environ["VIEWS_GATE_WARM"])
    eng = mk_engine(os.environ["VIEWS_GATE_DATA"])
    v = eng.views.get("mv")
    restored = v is not None and v.rebuilds == 0    # mirror, not recompute
    d0 = digest(eng.query("select * from mv"), ["g"])
    ok, _live = _drive(eng, rounds=6, seed=SEED + 1)    # keep folding
    out = {
        "restored_from_mirror": bool(restored),
        "digest_matches_warm": d0 == warm["digest"],
        "diff_ok": ok,
        "compile_ms": GLOBAL.get("prog/compile_ms"),
        "store_hits": GLOBAL.get("prog/store_hits"),
        "folds_after_restart": v.folds if v else -1,
        "rebuilds": v.rebuilds if v else -1,
    }
    zero_recompile = bool(out["compile_ms"] == 0 and out["store_hits"] > 0)

    # DROP unsubscribes the consumer and frees state, counter-checked
    mirror = os.path.join(os.environ["VIEWS_GATE_DATA"],
                          "__views", "mv.json")
    eng.execute("drop materialized view mv")
    out["drop"] = {
        "registered": GLOBAL.get("view/registered"),
        "mirror_gone": not os.path.exists(mirror),
        "view_gone": not eng.views.has("mv"),
        "topic_gone": "__cdc_t" not in eng.topics,
        "source_unwired": eng.catalog.table("t").changefeed is None,
    }
    out["ok"] = bool(restored and out["digest_matches_warm"] and ok
                     and zero_recompile
                     and out["folds_after_restart"] > warm["folds"]
                     and out["rebuilds"] == 0
                     and out["drop"]["mirror_gone"]
                     and out["drop"]["view_gone"]
                     and out["drop"]["topic_gone"]
                     and out["drop"]["source_unwired"]
                     and out["drop"]["registered"] == 0)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


def _last_json(stdout: bytes):
    for ln in reversed(stdout.decode(errors="replace").splitlines()):
        ln = ln.strip()
        if ln.startswith("{"):
            return json.loads(ln)
    return None


def main() -> int:
    mode = os.environ.get("VIEWS_GATE_CHILD")
    if mode == "warm":
        return child_warm()
    if mode == "restart":
        return child_restart()

    import shutil
    tmp = tempfile.mkdtemp(prefix="views_gate_")
    data_dir = os.path.join(tmp, "data")
    store_dir = os.path.join(tmp, "pstore")
    base = dict(os.environ)
    base["JAX_PLATFORMS"] = "cpu"
    base["YDB_TPU_PROGSTORE"] = store_dir
    base["VIEWS_GATE_DATA"] = data_dir
    # deterministic compile accounting, same levers as progstore_gate
    base["YDB_TPU_COMPILE_AHEAD"] = "0"
    for k in ("JAX_COMPILATION_CACHE_DIR", "YDB_TPU_PROGSTATS",
              "YDB_TPU_PROGSTORE_DEVICE",
              "YDB_TPU_VIEW_FOLD_BATCH", "YDB_TPU_VIEW_MAX_GROUPS"):
        base.pop(k, None)
    me = os.path.abspath(__file__)
    out = {"ok": False, "data_dir": data_dir}
    try:
        env = {**base, "VIEWS_GATE_CHILD": "warm"}
        rw = subprocess.run([sys.executable, me], env=env,
                            capture_output=True, timeout=900)
        warm = _last_json(rw.stdout)
        out["warm"] = warm
        out["warm_killed"] = rw.returncode == -signal.SIGKILL
        if not (warm and warm.get("ok") and out["warm_killed"]):
            sys.stderr.write(rw.stderr.decode(errors="replace")[-2000:])
            print(json.dumps(out), flush=True)
            return 1

        env = {**base, "VIEWS_GATE_CHILD": "restart",
               "VIEWS_GATE_WARM": json.dumps(warm)}
        rr = subprocess.run([sys.executable, me], env=env,
                            capture_output=True, timeout=900)
        out["restart"] = _last_json(rr.stdout)
        if rr.returncode != 0:
            sys.stderr.write(rr.stderr.decode(errors="replace")[-2000:])
        out["ok"] = bool(rr.returncode == 0)
        print(json.dumps(out), flush=True)
        return 0 if out["ok"] else 1
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
