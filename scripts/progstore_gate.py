#!/usr/bin/env python
"""CI gate for zero-compile serving (`ydb_tpu/progstore/`).

Three subprocesses against one store directory (each with a clean
process-global inventory, the way real restarts look):

  A. warm: an SF1-shaped fused bench join + a group-by land their
     fresh-compiled executables in `YDB_TPU_PROGSTORE`, print result
     digests + counters, then `kill -9` THEMSELVES — no clean shutdown,
     the manifest must already be durable;
  B. restart: same store dir, regenerated identical data — every
     dispatched shape deserializes (`prog/store_hits` == the warmed
     shape count), `prog/compile_ms` stays EXACTLY 0, every fused
     inventory row says `source='store'`, and both result digests are
     byte-equal to run A's;
  C. lever off: `YDB_TPU_PROGSTORE=0` runs byte-equal with zero store
     files touched and zero store counters moving.

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

ROWS = 40_000
NKEYS = 311
JOIN_SQL = ("select k, count(*) as n, sum(v) as s, sum(x) as sx "
            "from t, u where k = uid group by k order by k")
GROUP_SQL = "select k, sum(v) as s, count(*) as n from t group by k order by k"


def mk_engine():
    import numpy as np
    import pandas as pd

    from ydb_tpu.query import QueryEngine

    eng = QueryEngine(block_rows=1 << 13)
    eng.execute("create table t (id Int64 not null, k Int64 not null, "
                "v Double not null, primary key (id)) "
                "with (store = column)")
    ids = np.arange(ROWS, dtype=np.int64)
    df = pd.DataFrame({"id": ids, "k": ids % NKEYS, "v": ids * 0.5})
    t = eng.catalog.table("t")
    t.bulk_upsert(df, eng._next_version())
    t.indexate()
    eng.execute("create table u (uid Int64 not null, x Double not null, "
                "primary key (uid))")
    uids = np.arange(NKEYS, dtype=np.int64)
    du = pd.DataFrame({"uid": uids, "x": 10.0 + uids * 0.25})
    u = eng.catalog.table("u")
    u.bulk_upsert(du, eng._next_version())
    u.indexate()
    eng.prewarm()
    return eng


def digest(df) -> str:
    return hashlib.blake2s(
        df.to_csv(index=False).encode(), digest_size=16).hexdigest()


def child_warm() -> int:
    import jax
    jax.config.update("jax_platforms", "cpu")
    from ydb_tpu.utils.metrics import GLOBAL

    from ydb_tpu.utils import progstats

    eng = mk_engine()
    digests = {"join": digest(eng.query(JOIN_SQL)),
               "group": digest(eng.query(GROUP_SQL))}
    # introspect via the inventory API, NOT a `.sys` SELECT — the
    # sysview query would compile (and store) its own fused program
    # with a content-dependent shape, polluting the warmed-shape count
    fused = [r for r in progstats.inventory_rows() if r["kind"] == "fused"]
    out = {"digests": digests,
           "warmed_shapes": len(fused),
           "store_writes": GLOBAL.get("prog/store_writes"),
           "compile_ms": GLOBAL.get("prog/compile_ms"),
           "store_errors": GLOBAL.get("prog/store_errors"),
           "ok": bool(len(fused) >= 2
                      and GLOBAL.get("prog/store_writes") >= len(fused)
                      and GLOBAL.get("prog/compile_ms") > 0
                      and GLOBAL.get("prog/store_errors") == 0)}
    print(json.dumps(out), flush=True)
    # crash, don't exit: the store must be durable with NO shutdown
    # hook having run
    os.kill(os.getpid(), signal.SIGKILL)
    return 1                               # unreachable


def child_restart() -> int:
    import jax
    jax.config.update("jax_platforms", "cpu")
    from ydb_tpu.utils.metrics import GLOBAL

    from ydb_tpu.utils import progstats

    warm = json.loads(os.environ["PROGSTORE_GATE_WARM"])
    eng = mk_engine()
    digests = {"join": digest(eng.query(JOIN_SQL)),
               "group": digest(eng.query(GROUP_SQL))}
    inv = [r for r in progstats.inventory_rows() if r["kind"] == "fused"]
    sources = sorted({r["source"] for r in inv})
    out = {
        "digests": digests,
        "store_hits": GLOBAL.get("prog/store_hits"),
        "store_misses": GLOBAL.get("prog/store_misses"),
        "compile_ms": GLOBAL.get("prog/compile_ms"),
        "store_writes": GLOBAL.get("prog/store_writes"),
        "sources": sources,
        "fused_rows": len(inv),
    }
    out["ok"] = bool(
        digests == warm["digests"]
        and out["compile_ms"] == 0          # the zero-compile restart
        and out["store_hits"] == warm["warmed_shapes"]
        and out["store_writes"] == 0
        and sources == ["store"]
        and all(float(r["compile_ms"]) == 0.0 for r in inv))
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


def child_lever_off() -> int:
    import jax
    jax.config.update("jax_platforms", "cpu")
    from ydb_tpu.progstore import store
    from ydb_tpu.utils.metrics import GLOBAL

    warm = json.loads(os.environ["PROGSTORE_GATE_WARM"])
    eng = mk_engine()
    digests = {"join": digest(eng.query(JOIN_SQL)),
               "group": digest(eng.query(GROUP_SQL))}
    out = {
        "digests": digests,
        "store_disabled": store.get_store() is None,
        "writes": GLOBAL.get("prog/store_writes"),
        "hits": GLOBAL.get("prog/store_hits"),
        "misses": GLOBAL.get("prog/store_misses"),
    }
    out["ok"] = bool(digests == warm["digests"]
                     and out["store_disabled"]
                     and out["writes"] == 0 and out["hits"] == 0
                     and out["misses"] == 0)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


def _last_json(stdout: bytes):
    for ln in reversed(stdout.decode(errors="replace").splitlines()):
        ln = ln.strip()
        if ln.startswith("{"):
            return json.loads(ln)
    return None


def main() -> int:
    mode = os.environ.get("PROGSTORE_GATE_CHILD")
    if mode == "warm":
        return child_warm()
    if mode == "restart":
        return child_restart()
    if mode == "lever_off":
        return child_lever_off()

    import shutil
    tmp = tempfile.mkdtemp(prefix="progstore_gate_")
    store_dir = os.path.join(tmp, "pstore")
    base = dict(os.environ)
    base["JAX_PLATFORMS"] = "cpu"
    # deterministic counting: no background lane, no jax-level
    # persistent cache (a cache-loaded executable does not survive
    # serialize→deserialize, so nothing would land in the store)
    base["YDB_TPU_COMPILE_AHEAD"] = "0"
    for k in ("JAX_COMPILATION_CACHE_DIR", "YDB_TPU_PROGSTATS",
              "YDB_TPU_PROGSTORE_DEVICE"):
        base.pop(k, None)
    me = os.path.abspath(__file__)
    out = {"ok": False, "store_dir": store_dir}
    try:
        env = {**base, "PROGSTORE_GATE_CHILD": "warm",
               "YDB_TPU_PROGSTORE": store_dir}
        rw = subprocess.run([sys.executable, me], env=env,
                            capture_output=True, timeout=900)
        warm = _last_json(rw.stdout)
        out["warm"] = warm
        out["warm_killed"] = rw.returncode == -signal.SIGKILL
        if not (warm and warm.get("ok") and out["warm_killed"]):
            sys.stderr.write(rw.stderr.decode(errors="replace")[-2000:])
            print(json.dumps(out), flush=True)
            return 1

        env = {**base, "PROGSTORE_GATE_CHILD": "restart",
               "YDB_TPU_PROGSTORE": store_dir,
               "PROGSTORE_GATE_WARM": json.dumps(warm)}
        rr = subprocess.run([sys.executable, me], env=env,
                            capture_output=True, timeout=900)
        out["restart"] = _last_json(rr.stdout)
        if rr.returncode != 0:
            sys.stderr.write(rr.stderr.decode(errors="replace")[-2000:])

        env = {**base, "PROGSTORE_GATE_CHILD": "lever_off",
               "YDB_TPU_PROGSTORE": "0",
               "PROGSTORE_GATE_WARM": json.dumps(warm)}
        rl = subprocess.run([sys.executable, me], env=env,
                            capture_output=True, timeout=900)
        out["lever_off"] = _last_json(rl.stdout)
        if rl.returncode != 0:
            sys.stderr.write(rl.stderr.decode(errors="replace")[-2000:])

        out["ok"] = bool(rr.returncode == 0 and rl.returncode == 0)
        print(json.dumps(out), flush=True)
        return 0 if out["ok"] else 1
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
