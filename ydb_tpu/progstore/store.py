"""Persistent compiled-program store — XLA AOT executables that
outlive the process.

The reference YDB runs a compile service so query programs survive
session churn; here the equivalent is a content-addressed directory.
Every fresh AOT capture (`utils/progstats.capture`) serializes its
`jax.stages.Compiled` via `jax.experimental.serialize_executable` and
writes it under `YDB_TPU_PROGSTORE=<dir>`; a restarted process (or a
failover adoptee pointed at the same data dir) consults the store
before compiling and dispatches the deserialized executable —
`prog/store_hits` with `compile_ms ~= 0`.

Layout (one directory, human-inspectable):

    <dir>/manifest.jsonl      append-only index, latest line per key
                              wins; `"obj": null` lines are tombstones
    <dir>/objects/<digest>.bin pickled {payload, in_tree, out_tree,
                              extra}; <digest> = blake2s of the bytes,
                              re-verified at every load

A manifest line carries the store FORMAT version, an environment
fingerprint (jax + jaxlib versions — a serialized executable is not
portable across XLA revisions) and a device fingerprint (platform +
device kind + device count). The failure ladder is deliberate:

  * unknown key                → `prog/store_misses`, plain cold miss
  * format/env version skew,
    bad checksum, unpicklable,
    undeserializable           → `prog/store_corrupt`: the object is
                                 DELETED from disk, a tombstone is
                                 appended, and the caller sees a cold
                                 miss — never a crash, never a
                                 wrong-program dispatch
  * device fingerprint
    mismatch                   → `prog/store_refused`: the entry is
                                 refused but KEPT (a data dir copied
                                 from a CPU warmer is still valid back
                                 on CPU); the caller compiles fresh
  * any I/O error              → `prog/store_errors`, treated as miss

Cache keys are big tuples of fingerprints, signatures, frozensets and
numpy dtypes whose `repr` is not stable across processes (hash
randomization reorders set/dict iteration), so the store key is a
blake2s digest of a CANONICAL encoding (`canon_bytes`) that sorts
unordered collections and normalizes numpy/enum scalars.

`YDB_TPU_PROGSTORE` unset/empty/`0` disables everything: no directory
is created, no files are written, loads return None — byte-equal to
the pre-store engine. `YDB_TPU_PROGSTORE_DEVICE` overrides the device
fingerprint (the fault-injection hook the mismatch regression test
uses to simulate a foreign-backend store).
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import pickle
import threading
import time

import numpy as np

from ydb_tpu.utils.metrics import GLOBAL

# bump whenever the object body layout or the manifest schema changes —
# old entries then read as version skew and are evicted as corrupt
FORMAT_VERSION = 2          # 2: the object body names its execution devices

_MU = threading.Lock()
_STORES: dict = {}                     # guarded-by: _MU — root -> ProgramStore


def store_dir():
    """The `YDB_TPU_PROGSTORE` lever: a directory path enables the
    store, unset/empty/`0` disables it (no files, byte-equal)."""
    raw = os.environ.get("YDB_TPU_PROGSTORE", "").strip()
    if raw in ("", "0"):
        return None
    return raw


def enabled() -> bool:
    return store_dir() is not None


def env_fingerprint() -> str:
    """jax + jaxlib versions: the XLA revision pair a serialized
    executable is pinned to."""
    import jax
    import jaxlib
    return f"jax={jax.__version__};jaxlib={jaxlib.__version__}"


def device_fingerprint() -> str:
    """platform : device kind : local device count — what the
    executable was compiled FOR. `YDB_TPU_PROGSTORE_DEVICE` overrides
    (test hook for the copied-data-dir mismatch guard)."""
    spoof = os.environ.get("YDB_TPU_PROGSTORE_DEVICE", "").strip()
    if spoof:
        return spoof
    try:
        import jax
        devs = jax.local_devices()
        kind = str(getattr(devs[0], "device_kind", "unknown"))
        return f"{jax.default_backend()}:{kind}:{len(devs)}"
    except Exception:                  # noqa: BLE001 — fingerprint only
        return "unknown:unknown:0"


def _devices_by_id(ids):
    """The local devices an executable was compiled for. Without them
    jax loads a deserialized executable across EVERY local device, and a
    single-device program then fails at dispatch on a multi-device host
    ("expected N shards")."""
    import jax
    by_id = {d.id: d for d in jax.local_devices()}
    return [by_id[i] for i in ids]


# --------------------------------------------------------------------------
# canonical key encoding
# --------------------------------------------------------------------------


def _canon(x, out: list) -> None:
    """Append a canonical token stream for `x`. Unordered collections
    are sorted by their own canonical encoding; numpy scalars/dtypes
    and enums normalize to stable primitives; anything unknown falls
    back to repr (cache keys in this repo are built from canonical
    primitives, so the fallback is a safety net, not a path)."""
    if isinstance(x, bool) or x is None:
        out.append(f"b:{x};")
    elif isinstance(x, int):
        out.append(f"i:{x};")
    elif isinstance(x, float):
        out.append(f"f:{x!r};")
    elif isinstance(x, str):
        out.append(f"s:{len(x)}:{x};")
    elif isinstance(x, bytes):
        out.append(f"y:{x.hex()};")
    elif isinstance(x, (tuple, list)):
        out.append(f"t:{len(x)}[")
        for item in x:
            _canon(item, out)
        out.append("]")
    elif isinstance(x, (set, frozenset)):
        parts = []
        for item in x:
            sub: list = []
            _canon(item, sub)
            parts.append("".join(sub))
        out.append(f"u:{len(x)}[" + "".join(sorted(parts)) + "]")
    elif isinstance(x, dict):
        items = []
        for k, v in x.items():
            sub = []
            _canon(k, sub)
            _canon(v, sub)
            items.append("".join(sub))
        out.append(f"d:{len(x)}[" + "".join(sorted(items)) + "]")
    elif isinstance(x, np.dtype):
        out.append(f"n:{x.str};")
    elif isinstance(x, np.generic):
        _canon(x.item(), out)
    elif hasattr(x, "value") and hasattr(type(x), "__members__"):
        # Enum member: class name + value, import-order independent
        out.append(f"e:{type(x).__name__}:{x.value!r};")
    else:
        out.append(f"r:{x!r};")


def canon_bytes(key) -> bytes:
    out: list = []
    _canon(key, out)
    return "".join(out).encode()


def key_digest(kind: str, key) -> str:
    h = hashlib.blake2s(digest_size=16)
    h.update(kind.encode())
    h.update(b"\x00")
    h.update(canon_bytes(key))
    return h.hexdigest()


def _body_digest(body: bytes) -> str:
    return hashlib.blake2s(body, digest_size=16).hexdigest()


# --------------------------------------------------------------------------
# the store proper
# --------------------------------------------------------------------------


class ProgramStore:
    """One on-disk store rooted at `root`. The manifest is read once at
    open and maintained in memory; writes append (manifest lines are
    one JSON object per line, latest per key wins). Thread-safe; the
    sequential-process restart story (gate: run, kill -9, rerun) needs
    no cross-process locking because objects are content-addressed and
    the manifest is append-only."""

    def __init__(self, root: str):
        self.root = root
        self._mu = threading.Lock()
        self._index: dict = {}         # key digest -> manifest line dict
        self._loads = 0
        self._saves = 0
        os.makedirs(os.path.join(root, "objects"), exist_ok=True)
        self._read_manifest()

    # -- manifest ----------------------------------------------------------

    def _manifest_path(self) -> str:
        return os.path.join(self.root, "manifest.jsonl")

    def _read_manifest(self) -> None:
        try:
            with open(self._manifest_path(), "r", encoding="utf-8") as f:
                for ln in f:
                    ln = ln.strip()
                    if not ln:
                        continue
                    try:
                        ent = json.loads(ln)
                    except ValueError:
                        continue       # torn tail line from a kill -9
                    k = ent.get("key")
                    if not k:
                        continue
                    if ent.get("obj") is None:
                        self._index.pop(k, None)   # tombstone
                    else:
                        self._index[k] = ent
        except FileNotFoundError:
            pass
        except OSError:
            GLOBAL.inc("prog/store_errors")

    def _append_manifest(self, ent: dict) -> None:
        line = json.dumps(ent, sort_keys=True) + "\n"
        with open(self._manifest_path(), "a", encoding="utf-8") as f:
            f.write(line)

    # -- corruption handling -----------------------------------------------

    def _evict_corrupt(self, kd: str, ent: dict) -> None:
        """Satellite contract: a corrupt/skewed entry is counted,
        DELETED from disk and tombstoned — the next process never
        retries it."""
        GLOBAL.inc("prog/store_corrupt")
        obj = ent.get("obj")
        with self._mu:
            self._index.pop(kd, None)
            try:
                if obj:
                    try:
                        os.unlink(self._obj_path(obj))
                    except FileNotFoundError:
                        pass
                self._append_manifest({"v": FORMAT_VERSION, "key": kd,
                                       "obj": None, "ts": time.time()})
            except OSError:
                GLOBAL.inc("prog/store_errors")

    def _obj_path(self, digest: str) -> str:
        return os.path.join(self.root, "objects", f"{digest}.bin")

    # -- load / save -------------------------------------------------------

    def load(self, kind: str, key):
        """Deserialize the stored executable for (kind, key), or None.

        Returns `{"compiled", "extra"}` on a hit. Every non-hit path is
        a counted cold miss for the caller; this method never raises."""
        kd = key_digest(kind, key)
        with self._mu:
            ent = self._index.get(kd)
        if ent is None:
            GLOBAL.inc("prog/store_misses")
            return None
        if ent.get("v") != FORMAT_VERSION or \
                ent.get("env") != env_fingerprint():
            self._evict_corrupt(kd, ent)           # version skew
            return None
        if ent.get("device") != device_fingerprint():
            # a foreign-backend store must not dispatch here — refuse
            # loudly but keep the entry (it is valid on ITS device)
            GLOBAL.inc("prog/store_refused")
            return None
        try:
            with open(self._obj_path(ent["obj"]), "rb") as f:
                body = f.read()
        except FileNotFoundError:
            self._evict_corrupt(kd, ent)
            return None
        except OSError:
            GLOBAL.inc("prog/store_errors")
            return None
        if _body_digest(body) != ent["obj"]:
            self._evict_corrupt(kd, ent)           # truncated / garbage
            return None
        try:
            rec = pickle.loads(body)
            from jax.experimental import serialize_executable
            compiled = serialize_executable.deserialize_and_load(
                rec["payload"], rec["in_tree"], rec["out_tree"],
                execution_devices=_devices_by_id(rec["devices"]))
        except Exception:              # noqa: BLE001 — corrupt payload
            self._evict_corrupt(kd, ent)
            return None
        GLOBAL.inc("prog/store_hits")
        with self._mu:
            self._loads += 1
        return {"compiled": compiled, "extra": rec.get("extra") or {}}

    def save(self, kind: str, key, compiled, extra=None) -> bool:
        """Serialize a freshly compiled executable. Idempotent per key
        (an entry already indexed for this env/device is kept); any
        failure counts `prog/store_errors` and is swallowed — a broken
        disk must not fail the query that just compiled fine."""
        kd = key_digest(kind, key)
        with self._mu:
            ent = self._index.get(kd)
        if ent is not None and ent.get("v") == FORMAT_VERSION and \
                ent.get("env") == env_fingerprint() and \
                ent.get("device") == device_fingerprint():
            return True
        try:
            from jax.experimental import serialize_executable
            payload, in_tree, out_tree = \
                serialize_executable.serialize(compiled)
            # round-trip validation BEFORE publishing: an executable
            # that XLA itself loaded from its compilation cache can
            # serialize to a payload with dangling symbol references
            # ("Symbols not found" at deserialize) — such a payload
            # must never reach the manifest, where every future restart
            # would evict it as corrupt and recompile anyway
            devices = compiled.runtime_executable().local_devices()
            serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree, execution_devices=devices)
            buf = io.BytesIO()
            pickle.dump({"payload": payload, "in_tree": in_tree,
                         "out_tree": out_tree, "extra": extra or {},
                         "devices": [d.id for d in devices]},
                        buf, protocol=pickle.HIGHEST_PROTOCOL)
            body = buf.getvalue()
            digest = _body_digest(body)
            path = self._obj_path(digest)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(body)
            os.replace(tmp, path)      # atomic: no torn objects
            line = {"v": FORMAT_VERSION, "key": kd, "obj": digest,
                    "kind": kind, "env": env_fingerprint(),
                    "device": device_fingerprint(), "ts": time.time()}
            with self._mu:
                self._append_manifest(line)
                self._index[kd] = line
                self._saves += 1
        except Exception:              # noqa: BLE001 — never fail the query
            GLOBAL.inc("prog/store_errors")
            return False
        GLOBAL.inc("prog/store_writes")
        return True

    # -- introspection -----------------------------------------------------

    def stats(self) -> dict:
        """The `.sys/progstore` / ProgStoreStats payload for THIS
        store: index size, on-disk bytes, process load/save activity,
        plus the global counters (cumulative across stores)."""
        with self._mu:
            entries = len(self._index)
            kinds: dict = {}
            for ent in self._index.values():
                k = ent.get("kind", "?")
                kinds[k] = kinds.get(k, 0) + 1
            loads, saves = self._loads, self._saves
        obj_bytes = 0
        obj_count = 0
        try:
            objdir = os.path.join(self.root, "objects")
            for name in os.listdir(objdir):
                if name.endswith(".bin"):
                    obj_count += 1
                    obj_bytes += os.path.getsize(os.path.join(objdir, name))
        except OSError:
            pass
        return {
            "root": self.root, "entries": entries, "objects": obj_count,
            "object_bytes": obj_bytes, "kinds": kinds,
            "loads": loads, "saves": saves,
            "env": env_fingerprint(), "device": device_fingerprint(),
            "hits": GLOBAL.get("prog/store_hits"),
            "misses": GLOBAL.get("prog/store_misses"),
            "writes": GLOBAL.get("prog/store_writes"),
            "corrupt": GLOBAL.get("prog/store_corrupt"),
            "refused": GLOBAL.get("prog/store_refused"),
            "errors": GLOBAL.get("prog/store_errors"),
        }


def get_store():
    """The process-wide store for the current `YDB_TPU_PROGSTORE`
    directory, or None when the lever is off. Instances are cached per
    root so tests flipping the env get fresh isolated stores."""
    root = store_dir()
    if root is None:
        return None
    root = os.path.abspath(root)
    with _MU:
        st = _STORES.get(root)
        if st is None:
            try:
                st = ProgramStore(root)
            except OSError:
                GLOBAL.inc("prog/store_errors")
                return None
            _STORES[root] = st
        return st


def stats():
    """Stats for the active store, or a disabled stub (the sysview and
    the RPC never fabricate a store that is not there)."""
    st = get_store()
    if st is None:
        return {"root": "", "entries": 0, "objects": 0, "object_bytes": 0,
                "kinds": {}, "loads": 0, "saves": 0,
                "env": env_fingerprint(), "device": device_fingerprint(),
                "hits": GLOBAL.get("prog/store_hits"),
                "misses": GLOBAL.get("prog/store_misses"),
                "writes": GLOBAL.get("prog/store_writes"),
                "corrupt": GLOBAL.get("prog/store_corrupt"),
                "refused": GLOBAL.get("prog/store_refused"),
                "errors": GLOBAL.get("prog/store_errors")}
    return st.stats()


def reset_for_tests() -> None:
    """Drop cached store instances (test isolation: a re-created tmp
    dir must re-read its manifest, not reuse a stale index)."""
    with _MU:
        _STORES.clear()
