"""Replication v1 — synchronous WAL/manifest shipping to a standby.

The first availability axis (round-4 review #7): the reference keeps data
alive through erasure/mirror blob groups and re-placement
(`blobstorage_grouptype.cpp`, DSProxy `base/blobstorage.h:884`, Hive
`hive_impl.h:158`); the v1 analog here is a MIRROR of the durable
store's mutation stream. Every Store write (WAL appends, manifest/json
replacements, portion blobs, compaction rewrites, drops) ships
SYNCHRONOUSLY to a standby before the write is acknowledged — a commit
the client saw is on both sides, so killing the primary loses nothing:
an engine booted from the standby root recovers to the last committed
plan step through the ordinary crash-recovery path (`storage/persist.py
load()` — the standby IS a crash image that happens to be remote).

Transports: `DirSink` mirrors into a local directory (tests, same-host
standby); `GrpcSink` ships to a `StandbyServer` in another process
(JSON ops, blob payloads base64 — the DCN seam). Apply is idempotent
(appends re-framed by record, json/blob replaces, missing-ok deletes).
"""

from __future__ import annotations

import base64
import json
import os
from typing import Optional

SERVICE = "ydb_tpu.Replica"


def apply_op(root: str, op: dict) -> None:
    """Apply one shipped mutation under the standby root."""
    from ydb_tpu.storage import blobfile as B
    from ydb_tpu.storage.persist import _atomic_json

    kind = op["op"]
    rel = op.get("path", "")
    if os.path.isabs(rel) or ".." in rel.split(os.sep):
        raise ValueError(f"bad replica path {rel!r}")
    path = os.path.join(root, rel)
    if kind in ("json", "wal_append", "wal_rewrite", "put_b64"):
        os.makedirs(os.path.dirname(path), exist_ok=True)
    if kind == "json":
        _atomic_json(path, op["data"])
    elif kind == "wal_append":
        B.wal_append(path, op["data"], sync=op.get("sync", True))
    elif kind == "wal_rewrite":
        B.wal_rewrite(path, op["data"])
    elif kind == "put_b64":
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(base64.b64decode(op["data"]))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    elif kind == "jsonl_append":
        # JSON-lines append (the 2PC decision-log mirror, cluster/dtx.py):
        # one fsynced line per shipped record. A re-shipped record after a
        # crash-before-ack duplicates a line; the dtx folds are per-gtx
        # last-record-wins, so duplicates are harmless.
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "ab") as f:
            f.write(json.dumps(op["data"]).encode() + b"\n")
            f.flush()
            os.fsync(f.fileno())
    elif kind == "unlink":
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass
    elif kind == "rmtree":
        import shutil
        shutil.rmtree(path, ignore_errors=True)
    else:
        raise ValueError(f"unknown replica op {kind!r}")


class DirSink:
    """Standby on a local directory (same-host mirror / tests)."""

    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)

    def ship(self, op: dict) -> None:
        apply_op(self.root, op)

    def has_catalog(self) -> bool:
        return os.path.exists(os.path.join(self.root, "catalog.json"))


class GrpcSink:
    """Standby in another process, over its Replica gRPC front."""

    def __init__(self, endpoint: str, token: str = ""):
        import grpc
        self.endpoint = endpoint
        self.token = token
        self._channel = grpc.insecure_channel(endpoint, options=[
            ("grpc.max_send_message_length", 256 << 20),
            ("grpc.max_receive_message_length", 256 << 20)])
        self._apply = self._channel.unary_unary(
            f"/{SERVICE}/Apply",
            request_serializer=lambda o: json.dumps(o).encode(),
            response_deserializer=lambda b: json.loads(b.decode()))

    def ship(self, op: dict) -> None:
        resp = self._apply({**op, "token": self.token})
        if "error" in resp:
            raise RuntimeError(f"replica apply failed: {resp['error']}")

    def has_catalog(self) -> bool:
        resp = self._apply({"op": "probe", "path": "catalog.json",
                            "token": self.token})
        if "error" in resp:
            raise RuntimeError(f"replica probe failed: {resp['error']}")
        return bool(resp.get("exists"))


class StandbyServer:
    """Receives the primary's mutation stream into a local root. Promote
    by booting `QueryEngine(data_dir=root)` — ordinary crash recovery."""

    def __init__(self, root: str, port: int = 0, token: str = ""):
        import hmac

        from concurrent import futures

        import grpc
        self.root = root
        os.makedirs(root, exist_ok=True)
        self.applied = 0
        tok = token

        def handle_apply(request, context):
            try:
                if tok and not hmac.compare_digest(
                        str(request.get("token", "")), tok):
                    return {"error": "Unauthenticated"}
                if request.get("op") == "probe":
                    rel = request.get("path", "")
                    if os.path.isabs(rel) or ".." in rel.split(os.sep):
                        return {"error": "bad probe path"}
                    return {"ok": True, "exists": os.path.exists(
                        os.path.join(self.root, rel))}
                apply_op(self.root, request)
                self.applied += 1
                return {"ok": True}
            except Exception as e:           # noqa: BLE001 — wire boundary
                return {"error": f"{type(e).__name__}: {e}"}

        handlers = {
            "Apply": grpc.unary_unary_rpc_method_handler(
                handle_apply,
                request_deserializer=lambda b: json.loads(b.decode()),
                response_serializer=lambda o: json.dumps(o).encode()),
        }
        self._server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=4),
            options=[("grpc.max_receive_message_length", 256 << 20)])
        self._server.add_generic_rpc_handlers(
            (grpc.method_handlers_generic_handler(SERVICE, handlers),))
        self.port = self._server.add_insecure_port(f"127.0.0.1:{port}")
        self._server.start()

    def stop(self) -> None:
        self._server.stop(grace=None)


def make_sink(replica) -> Optional[object]:
    """Engine-facing factory: sink object | 'host:port' | directory."""
    if replica is None or hasattr(replica, "ship"):
        return replica
    if isinstance(replica, str):
        if ":" in replica and not os.sep in replica:
            return GrpcSink(replica)
        return DirSink(replica)
    raise TypeError(f"bad replica target {replica!r}")
