"""Raw PostgreSQL protocol-v3 client, simple-query flow only.

Copied from `tests/test_pgwire.py::PgClient` (PR 25): the image has no
client library, and speaking the documented framing directly also keeps
the client's cost small and fixed. Touches a socket and nothing else.
"""

from __future__ import annotations

import socket
import struct


class PgError(RuntimeError):
    """The server answered with an error; the connection stays usable."""


class PgClient:
    def __init__(self, port: int, timeout: float = 120.0):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=10)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.f = self.sock.makefile("rb")
        params = b"user\0bench\0database\0ydb\0\0"
        body = struct.pack("!I", 196608) + params
        self.sock.sendall(struct.pack("!I", len(body) + 4) + body)
        self._drain_until_ready()
        self.sock.settimeout(timeout)

    def _read_msg(self):
        tag = self.f.read(1)
        if not tag:
            raise ConnectionError("connection closed by the server")
        (length,) = struct.unpack("!I", self.f.read(4))
        return tag, self.f.read(length - 4)

    def _drain_until_ready(self):
        msgs = []
        while True:
            tag, payload = self._read_msg()
            if tag == b"Z":
                return msgs
            msgs.append((tag, payload))

    def query(self, sql: str):
        """-> (column names, rows of text cells or None, command tag)."""
        body = sql.encode() + b"\0"
        self.sock.sendall(b"Q" + struct.pack("!I", len(body) + 4) + body)
        cols, rows, tag, err = [], [], None, None
        for t, payload in self._drain_until_ready():
            if t == b"T":
                (n,) = struct.unpack("!H", payload[:2])
                off = 2
                for _ in range(n):
                    end = payload.index(b"\0", off)
                    cols.append(payload[off:end].decode())
                    off = end + 1 + 18
            elif t == b"D":
                (n,) = struct.unpack("!H", payload[:2])
                off = 2
                row = []
                for _ in range(n):
                    (ln,) = struct.unpack("!i", payload[off:off + 4])
                    off += 4
                    if ln == -1:
                        row.append(None)
                    else:
                        row.append(payload[off:off + ln].decode())
                        off += ln
                rows.append(row)
            elif t == b"C":
                tag = payload.rstrip(b"\0").decode()
            elif t == b"E":
                err = payload
        if err is not None:
            fields = {chr(p[0]): p[1:].decode()
                      for p in err.split(b"\0") if p}
            raise PgError(fields.get("M", "pg error"))
        return cols, rows, tag

    def close(self):
        try:
            self.sock.sendall(b"X" + struct.pack("!I", 4))
        except OSError:
            pass
        self.f.close()
        self.sock.close()
