"""PostgreSQL wire-protocol front (v3, simple + extended query flow).

The reference serves the PG wire protocol next to gRPC
(`ydb/core/local_pgwire/`, `ydb/apps/pgwire` — startup/auth handshake,
simple `Q` queries, text-format result rows), so any psql-compatible
client can talk to it. Same here: a threaded TCP server translating the
v3 message flow onto the embedded engine.

Supported flow:
  * SSLRequest → 'N' (plaintext), StartupMessage → AuthenticationOk +
    ParameterStatus + BackendKeyData + ReadyForQuery
  * 'Q' (simple query) → RowDescription / DataRow* / CommandComplete /
    ReadyForQuery — text format, one statement per message
  * extended protocol: Parse ('P') with $n placeholders and optional
    param type oids, Bind ('B') with TEXT-format params (validated and
    inlined as typed literals — the proxy-style parameterization),
    Describe ('D'→ NoData; row descriptions ride Execute), Execute
    ('E'), Close ('C'), Sync ('S'), Flush ('H')
  * BEGIN/COMMIT/ROLLBACK ride the per-connection session, and the
    ReadyForQuery status byte tracks it ('I' idle / 'T' in tx)
  * 'X' terminate; errors → ErrorResponse (severity/code/message)
"""

from __future__ import annotations

import socket
import socketserver
import struct
import threading
import time
from contextlib import contextmanager, nullcontext

from ydb_tpu.utils.metrics import GLOBAL

_SSL_REQUEST = 80877103
_CANCEL_REQUEST = 80877102
_PROTO_V3 = 196608

# dtype kind -> (type oid, text encoder)
_PG_TEXT = "25"


def _date_str(days: int) -> str:
    import datetime
    return (datetime.date(1970, 1, 1)
            + datetime.timedelta(days=int(days))).isoformat()


def _oid_and_enc(kind: str):
    from ydb_tpu.core.dtypes import Kind
    k = Kind(kind)
    if k in (Kind.INT64, Kind.UINT64):
        return 20, str
    if k is Kind.INT32:
        return 23, str
    if k is Kind.FLOAT64:
        return 701, repr
    if k is Kind.BOOL:
        return 16, (lambda v: "t" if v else "f")
    if k is Kind.DATE32:
        return 1082, _date_str
    return 25, str                    # STRING and anything else: text


def _msg(tag: bytes, payload: bytes) -> bytes:
    return tag + struct.pack("!I", len(payload) + 4) + payload


_INT_OIDS = (20, 21, 23, 26)
_FLOAT_OIDS = (700, 701, 1700)


def _substitute_params(sql: str, params: list, oids: list) -> str:
    """Inline TEXT-format parameters as validated typed literals (the
    proxy-style parameterization: the engine's own planner re-binds them
    as runtime params where it can). Numerics are parsed — a malformed
    value raises instead of splicing into the SQL text."""
    import re

    def lit(m):
        i = int(m.group(1)) - 1
        if i < 0 or i >= len(params):
            raise ValueError(f"parameter ${i + 1} not bound")
        v = params[i]
        if v is None:
            return "NULL"
        oid = oids[i] if i < len(oids) else 0
        if oid in _INT_OIDS:
            return str(int(v))
        if oid in _FLOAT_OIDS:
            return repr(float(v))
        if oid == 16:
            lv = v.lower()
            if lv in ("t", "true", "1", "on", "y", "yes"):
                return "TRUE"
            if lv in ("f", "false", "0", "off", "n", "no"):
                return "FALSE"
            raise ValueError(f"bad boolean parameter {v!r}")
        if oid == 1082:
            if not re.fullmatch(r"\d{4}-\d{2}-\d{2}", v):
                raise ValueError(f"bad date parameter {v!r}")
            return f"date '{v}'"
        # unspecified type (oid 0/705, what psycopg sends for all text
        # params): inline as a STRING and let the binder's PG-style
        # coercion re-type it against the compared column's domain
        # (ADVICE r4 — sniffing digits into numbers here silently broke
        # string-column comparisons like name = '123')
        s = v.replace("'", "''")
        return f"'{s}'"

    # quote-aware scan: $n inside a '...' literal is literal text, not a
    # placeholder (re.sub over the whole text would rewrite it)
    out = []
    i, n = 0, len(sql)
    in_str = False
    while i < n:
        ch = sql[i]
        if in_str:
            out.append(ch)
            if ch == "'":
                if i + 1 < n and sql[i + 1] == "'":
                    out.append("'")
                    i += 1
                else:
                    in_str = False
            i += 1
            continue
        if ch == "'":
            in_str = True
            out.append(ch)
            i += 1
            continue
        if ch == "$":
            m = re.match(r"\$(\d+)", sql[i:])
            if m:
                out.append(lit(m))
                i += m.end()
                continue
        out.append(ch)
        i += 1
    return "".join(out)


def _cstr(s: str) -> bytes:
    return s.encode() + b"\0"


def _error(message: str, code: str = "XX000") -> bytes:
    payload = b"S" + _cstr("ERROR") + b"C" + _cstr(code) \
        + b"M" + _cstr(message) + b"\0"
    return _msg(b"E", payload)


def _ready(status: bytes) -> bytes:
    return _msg(b"Z", status)


class _Handler(socketserver.BaseRequestHandler):
    def handle(self):  # noqa: C901 — one protocol loop
        sock: socket.socket = self.request
        srv: "PgServer" = self.server.owner   # type: ignore[attr-defined]
        f = sock.makefile("rb")

        def read_exact(n):
            data = f.read(n)
            if data is None or len(data) < n:
                raise ConnectionError
            return data

        try:
            # startup (possibly preceded by an SSLRequest)
            while True:
                (length,) = struct.unpack("!I", read_exact(4))
                body = read_exact(length - 4)
                (proto,) = struct.unpack("!I", body[:4])
                if proto == _SSL_REQUEST:
                    sock.sendall(b"N")
                    continue
                if proto == _CANCEL_REQUEST:
                    return
                if proto != _PROTO_V3:
                    sock.sendall(_error(f"unsupported protocol {proto}"))
                    return
                break
            out = _msg(b"R", struct.pack("!I", 0))          # AuthenticationOk
            for k, v in (("server_version", "15.0 (ydb-tpu)"),
                         ("server_encoding", "UTF8"),
                         ("client_encoding", "UTF8"),
                         ("integer_datetimes", "on")):
                out += _msg(b"S", _cstr(k) + _cstr(v))
            out += _msg(b"K", struct.pack("!II", 0, 0))     # BackendKeyData
            out += _ready(b"I")
            sock.sendall(out)

            session = srv.engine.session()
            self._aborted = False      # PG aborted-transaction state
            self._sent_rows = False    # the last reply built held rows
            self._stmts: dict = {}     # name -> (sql, [oid])
            self._portals: dict = {}   # name -> bound sql
            pending = b""              # extended-flow replies batch to Sync
            skip = False               # error → ignore until Sync (v3 rule)

            def step(reply: bytes) -> bytes:
                nonlocal skip
                if reply[:1] == b"E":
                    skip = True
                return reply

            while True:
                tag = f.read(1)
                if not tag or tag == b"X":
                    return
                (length,) = struct.unpack("!I", read_exact(4))
                payload = read_exact(length - 4)
                if tag == b"Q":
                    sql = payload.rstrip(b"\0").decode()
                    self._sent_rows = False
                    self._flush(srv, sock, self._run(srv, session, sql))
                elif tag == b"S":                       # Sync
                    if session.tx is None:
                        # portals survive Sync inside a tx block (spec)
                        self._portals.clear()
                    skip = False
                    sock.sendall(pending
                                 + _ready(self._status(session)))
                    pending = b""
                elif skip and tag in (b"P", b"B", b"D", b"E", b"C",
                                      b"H"):
                    continue    # discard until Sync after an error
                elif tag == b"P":
                    pending += step(self._parse_msg(payload))
                elif tag == b"B":
                    pending += step(self._bind_msg(payload))
                elif tag == b"D":
                    pending += step(self._describe_msg(srv, session,
                                                       payload))
                elif tag == b"E":
                    pending += step(self._execute_msg(srv, session,
                                                      payload))
                elif tag == b"C":
                    kind, rest = payload[:1], payload[1:].rstrip(b"\0")
                    store = self._stmts if kind == b"S" else self._portals
                    store.pop(rest.decode(), None)
                    pending += _msg(b"3", b"")          # CloseComplete
                elif tag == b"H":                       # Flush
                    sock.sendall(pending)
                    pending = b""
                else:
                    sock.sendall(_error(
                        f"message {tag.decode(errors='replace')!r} not "
                        "supported") + _ready(self._status(session)))
        except (ConnectionError, BrokenPipeError, struct.error):
            pass
        finally:
            sock.close()

    def _parse_msg(self, payload: bytes) -> bytes:
        try:
            z1 = payload.index(b"\0")
            name = payload[:z1].decode()
            z2 = payload.index(b"\0", z1 + 1)
            sql = payload[z1 + 1:z2].decode()
            off = z2 + 1
            (noids,) = struct.unpack_from("!H", payload, off)
            off += 2
            oids = list(struct.unpack_from(f"!{noids}I", payload, off)) \
                if noids else []
            self._stmts[name] = (sql, oids)
            return _msg(b"1", b"")                      # ParseComplete
        except (ValueError, struct.error) as e:
            return _error(f"malformed Parse: {e}", code="08P01")

    def _bind_msg(self, payload: bytes) -> bytes:
        try:
            z1 = payload.index(b"\0")
            portal = payload[:z1].decode()
            z2 = payload.index(b"\0", z1 + 1)
            stmt_name = payload[z1 + 1:z2].decode()
            off = z2 + 1
            (nfmt,) = struct.unpack_from("!H", payload, off)
            off += 2
            fmts = list(struct.unpack_from(f"!{nfmt}H", payload, off))
            off += 2 * nfmt
            (nparams,) = struct.unpack_from("!H", payload, off)
            off += 2
            params = []
            for i in range(nparams):
                (plen,) = struct.unpack_from("!i", payload, off)
                off += 4
                if plen < 0:
                    params.append(None)
                else:
                    fmt = fmts[i] if i < len(fmts) \
                        else (fmts[0] if len(fmts) == 1 else 0)
                    if fmt != 0:
                        return _error("binary-format parameters are not "
                                      "supported (send text format)")
                    params.append(payload[off:off + plen].decode())
                    off += plen
            if stmt_name not in self._stmts:
                return _error(f"unknown prepared statement "
                              f"{stmt_name!r}", code="26000")
            sql, oids = self._stmts[stmt_name]
            self._portals[portal] = {
                "sql": _substitute_params(sql, params, oids)}
            return _msg(b"2", b"")                      # BindComplete
        except (ValueError, struct.error) as e:
            return _error(f"malformed Bind: {e}", code="08P01")

    _READ_KINDS = ("select", "setop", "explain")

    def _describe_msg(self, srv, session, payload: bytes) -> bytes:
        """Describe, per the v3 spec: the ROW DESCRIPTION belongs here,
        not on Execute (ADVICE r4 — JDBC/psycopg decode result sets off
        the Describe reply). Portal variant: read statements run NOW
        (execute-on-describe — output schemas need the bound plan) and
        the cached result rides the following Execute as DataRows only;
        non-reads answer NoData without executing (Describe must never
        mutate). Statement variant: ParameterDescription + NoData (the
        SQL still holds unbound $n placeholders)."""
        kind, rest = payload[:1], payload[1:].rstrip(b"\0")
        if kind == b"S":
            ent = self._stmts.get(rest.decode())
            if ent is None:
                return _error(f"unknown prepared statement "
                              f"{rest.decode()!r}", code="26000")
            _sql, oids = ent
            body = struct.pack("!H", len(oids))
            for o in oids:
                body += struct.pack("!I", o)
            return _msg(b"t", body) + _msg(b"n", b"")
        portal = self._portals.get(rest.decode())
        if portal is None:
            return _error(f"unknown portal {rest.decode()!r}", code="34000")
        first = portal["sql"].strip().split(None, 1)
        head = first[0].lower().rstrip(";") if first else ""
        if head not in ("select", "with", "values", "explain") \
                or self._aborted:
            return _msg(b"n", b"")
        try:
            # remember the commit epoch: a write landing between Describe
            # and Execute (same batch) invalidates this pre-computed
            # result — Execute re-runs instead of replaying stale rows
            portal["epoch"] = srv.engine.coordinator.last_plan_step
            block = srv.engine.execute(portal["sql"], session=session)
            kind2 = srv.engine.last_stats.kind
            if kind2 not in self._READ_KINDS:
                # executed but not row-producing: remember the completion
                # tag so the following Execute does NOT run it again
                n = getattr(srv.engine, "last_rows_affected", 0)
                portal["done_tag"] = {
                    "insert": f"INSERT 0 {n}", "update": f"UPDATE {n}",
                    "delete": f"DELETE {n}",
                    **self._DDL_TAGS}.get(kind2, kind2.upper())
                return _msg(b"n", b"")
            portal["result"] = block
            return self._row_desc(block)
        except Exception as e:           # noqa: BLE001 — wire boundary
            if session.tx is not None:
                self._aborted = True
            return _error(f"{type(e).__name__}: {e}")

    def _execute_msg(self, srv, session, payload: bytes) -> bytes:
        try:
            z1 = payload.index(b"\0")
            portal_name = payload[:z1].decode()
        except ValueError:
            return _error("malformed Execute", code="08P01")
        portal = self._portals.get(portal_name)
        if portal is None:
            return _error(f"unknown portal {portal_name!r}", code="34000")
        if self._aborted:
            # a statement failed inside the tx AFTER this portal was
            # described: its cached result must not leak past the
            # aborted-transaction barrier. Drop the caches and let _run
            # apply the 25P02 rule (which still honors ROLLBACK/COMMIT).
            portal.pop("result", None)
            portal.pop("done_tag", None)
        done = portal.pop("done_tag", None)
        if done is not None:
            portal["consumed"] = True
            return _msg(b"C", _cstr(done))
        block = portal.pop("result", None)
        if block is not None \
                and portal.get("epoch") != srv.engine.coordinator.last_plan_step:
            # a write landed since Describe: the client already holds the
            # RowDescription, so re-run and emit DataRows only (a second
            # 'T' inside Execute would desync v3 clients)
            try:
                block = srv.engine.execute(portal["sql"], session=session)
            except Exception as e:           # noqa: BLE001 — wire boundary
                if session.tx is not None:
                    self._aborted = True
                return _error(f"{type(e).__name__}: {e}")
        if block is not None:
            # described portal: the result was produced at Describe time;
            # Execute emits DataRows + CommandComplete only (spec shape)
            portal["consumed"] = True
            return self._data_rows(block) \
                + _msg(b"C", _cstr(f"SELECT {block.length}"))
        if portal.get("consumed"):
            # re-Execute of a completed portal: the stream is exhausted
            # (spec: portals run once) — no re-execution, no second 'T'
            return _msg(b"C", _cstr("SELECT 0"))
        # reuse the simple-query runner minus its trailing ReadyForQuery
        # (extended flow defers that to Sync)
        out = self._run(srv, session, portal["sql"])
        z = _ready(self._status(session))
        return out[:-len(z)] if out.endswith(z) else out

    def _status(self, session) -> bytes:
        if session.tx is None:
            return b"I"
        return b"E" if self._aborted else b"T"

    _DDL_TAGS = {"createtable": "CREATE TABLE", "droptable": "DROP TABLE",
                 "altertable": "ALTER TABLE", "createindex": "CREATE INDEX",
                 "dropindex": "DROP INDEX",
                 "creatematerializedview": "CREATE MATERIALIZED VIEW",
                 "dropmaterializedview": "DROP MATERIALIZED VIEW"}

    def _run(self, srv, session, sql: str) -> bytes:
        if not sql.strip():
            return _msg(b"I", b"") + _ready(self._status(session))
        # PG aborted-transaction rule: after an error inside an explicit
        # tx, everything except ROLLBACK is rejected, and COMMIT rolls
        # back (answering ROLLBACK) — partial data must not persist
        first = sql.strip().split(None, 1)[0].lower().rstrip(";")
        if self._aborted:
            if first in ("rollback", "commit"):
                try:
                    srv.engine.execute("rollback", session=session,
                                       _internal=True)
                except Exception:            # noqa: BLE001
                    pass
                self._aborted = False
                return _msg(b"C", _cstr("ROLLBACK")) \
                    + _ready(self._status(session))
            return _error("current transaction is aborted, commands "
                          "ignored until end of transaction block",
                          code="25P02") + _ready(self._status(session))
        # no front-side lock: the engine serializes its own write path
        # internally and SELECTs run concurrently over MVCC snapshots;
        # last_stats / last_rows_affected are thread-local to this handler
        try:
            block = srv.engine.execute(sql, session=session)
            kind = srv.engine.last_stats.kind
            if kind in ("select", "setop", "explain"):
                return self._rows(srv, block) \
                    + _ready(self._status(session))
            n = getattr(srv.engine, "last_rows_affected", 0)
        except Exception as e:               # noqa: BLE001 — wire boundary
            if session.tx is not None:
                self._aborted = True
            return _error(f"{type(e).__name__}: {e}") \
                + _ready(self._status(session))
        tag = {"insert": f"INSERT 0 {n}",
               "update": f"UPDATE {n}",
               "delete": f"DELETE {n}",
               "begin": "BEGIN", "commit": "COMMIT",
               "rollback": "ROLLBACK",
               **self._DDL_TAGS}.get(kind, kind.upper())
        return _msg(b"C", _cstr(tag)) + _ready(self._status(session))

    @staticmethod
    def _row_desc(block) -> bytes:
        """RowDescription ('T') for a result block."""
        desc = struct.pack("!H", len(block.schema.columns))
        for c in block.schema.columns:
            oid, _enc = _oid_and_enc(c.dtype.kind.value)
            desc += _cstr(c.name) + struct.pack("!IHIhih", 0, 0, oid, -1,
                                                -1, 0)
        return _msg(b"T", desc)

    @staticmethod
    def _data_rows(block) -> bytes:
        """DataRow ('D') stream, serialized straight from the column
        arrays — no pandas on this thread (pyarrow-backed DataFrame
        construction is not safe off the main thread in this image)."""
        encs, series = [], []
        for c in block.schema.columns:
            _oid, enc = _oid_and_enc(c.dtype.kind.value)
            encs.append(enc)
            cd = block.columns[c.name]
            if c.dtype.is_string and cd.dictionary is not None:
                vals = cd.dictionary.decode(cd.data)
            else:
                vals = cd.data
            series.append((vals, cd.valid))
        chunks = []                      # list + join: linear, not O(n^2)
        ncols_hdr = struct.pack("!H", len(series))
        null_cell = struct.pack("!i", -1)
        for i in range(block.length):
            body = [ncols_hdr]
            for (vals, valid), enc in zip(series, encs):
                v = vals[i]
                if v is None or (valid is not None and not valid[i]) \
                        or (isinstance(v, float) and v != v):
                    body.append(null_cell)
                else:
                    if hasattr(v, "item"):
                        v = v.item()
                    text = enc(v).encode()
                    body.append(struct.pack("!I", len(text)) + text)
            chunks.append(_msg(b"D", b"".join(body)))
        return b"".join(chunks)

    def _rows(self, srv, block) -> bytes:
        """Simple-query result: RowDescription + DataRows + tag, counted
        where the work is done (`front/pg/*`)."""
        with _encoding(srv):
            out = self._row_desc(block) + self._data_rows(block) \
                + _msg(b"C", _cstr(f"SELECT {block.length}"))
        GLOBAL.inc("front/pg/statements")
        GLOBAL.inc("front/pg/rows", block.length)
        GLOBAL.inc("front/pg/bytes", len(out))
        self._sent_rows = True
        return out

    def _flush(self, srv, sock, reply: bytes) -> None:
        """Put a simple query's answer on the wire; the flush of one
        that holds rows is the last part of `front/pg/encode_ms`."""
        with _encoding(srv) if self._sent_rows else nullcontext():
            sock.sendall(reply)


@contextmanager
def _encoding(srv):
    """The front's own work on an answer with rows: `front/pg/encode_ms`
    and a `pg-encode` annotation in the profiler's trace."""
    t0 = time.perf_counter()
    with srv.engine.tracer.annotate("pg-encode"):
        yield
    GLOBAL.inc("front/pg/encode_ms", (time.perf_counter() - t0) * 1000.0)


class PgServer:
    """Threaded pgwire listener over an embedded engine."""

    def __init__(self, engine, port: int = 0, host: str = "127.0.0.1"):
        self.engine = engine

        class _TCP(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True
        self._tcp = _TCP((host, port), _Handler)
        self._tcp.owner = self            # type: ignore[attr-defined]
        self.port = self._tcp.server_address[1]
        self._thread = threading.Thread(target=self._tcp.serve_forever,
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._tcp.shutdown()
        self._tcp.server_close()


def serve_pg(engine, port: int = 0) -> PgServer:
    return PgServer(engine, port)
