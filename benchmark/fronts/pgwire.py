"""Front `pgwire`: `serve_pg` on a thread over the engine handed in (the
way `cli.cmd_server` and `chip_smoke.served_phase` start it); clients
are raw protocol-v3 sockets."""

from __future__ import annotations

from pgclient import PgClient


class Front:
    def __init__(self, engine):
        from ydb_tpu.server.pgwire import serve_pg
        self._srv = serve_pg(engine, port=0)
        self.port = self._srv.port

    def connect(self, timeout: float = 120.0):
        """A client with `.query(sql) -> (cols, rows, tag)` and `.close()`."""
        return PgClient(self.port, timeout=timeout)

    def stop(self) -> None:
        self._srv.stop()


def start(engine) -> Front:
    return Front(engine)
