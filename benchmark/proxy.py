"""The engine as the front sees it, with a clock round every statement.

`serve_pg` is handed this in the engine's place. It forwards everything;
round `execute` it notes, on the server's own thread, the wall of the call
and the statement's `last_stats` (which the engine keeps per thread), and
writes an `eng.query` annotation into the profiler's trace so an idle gap
of the device can be named. This is the benchmark's own span round the
call into the engine; spans inside the program are the program's.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field


@dataclass
class EngineCall:
    sql: str
    t0: float
    t1: float
    error: str | None = None
    kind: str = ""
    path: str = ""
    parse_ms: float = 0.0
    plan_ms: float = 0.0
    phases: dict = field(default_factory=dict)
    used: bool = False


class EngineProxy:
    def __init__(self, engine):
        object.__setattr__(self, "_eng", engine)
        object.__setattr__(self, "calls", [])
        object.__setattr__(self, "_mu", threading.Lock())

    def __getattr__(self, name):
        return getattr(self._eng, name)

    def __setattr__(self, name, value):
        setattr(self._eng, name, value)

    def execute(self, sql, *args, **kwargs):
        from jax.profiler import TraceAnnotation
        call = EngineCall(sql=sql, t0=time.perf_counter(), t1=0.0)
        try:
            with TraceAnnotation("eng.query"):
                return self._eng.execute(sql, *args, **kwargs)
        except BaseException as e:             # noqa: BLE001 — re-raised
            call.error = f"{type(e).__name__}: {e}"
            raise
        finally:
            call.t1 = time.perf_counter()
            st = self._eng.last_stats
            if st is not None and call.error is None:
                call.kind = st.kind
                call.parse_ms, call.plan_ms = st.parse_ms, st.plan_ms
                call.phases = dict(st.phases or {})
                call.path = self._eng.executor.last_path
            with self._mu:
                self.calls.append(call)

    def take_calls(self) -> list:
        with self._mu:
            out = list(self.calls)
            self.calls.clear()
        return out


def match(samples, calls) -> None:
    """Give each client sample the engine call it caused: same text, inside
    the client's send..done interval (one clock, one process), used once."""
    by_sql: dict = {}
    for c in sorted(calls, key=lambda c: c.t0):
        by_sql.setdefault(c.sql, []).append(c)
    for s in sorted(samples, key=lambda s: s.t_send):
        for c in by_sql.get(s.sql, ()):
            if not c.used and c.t0 >= s.t_send and c.t1 <= s.t_done:
                c.used = True
                s.call = c
                break
