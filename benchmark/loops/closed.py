"""Loop `closed`: every stream sends its next statement only when the last
one has answered. At `seconds` the streams stop issuing; statements in
flight finish and count; the window ends at the last completion."""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass


@dataclass
class Sample:
    stream: int
    item: object
    sql: str
    t_send: float
    t_done: float
    answer: tuple | None = None      # (column names, rows of text cells)
    error: str | None = None
    call: object = None              # the engine call it caused (proxy.match)
    failed: bool = False             # set by compare.judge

    @property
    def latency_ms(self) -> float:
        return (self.t_done - self.t_send) * 1e3


def run(clients: list, plans: list, seconds: float, mix: dict) -> tuple[float, float, list]:
    """clients[i] drives plans[i] round after round. -> (t_start, t_end,
    samples); t_end is the last completion."""
    samples: list = []
    mu = threading.Lock()
    go = threading.Event()
    t_start_box: list = []

    def stream(i: int):
        client, plan = clients[i], plans[i]
        go.wait()
        deadline = t_start_box[0] + seconds
        k = 0
        while time.perf_counter() < deadline:
            item = plan[k % len(plan)]
            k += 1
            s = Sample(i, item, item.sql, time.perf_counter(), 0.0)
            try:
                cols, rows, _tag = client.query(item.sql)
                s.answer = (cols, rows)
            except Exception as e:             # noqa: BLE001 — counted
                s.error = f"{type(e).__name__}: {e}"
                lost = isinstance(e, (OSError, EOFError))
            s.t_done = time.perf_counter()
            with mu:
                samples.append(s)
            if s.error is not None and lost:
                break              # timed out or closed: out of step

    threads = [threading.Thread(target=stream, args=(i,), daemon=True)
               for i in range(len(clients))]
    for t in threads:
        t.start()
    t_start_box.append(time.perf_counter())
    go.set()
    for t in threads:
        t.join()
    t_end = max((s.t_done for s in samples), default=time.perf_counter())
    return t_start_box[0], t_end, samples
