#!/usr/bin/env python3
"""Records the small trace `tests/data/tiny.xplane.pb` and prints what a
trace holds (planes, lines, the commonest event names).

    python3 benchmark/tests/record_trace.py record <out-dir>   # on the chip
    python3 benchmark/tests/record_trace.py show <file.xplane.pb>

`record` runs three tiny jitted programs inside the annotations the harness
and the program write (`window`, `client.query`, `eng.query`,
`device-dispatch`, `readout-transfer`), with a sleep between them so the
device has idle gaps of a known cause.
"""

from __future__ import annotations

import collections
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def record(out_dir: str) -> str:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileOptions, TraceAnnotation

    import trace_reduce
    f = jax.jit(lambda x: jnp.sort(x @ x, axis=-1).sum())
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    log = tempfile.mkdtemp(prefix="tiny-trace-")
    jax.profiler.start_trace(log, profiler_options=opts)
    with TraceAnnotation("window"):
        for _ in range(3):
            with TraceAnnotation("client.query"):
                time.sleep(0.002)                      # "front"
                with TraceAnnotation("eng.query"):
                    with TraceAnnotation("device-dispatch"):
                        y = f(x)
                    with TraceAnnotation("readout-transfer"):
                        y.block_until_ready()
                    time.sleep(0.004)                  # host work in the engine
            time.sleep(0.003)                          # between queries
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "tiny.xplane.pb")
    shutil.copy(trace_reduce.find_xplane(log), dst)
    shutil.rmtree(log, ignore_errors=True)
    return dst


def show(path: str) -> None:
    from jax.profiler import ProfileData
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            span = (min((e.start_ns for e in evs), default=0),
                    max((e.start_ns + e.duration_ns for e in evs), default=0))
            print(f"  LINE {line.name!r}: {len(evs)} events, span {span}, "
                  f"top {names.most_common(6)}")


if __name__ == "__main__":
    import trace_reduce
    target = record(sys.argv[2]) if sys.argv[1] == "record" else sys.argv[2]
    show(target)
    print(trace_reduce.reduce_trace(target))
