"""From the profiler's `.xplane.pb` to device busy time, the operations
that took most of it, and the idle gaps named by what the host was doing.

Read with `jax.profiler.ProfileData` and nothing else. Checked on the
small trace recorded on the chip that lies in `tests/data/`
(`tests/test_trace_reduce.py`).

What a TPU trace holds (looked at by hand, PR 25): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Ops` has one event per executed HLO
operation (a `while` spans the operations of its body, so events nest);
and `/host:CPU`, one line per thread, with the `TraceAnnotation`s of the
program (`device-dispatch`, `device-execute`, `readout-transfer`,
`join-builds`, `superblock-upload`) and of the harness (`window`,
`client.query`, `eng.query`) on the same clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW = "window"
CLIENT = "client.query"
ENGINE = "eng.query"
# the program's own scopes (`query/executor.py` `_xla_scope`), innermost first
PROGRAM_SCOPES = ("join-builds", "superblock-upload", "device-dispatch",
                  "device-dispatch-batched", "device-execute",
                  "readout-transfer")
SAMPLES_PER_GAP = 20
LONGEST_GAPS = 400          # the rest are summed unnamed


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _merge(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _self_times(events: list) -> dict:
    """{name: seconds not covered by a nested event}; events (start, end,
    name) of one line, where a later, shorter event inside an earlier one
    is its child."""
    total: dict = {}
    stack: list = []                 # [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, self_ns = stack.pop()
            total[name] = total.get(name, 0.0) + self_ns

    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close(a)
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        stack.append([b, name, b - a])
    close(float("inf"))
    return {k: v / 1e9 for k, v in total.items()}


_OPCODE = re.compile(r"[\]})] ([a-z][\w\-]*)\(")
_KIND = re.compile(r"kind=k(\w+)")


def short_op(text: str) -> str:
    """An HLO operation's event name is its whole text; keep its name, its
    opcode and a fusion's kind: `fusion.3 fusion:Custom`."""
    name, _, rest = text.partition(" = ")
    if not rest:
        return text[:80]
    op = _OPCODE.search(" " + rest)
    kind = _KIND.search(rest)
    out = name.lstrip("%") + (" " + op.group(1) if op else "")
    return (out + (":" + kind.group(1) if kind else ""))[:80]


def _module_of(modules: list, t: float) -> str:
    """`jit_fn(14729839869830399634)` -> `jit_fn(..9634)`: which program."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i < 0 or modules[i][1] < t:
        return "?"
    name = modules[i][2]
    head, _, tail = name.partition("(")
    return f"{head}(..{tail.rstrip(')')[-4:]})" if tail else name


def _covering(spans: list, t: float):
    """Innermost (shortest) of `spans` [(a, b, name)] that covers t."""
    best = None
    for a, b, name in spans:
        if a <= t <= b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else None


def reduce_trace(xplane_path: str) -> dict:
    """-> {chips, busy_s (mean over chips), window_s, device_ops, idle_gaps}
    `busy_s` is None where the trace holds no device plane (a CPU run)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    device_events: dict = {}         # plane name -> [(start, end, name)]
    device_modules: dict = {}        # plane name -> sorted [(start, end, name)]
    host_lines: list = []            # [[(start, end, name)], ...] per thread
    known = set(PROGRAM_SCOPES) | {WINDOW, CLIENT, ENGINE}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    into = device_events if line.name == OPS_LINE \
                        else device_modules
                    into[plane.name] = sorted(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events if e.name in known]
                if spans:
                    host_lines.append(spans)

    window = [s for spans in host_lines for s in spans if s[2] == WINDOW]
    out = {"chips": len(device_events), "busy_s": None, "window_s": None,
           "device_ops": [], "idle_gaps": []}
    if window:
        w0, w1 = window[0][0], window[0][1]
        out["window_s"] = (w1 - w0) / 1e9
    if not device_events:
        return out

    busy, ops = [], {}
    for plane_name, events in device_events.items():
        merged = _merge([(a, b) for a, b, _n in events])
        busy.append(sum(b - a for a, b in merged) / 1e9)
        modules = device_modules.get(plane_name, [])
        named_events = [(a, b, f"{_module_of(modules, a)}/{short_op(n)}")
                        for a, b, n in events]
        for name, sec in _self_times(named_events).items():
            ops[name] = ops.get(name, 0.0) + sec / len(device_events)
    out["busy_s"] = sum(busy) / len(busy)
    out["device_ops"] = [[n, s] for n, s in sorted(
        ops.items(), key=lambda kv: -kv[1])[:10]]

    # idle gaps of the first chip inside the window, by what the host did
    first = device_events[sorted(device_events)[0]]
    merged = _merge([(a, b) for a, b, _n in first])
    if not window:
        w0, w1 = merged[0][0], merged[-1][1]
        out["window_s"] = (w1 - w0) / 1e9
    edges = [w0] + [x for a, b in merged for x in (a, b)
                    if w0 <= a and b <= w1] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    named: dict = {}
    gaps.sort(key=lambda g: g[0] - g[1])
    rest = sum(b - a for a, b in gaps[LONGEST_GAPS:]) / 1e9
    if rest:
        named["gaps-too-short-to-name"] = rest
    for a, b in gaps[:LONGEST_GAPS]:
        near = [[s for s in spans if s[2] != WINDOW and s[0] <= b and s[1] >= a]
                for spans in host_lines]
        step = (b - a) / SAMPLES_PER_GAP
        for k in range(SAMPLES_PER_GAP):
            t = a + (k + 0.5) * step
            labels, client = set(), False
            for spans in near:
                name = _covering(spans, t)
                if name == CLIENT:
                    client = True
                elif name is not None:
                    labels.add(name)
            label = "+".join(sorted(labels)) if labels else (
                "front" if client else "between-queries")
            named[label] = named.get(label, 0.0) + step / 1e9
    out["idle_gaps"] = [[n, s] for n, s in sorted(
        named.items(), key=lambda kv: -kv[1])[:10]]
    return out
