"""Full-suite single-process gate — the executable-accumulation pin.

review weakness #3: before PR 6, running the WHOLE test suite (slow soaks
included) in one process accumulated compiled executables until the
process SEGFAULTed. PR 6's parameter-lifted program cache flattened the
exec cache; this gate REGRESSION-PINS that fix by running every test in
ONE pytest process and asserting (a) rc == 0 and (b) no segfault
signature anywhere in the output or the return code (-11/139 = SIGSEGV,
134 = SIGABRT).

Too slow for tier-1 (the soaks alone run minutes) — `scripts/ci.sh`
runs it on the nightly leg (CI_FULLSUITE=1). Prints one JSON line;
exit 0 = green.
"""

import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TIMEOUT_S = int(os.environ.get("FULLSUITE_TIMEOUT", "3600"))
CRASH_RCS = (-11, 139, -6, 134)         # SIGSEGV / SIGABRT spellings
CRASH_RE = re.compile(
    r"Segmentation fault|core dumped|Fatal Python error", re.I)


def main() -> int:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)
    argv = [sys.executable, "-m", "pytest", "tests/", "-q",
            "--continue-on-collection-errors", "-p", "no:cacheprovider",
            "-p", "no:xdist", "-p", "no:randomly"]
    t0 = time.monotonic()
    try:
        proc = subprocess.run(argv, env=env, cwd=REPO,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True,
                              timeout=TIMEOUT_S)
        rc, out = proc.returncode, proc.stdout
    except subprocess.TimeoutExpired as e:
        rc, out = 124, (e.stdout or b"").decode("utf-8", "replace") \
            if isinstance(e.stdout, bytes) else (e.stdout or "")
    dur = round(time.monotonic() - t0, 1)

    tail = out[-4000:]
    m = re.search(r"(\d+) passed", out)
    passed = int(m.group(1)) if m else 0
    m = re.search(r"(\d+) failed", out)
    failed = int(m.group(1)) if m else 0
    crashed = rc in CRASH_RCS or bool(CRASH_RE.search(out))
    gate = {
        "suite_green": rc == 0,
        "no_segfault": not crashed,
        "single_process": True,          # by construction (no xdist)
    }
    ok = all(gate.values())
    print(json.dumps({
        "metric": "fullsuite_gate", "ok": ok, "gate": gate, "rc": rc,
        "passed": passed, "failed": failed, "duration_s": dur,
        "tail": tail if not ok else "",
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
