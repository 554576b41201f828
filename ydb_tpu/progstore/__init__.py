"""Zero-compile serving: the persistent compiled-program subsystem.

Three lanes:

  store.py          on-disk content-addressed AOT executable store
                    (`YDB_TPU_PROGSTORE=<dir>`): a fresh compile is
                    serialized once and every later process with the
                    same cache key, jax/jaxlib version and device
                    fingerprint deserializes it instead of compiling —
                    `prog/store_hits` with `compile_ms ~= 0`.
  buckets.py        shape-bucketed polymorphism: scan source counts
                    quantize to a geometric ladder so a growing table
                    migrates between O(log n) program shapes.
  compile_ahead.py  the compile-ahead lane (`YDB_TPU_COMPILE_AHEAD`):
                    novel (key, bucket) pairs compile in the background
                    overlapped with the admission-queue wait, with
                    single-flight dedup so a client storm on a fresh
                    shape compiles once.

The store and the compile-ahead lane default as documented in their
modules and are byte-equal escape hatches when disabled:
`YDB_TPU_PROGSTORE=0` leaves no files and `YDB_TPU_COMPILE_AHEAD=0`
restores strictly synchronous compiles. Bucketing always runs.
"""

from ydb_tpu.progstore import buckets, compile_ahead, store  # noqa: F401
