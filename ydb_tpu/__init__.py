"""ydb_tpu — a TPU-native distributed SQL engine.

A from-scratch framework with the capability surface of YDB (reference:
waralex/ydb), redesigned TPU-first:

- the columnar execution substrate is a typed SSA-style op IR
  (``ydb_tpu.ops``) with a numpy oracle lowering and an XLA lowering
  (``jax.jit`` per program/shape-bucket) — the analog of the reference's
  ColumnShard SSA program (`ydb/core/protos/ssa.proto`) and MiniKQL block
  compute nodes (`ydb/library/yql/minikql/comp_nodes/mkql_block_*.cpp`);
- the storage layer is an embedded column store mirroring ColumnShard's
  InsertTable/portions/compaction model (`ydb/core/tx/columnshard/engines/`);
- distributed execution is a DQ-style stage/task/channel graph
  (`ydb/library/yql/dq/`) whose hash shuffles lower to XLA collectives over
  a `jax.sharding.Mesh` instead of Interconnect TCP channels.

Numeric policy: f64/i64 are first-class (TPU emulates f64 with adequate
precision for SQL aggregate semantics); therefore jax x64 mode is enabled
at package import.
"""

import os as _os

import jax as _jax

_jax.config.update("jax_enable_x64", True)

# Persistent compilation cache: SQL engines compile one executable per
# (program, shape-bucket) and re-create the same shapes across processes
# (server restarts, CLI runs, benchmarks), and the TPU compiler takes
# seconds to minutes per program (PERF.md round 22). The cache is placed
# from OUTSIDE, by JAX's own variable: where JAX_COMPILATION_CACHE_DIR is
# set, nothing is set here. Otherwise it lives at the fixed
# <checkout>/.jax_cache — the path is part of the cache key, so it is
# never a temporary, per-pid or timed directory. Forced-CPU processes
# (tests, virtual meshes) run without one unless a directory is given:
# CPU compiles are fast, and XLA:CPU AOT entries warn about host-feature
# mismatches across machines.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        and _os.environ.get("JAX_PLATFORMS", "") != "cpu":
    _jax.config.update("jax_compilation_cache_dir", _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache"))

# pandas 3 defaults str columns/indexes to pyarrow-backed storage, and
# ArrowStringArray._from_sequence intermittently SEGFAULTS when a
# DataFrame is constructed on a non-main thread in this image (observed
# from the pgwire/gRPC server threads). numpy-backed str storage keeps
# the same dtype semantics without pyarrow on the construction path.
import pandas as _pd

_pd.set_option("mode.string_storage", "python")

__version__ = "0.1.0"
