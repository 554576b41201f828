"""Device sort / top-k block operators.

Analogs of WideTopSort/WideSort/WideTop (`mkql_block_top.cpp`,
`mkql_wide_top_sort.cpp`): multi-key sort via ``lax.sort`` over bit-monotone
encodings (descending keys flip their encoding), carrying row indices, then
a static-width head take for LIMIT.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ydb_tpu.ops.device import DeviceBlock
from ydb_tpu.ops.xla_exec import (
    _sort_operand, _zero_like_operand, record_sort, sort_total,
)


def sort_env(arrays, valids, length, sel, keys: tuple, names: tuple):
    """Traceable sort body (callable from fused jitted pipelines);
    keys: tuple of (col_name, ascending, nulls_first)."""
    return _sort_impl(arrays, valids, length, sel, keys, names)


@partial(jax.jit, static_argnames=("keys", "names"))
def _sort_block(arrays, valids, length, sel, keys: tuple, names: tuple):
    return _sort_impl(arrays, valids, length, sel, keys, names)


def _sort_impl(arrays, valids, length, sel, keys: tuple, names: tuple):
    """keys: tuple of (col_name, ascending, nulls_first).

    Sorts key encodings + a row-id only (carrying whole rows through a wide
    multi-operand ``lax.sort`` explodes XLA compile time on TPU); row values
    follow by permutation gathers, which XLA fuses."""
    first = arrays[names[0]]
    cap = first.shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    active = iota < length
    if sel is not None:
        active = active & sel

    sort_ops = [(~active).astype(jnp.int32)]  # dropped rows go last
    for (name, asc, nulls_first) in keys:
        d = arrays[name]
        v = valids.get(name)
        enc = _sort_operand(d)
        if not asc:
            if enc.dtype in (jnp.float64, jnp.float32):
                enc = -enc
            else:
                enc = ~enc  # bitwise not: reverses order, no int64-min overflow
        if v is not None:
            nullrank = (~v).astype(jnp.int32) if not nulls_first else v.astype(jnp.int32)
            sort_ops.append(nullrank)
            enc = jnp.where(v, enc, _zero_like_operand(enc))
        sort_ops.append(enc)

    # iota as the final key → deterministic (stable) order; the sorted iota
    # IS the permutation
    record_sort(cap, len(sort_ops) + 1)   # sort/rows_max + operands_max
    out = sort_total(sort_ops, iota)
    perm = out[-1]
    new_arrays, new_valids = {}, {}
    for name in names:
        new_arrays[name] = arrays[name][perm]
        if name in valids:
            new_valids[name] = valids[name][perm]
    new_len = jnp.sum(active.astype(jnp.int32))
    return new_arrays, new_valids, new_len


def sort_block(dblock: DeviceBlock, keys: list[tuple], sel=None,
               limit=None) -> DeviceBlock:
    """keys: [(name, ascending, nulls_first)]; limit caps the result length."""
    names = tuple(dblock.schema.names)
    arrays, valids, length = _sort_block(
        dblock.arrays, dblock.valids, dblock.length, sel,
        tuple(keys), names)
    if limit is not None:
        length = jnp.minimum(length, jnp.int32(limit))
    return DeviceBlock(dblock.schema, arrays, valids, length,
                       dblock.capacity, dict(dblock.dictionaries))
