"""`xla_exec.compact_env`, the lowering of `ir.Compact`, against a numpy
reference: the kept rows, their order, the live count, the overflow flag
and what the masked slots hold, for every column type a row env carries,
with and without validity planes. And the shape of the program: the
source row of each kept slot is found once, so no scatter takes its
updates from an array of the scan capacity (the per-column dropping
scatter that cost 417-548 ms a statement at SF1; PERF.md round 27).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ydb_tpu.core import dtypes as dt
from ydb_tpu.ops import ir
from ydb_tpu.ops.xla_exec import ProgramCache, compact_env

CAP = 4096
NEW_CAP = 512

KINDS = {
    "int32": dt.Kind.INT32, "int64": dt.Kind.INT64,
    "float64": dt.Kind.FLOAT64, "date32": dt.Kind.DATE32,
}


def _column(kind: str, rng) -> np.ndarray:
    np_dtype = dt.DType(KINDS[kind], False).np
    if kind == "float64":
        col = rng.normal(size=CAP) * 10.0 ** rng.integers(-9, 9, size=CAP)
        col[:4] = [0.0, -0.0, np.inf, -np.inf]
        return col.astype(np_dtype)
    info = np.iinfo(np_dtype)
    return rng.integers(info.min, info.max, size=CAP, dtype=np.int64,
                        endpoint=True).astype(np_dtype)


def _case(case: str, rng):
    """(length, sel | None) of one named case."""
    if case == "none_live":
        return CAP, np.zeros(CAP, dtype=bool)
    if case == "all_live_at_bound":          # exactly NEW_CAP rows live
        sel = np.zeros(CAP, dtype=bool)
        sel[rng.choice(CAP, size=NEW_CAP, replace=False)] = True
        return CAP, sel
    if case == "overflow":                   # live > NEW_CAP
        return CAP, rng.random(CAP) < 0.5
    if case == "short_no_sel":               # length < cap, sel=None
        return NEW_CAP - 37, None
    if case == "sparse_beyond_length":       # sel also holds dead rows
        return CAP - 1000, rng.random(CAP) < 0.05
    raise ValueError(case)


CASES = ("none_live", "all_live_at_bound", "overflow", "short_no_sel",
         "sparse_beyond_length")


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("nullable", [False, True],
                         ids=["no_validity", "validity"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_compact_env_matches_numpy(kind, nullable, case):
    rng = np.random.default_rng(
        27 + 100 * sorted(KINDS).index(kind) + CASES.index(case))
    col = _column(kind, rng)
    valid = (rng.random(CAP) < 0.8) if nullable else None
    pos = np.arange(CAP, dtype=np.int32)
    length, sel = _case(case, rng)

    active = pos < length
    if sel is not None:
        active &= sel
    rows = np.flatnonzero(active)
    kept = rows[:NEW_CAP]

    env = {"c": (jnp.asarray(col),
                 None if valid is None else jnp.asarray(valid)),
           "pos": (jnp.asarray(pos), None)}
    new_env, new_len, new_sel, live, ovf = jax.jit(
        lambda e, n, s: compact_env(e, n, s, CAP, NEW_CAP))(
            env, jnp.int32(length), None if sel is None else jnp.asarray(sel))

    assert int(live) == len(rows)
    assert bool(ovf) == (len(rows) > NEW_CAP)
    assert int(new_len) == len(kept)
    np.testing.assert_array_equal(np.asarray(new_sel),
                                  np.arange(NEW_CAP) < len(kept))
    got, got_valid = new_env["c"]
    assert got.shape == (NEW_CAP,) and got.dtype == col.dtype
    # stable: the kept rows are the FIRST live rows, in scan order
    got_pos = np.asarray(new_env["pos"][0])
    np.testing.assert_array_equal(got_pos[:len(kept)], kept)
    assert np.all(np.diff(got_pos[:len(kept)]) > 0)
    # bit for bit (-0.0 and inf included), not merely equal
    assert np.asarray(got)[:len(kept)].tobytes() == col[kept].tobytes()
    # masked slots: deterministic, a copy of the capacity's last row
    assert np.asarray(got)[len(kept):].tobytes() \
        == np.full(NEW_CAP - len(kept), col[CAP - 1]).tobytes()
    if valid is None:
        assert got_valid is None
    else:
        np.testing.assert_array_equal(np.asarray(got_valid)[:len(kept)],
                                      valid[kept])
        assert np.all(np.asarray(got_valid)[len(kept):] == valid[CAP - 1])


def test_compact_env_columnless_env():
    """A count(*) plan's env holds no column: the mask carries the shape."""
    sel = np.arange(CAP) % 16 == 3
    env, new_len, new_sel, live, ovf = compact_env(
        {}, jnp.int32(CAP), jnp.asarray(sel), CAP, NEW_CAP)
    assert env == {} and new_sel.shape == (NEW_CAP,)
    assert (int(new_len), int(live), bool(ovf)) == (CAP // 16, CAP // 16,
                                                    False)


def test_compact_env_refuses_to_grow():
    with pytest.raises(ValueError, match="shrinks"):
        compact_env({}, jnp.int32(8), None, 8, 16)


def _wide_scatters(stablehlo: str, cap: int) -> list:
    """Operand types of every scatter whose UPDATES have `cap` rows."""
    found = []
    for m in re.finditer(r'"stablehlo\.scatter"\(.*?\}\) : \(([^)]*)\) ->',
                         stablehlo, flags=re.DOTALL):
        updates = m.group(1).split(",")[-1].strip()
        if updates.startswith(f"tensor<{cap}x"):
            found.append(m.group(1))
    return found


def _vec(dtype):
    return jax.ShapeDtypeStruct((CAP,), dtype)


def test_wide_scatter_detector_finds_the_old_lowering():
    """The planted fault: what `compact_env` did before PR 27."""
    def per_column_scatter(active, a):
        rank = jnp.cumsum(active.astype(jnp.int32)) - 1
        tgt = jnp.where(active, rank, jnp.int32(NEW_CAP))
        return jnp.zeros((NEW_CAP,), a.dtype).at[tgt].set(a, mode="drop")

    text = jax.jit(per_column_scatter).lower(
        _vec(np.bool_), _vec(np.float64)).as_text()
    assert len(_wide_scatters(text, CAP)) == 1


def test_compact_program_holds_no_scan_capacity_scatter():
    """Filter at scan capacity, Compact to the bound, a group-by behind
    it: nothing in the lowered program scatters `CAP` updates."""
    prog = ir.Program()
    prog.filter(ir.call("le", ir.Col("d"),
                        ir.Const(10471, dt.DType(dt.Kind.DATE32, False))))
    prog.compact(NEW_CAP, bound=400)
    prog.group_by([], [ir.Agg("s", "sum", "v"), ir.Agg("n", "count_all")])
    sig = (("d", "date32", False), ("k", "int64", False),
           ("v", "float64", True))
    fn = ProgramCache._build(prog, sig, CAP)
    arrays = {n: _vec(dt.DType(dt.Kind(k), nu).np) for (n, k, nu) in sig}
    valids = {n: _vec(np.bool_) for (n, _k, nu) in sig if nu}
    text = fn.lower(arrays, valids, jax.ShapeDtypeStruct((), np.int32),
                    {}).as_text()
    assert "stablehlo.sort" in text and "stablehlo.gather" in text
    assert _wide_scatters(text, CAP) == []
