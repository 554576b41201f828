"""Pins the benchmark's copies to what they were copied from: the
generator table for table, the five references (run with TPC-H's
validation parameters) against `tests/tpch_util.oracle`."""

import numpy as np
import pytest

import traffic
from refutil import Frames, iso
from tpch_gen import TPCH_COLUMNS, TpchData

VALIDATION = {"q1": {"delta": 90},
              "q6": {"year": 1994, "discount": 6, "quantity": 24},
              "q3": {"segment": "BUILDING", "date": "1995-03-15"},
              "q9": {"color": "green"},
              "q18": {"quantity": 250}}
SF, SEED = 0.01, 19920101


@pytest.fixture(scope="module")
def both():
    from ydb_tpu.bench import tpch_gen as theirs
    return TpchData(SF, SEED), theirs.TpchData(SF, SEED), theirs


def test_the_generator_is_the_repos_own(both):
    mine, orig, theirs = both
    assert set(mine.tables) == set(orig.tables)
    for table, cols in orig.tables.items():
        assert list(mine.tables[table]) == list(cols)
        for name, a in cols.items():
            b = mine.tables[table][name]
            assert a.dtype == b.dtype and np.array_equal(a, b), (table, name)
    for table, (schema, keys) in theirs.TPCH_SCHEMAS.items():
        cols, my_keys = TPCH_COLUMNS[table]
        assert my_keys == keys
        assert [(c.name, c.dtype.kind.value) for c in schema] == [
            (n, {"string": "string"}.get(k, k)) for n, k in cols]


@pytest.mark.parametrize("name", sorted(VALIDATION))
def test_reference_equals_the_repos_oracle(both, name):
    from tests.tpch_util import QUERIES, assert_frames_match, oracle
    mine, orig, _ = both
    mod = traffic.load_module("queries/tpch", name)
    got = mod.reference(Frames(mine.tables), VALIDATION[name])
    want = oracle(name, orig)
    for col in want.columns:                 # theirs keeps dates as days
        if col.endswith("date") and want[col].dtype.kind in "iu":
            want = want.assign(**{col: iso(want[col])})
    got.columns = list(want.columns)
    assert_frames_match(got, want, ordered=True)
    # and the SQL is the repo's own text with the validation literals
    squeeze = lambda s: " ".join(s.split())            # noqa: E731
    assert squeeze(mod.sql(VALIDATION[name])) == squeeze(QUERIES[name])
