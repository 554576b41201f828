"""TPC-H Q6, forecasting revenue change. DATE 1 Jan of 1993..1997,
DISCOUNT in [0.02, 0.09], QUANTITY 24 or 25 (cl. 2.4.6.3)."""

TABLES = {"lineitem": ["l_extendedprice", "l_discount", "l_quantity",
                       "l_shipdate"]}


def sample(rng) -> dict:
    return {"year": int(rng.integers(1993, 1998)),
            "discount": int(rng.integers(2, 10)),      # hundredths
            "quantity": int(rng.integers(24, 26))}


def _bounds(p: dict):
    # the literals as the SQL text carries them, so both sides read the
    # same doubles
    return (float(f"0.{p['discount'] - 1:02d}"),
            float(f"0.{p['discount'] + 1:02d}"))


def sql(p: dict) -> str:
    lo, hi = _bounds(p)
    return f"""
select sum(l_extendedprice*l_discount) as revenue
from lineitem
where l_shipdate >= date '{p["year"]}-01-01'
  and l_shipdate < date '{p["year"]}-01-01' + interval '1' year
  and l_discount between {lo!r} and {hi!r}
  and l_quantity < {p["quantity"]}"""


def reference(f, p: dict):
    import pandas as pd
    from refutil import day
    lo, hi = _bounds(p)
    li = f("lineitem", TABLES["lineitem"])
    d = li[(li.l_shipdate >= day(f"{p['year']}-01-01"))
           & (li.l_shipdate < day(f"{p['year'] + 1}-01-01"))
           & (li.l_discount >= lo - 1e-12) & (li.l_discount <= hi + 1e-12)
           & (li.l_quantity < p["quantity"])]
    return pd.DataFrame({"revenue": [(d.l_extendedprice * d.l_discount).sum()]})
