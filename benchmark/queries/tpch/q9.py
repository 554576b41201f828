"""TPC-H Q9, product type profit measure. COLOR a word of the part-name
list (cl. 2.4.9.3). `hot` is left out: it is also inside `hotpink`, so it
alone would match twice the parts and change the cell's work by the seed
(listed under `assumed` in the configuration)."""

TABLES = {"part": ["p_partkey", "p_name"],
          "supplier": ["s_suppkey", "s_nationkey"],
          "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_quantity",
                       "l_extendedprice", "l_discount"],
          "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
          "orders": ["o_orderkey", "o_orderdate"],
          "nation": ["n_nationkey", "n_name"]}

_COLORS = ["almond", "antique", "aquamarine", "azure", "beige", "bisque",
           "black", "blanched", "blue", "blush", "brown", "burlywood",
           "burnished", "chartreuse", "chiffon", "chocolate", "coral",
           "cornflower", "cornsilk", "cream", "cyan", "dark", "deep",
           "dim", "dodger", "drab", "firebrick", "floral", "forest",
           "frosted", "gainsboro", "ghost", "goldenrod", "green", "grey",
           "honeydew", "hotpink", "indian", "ivory", "khaki",
           "lace", "lavender", "lawn", "lemon", "light", "lime", "linen"]


def sample(rng) -> dict:
    return {"color": _COLORS[int(rng.integers(0, len(_COLORS)))]}


def sql(p: dict) -> str:
    return f"""
select n_name, year(o_orderdate) as o_year,
  sum(l_extendedprice*(1-l_discount) - ps_supplycost*l_quantity) as sum_profit
from part, supplier, lineitem, partsupp, orders, nation
where s_suppkey = l_suppkey and ps_suppkey = l_suppkey
  and ps_partkey = l_partkey and p_partkey = l_partkey
  and o_orderkey = l_orderkey and s_nationkey = n_nationkey
  and p_name like '%{p["color"]}%'
group by n_name, o_year
order by n_name, o_year desc"""


def reference(f, p: dict):
    import numpy as np
    import pandas as pd
    pa, su, li, ps, od, na = (f(t, TABLES[t]) for t in (
        "part", "supplier", "lineitem", "partsupp", "orders", "nation"))
    part = pa[pa.p_name.str.contains(p["color"], regex=False)]
    j = li.merge(part, left_on="l_partkey", right_on="p_partkey") \
          .merge(su, left_on="l_suppkey", right_on="s_suppkey") \
          .merge(ps, left_on=["l_partkey", "l_suppkey"],
                 right_on=["ps_partkey", "ps_suppkey"]) \
          .merge(od, left_on="l_orderkey", right_on="o_orderkey") \
          .merge(na, left_on="s_nationkey", right_on="n_nationkey")
    oy = (pd.to_datetime(j.o_orderdate, unit="D", origin="unix")
          .dt.year.astype(np.int64))
    amount = j.l_extendedprice * (1 - j.l_discount) \
        - j.ps_supplycost * j.l_quantity
    j = j.assign(o_year=oy, amount=amount)
    g = j.groupby(["n_name", "o_year"]).amount.sum().reset_index() \
         .rename(columns={"amount": "sum_profit"})
    return g.sort_values(["n_name", "o_year"],
                         ascending=[True, False], kind="stable")
