"""TPC-H Q3, shipping priority. SEGMENT one of five, DATE a day of March
1995 (cl. 2.4.3.3)."""

TABLES = {"customer": ["c_custkey", "c_mktsegment"],
          "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                     "o_shippriority"],
          "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                       "l_shipdate"]}

_SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]


def sample(rng) -> dict:
    return {"segment": _SEGMENTS[int(rng.integers(0, 5))],
            "date": f"1995-03-{int(rng.integers(1, 32)):02d}"}


def sql(p: dict) -> str:
    return f"""
select l_orderkey, sum(l_extendedprice*(1-l_discount)) as revenue,
  o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = '{p["segment"]}' and c_custkey = o_custkey
  and l_orderkey = o_orderkey
  and o_orderdate < date '{p["date"]}' and l_shipdate > date '{p["date"]}'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate
limit 10"""


def reference(f, p: dict):
    from refutil import day, iso
    cu, od, li = (f(t, TABLES[t]) for t in ("customer", "orders", "lineitem"))
    c = cu[cu.c_mktsegment == p["segment"]]
    o = od[od.o_orderdate < day(p["date"])]
    l = li[li.l_shipdate > day(p["date"])]
    j = l.merge(o, left_on="l_orderkey", right_on="o_orderkey") \
         .merge(c, left_on="o_custkey", right_on="c_custkey")
    j = j.assign(rev=j.l_extendedprice * (1 - j.l_discount))
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"]).rev.sum() \
         .reset_index().rename(columns={"rev": "revenue"})
    g = g.sort_values(["revenue", "o_orderdate"],
                      ascending=[False, True], kind="stable").head(10)
    g = g.assign(o_orderdate=iso(g.o_orderdate))
    return g[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]]
