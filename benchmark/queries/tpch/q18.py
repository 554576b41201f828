"""TPC-H Q18, large volume customer. QUANTITY in [248, 252]: the
specification's 312..315 (cl. 2.4.18.3) almost never occurs in this
generator's orders (1-7 lines of quantity 1-50), so the range stays round
the repo's own 250 (listed under `assumed` in the configuration)."""

TABLES = {"customer": ["c_custkey", "c_name"],
          "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                     "o_totalprice"],
          "lineitem": ["l_orderkey", "l_quantity"]}


def sample(rng) -> dict:
    return {"quantity": int(rng.integers(248, 253))}


def sql(p: dict) -> str:
    return f"""
select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
  sum(l_quantity) as total_qty
from customer, orders, lineitem
where o_orderkey in (select l_orderkey from lineitem
                     group by l_orderkey having sum(l_quantity) > {p["quantity"]})
  and c_custkey = o_custkey and o_orderkey = l_orderkey
group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
order by o_totalprice desc, o_orderdate
limit 100"""


def reference(f, p: dict):
    from refutil import iso
    cu, od, li = (f(t, TABLES[t]) for t in ("customer", "orders", "lineitem"))
    big = li.groupby("l_orderkey").l_quantity.sum()
    big = big[big > p["quantity"]].index
    o = od[od.o_orderkey.isin(big)]
    j = li.merge(o, left_on="l_orderkey", right_on="o_orderkey") \
          .merge(cu, left_on="o_custkey", right_on="c_custkey")
    g = j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                   "o_totalprice"]).l_quantity.sum().reset_index() \
         .rename(columns={"l_quantity": "total_qty"})
    g = g.sort_values(["o_totalprice", "o_orderdate"],
                      ascending=[False, True], kind="stable").head(100)
    return g.assign(o_orderdate=iso(g.o_orderdate))
