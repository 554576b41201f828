"""TPC-H Q1, pricing summary report. DELTA in [60, 120] days (cl. 2.4.1.3)."""

TABLES = {"lineitem": ["l_returnflag", "l_linestatus", "l_quantity",
                       "l_extendedprice", "l_discount", "l_tax",
                       "l_shipdate"]}


def sample(rng) -> dict:
    return {"delta": int(rng.integers(60, 121))}


def sql(p: dict) -> str:
    return f"""
select l_returnflag, l_linestatus,
  sum(l_quantity) as sum_qty,
  sum(l_extendedprice) as sum_base_price,
  sum(l_extendedprice*(1-l_discount)) as sum_disc_price,
  sum(l_extendedprice*(1-l_discount)*(1+l_tax)) as sum_charge,
  avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
  avg(l_discount) as avg_disc, count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '{p["delta"]}' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus"""


def reference(f, p: dict):
    from refutil import day
    li = f("lineitem", TABLES["lineitem"])
    d = li[li.l_shipdate <= day("1998-12-01") - p["delta"]]
    disc = d.l_extendedprice * (1 - d.l_discount)
    d = d.assign(dp=disc, ch=disc * (1 + d.l_tax))
    return d.groupby(["l_returnflag", "l_linestatus"], sort=True).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("dp", "sum"), sum_charge=("ch", "sum"),
        avg_qty=("l_quantity", "mean"), avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_shipdate", "count")).reset_index()
