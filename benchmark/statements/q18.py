"""TPC-H Q18 (large volume customer), substitution parameter QUANTITY in [312, 315]
(clause 2.4.18.3)."""

import numpy as np

TABLES = {"customer": ["c_custkey", "c_name"],
          "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"],
          "lineitem": ["l_orderkey", "l_quantity"]}

VALIDATION = {"quantity": 300}  # clause 2.4.18.4

SQL = """
select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice, sum(l_quantity)
from customer, orders, lineitem
where o_orderkey in (select l_orderkey from lineitem group by l_orderkey
                     having sum(l_quantity) > {quantity})
  and c_custkey = o_custkey and o_orderkey = l_orderkey
group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
order by o_totalprice desc, o_orderdate limit 100"""


def params(rng, config):
    return {"quantity": rng.randint(312, 315)}


def render(p):
    return SQL.format(**p), None


def reference(T, p, dtype=np.float64):
    c, o, l = T["customer"], T["orders"], T["lineitem"]
    qty = l.groupby("l_orderkey")["l_quantity"].sum()
    big = qty[qty > p["quantity"] * 100].index  # l_quantity is a decimal scaled by 100
    o2 = o[o["o_orderkey"].isin(big)]
    j = o2.merge(c[["c_custkey", "c_name"]], left_on="o_custkey", right_on="c_custkey")
    j = j.merge(l[["l_orderkey", "l_quantity"]], left_on="o_orderkey", right_on="l_orderkey")
    r = j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice"])[
        "l_quantity"].sum().reset_index()
    r = r.sort_values(["o_totalprice", "o_orderdate"], ascending=[False, True]).head(100)
    hundred = dtype(100)
    r = r.assign(c_name=r["c_name"].astype(str),
                 o_orderdate=r["o_orderdate"].to_numpy().astype("datetime64[D]"),
                 o_totalprice=r["o_totalprice"].to_numpy().astype(dtype) / hundred,
                 sum_qty=r["l_quantity"].to_numpy().astype(dtype) / hundred)
    return r[["c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice",
              "sum_qty"]].reset_index(drop=True)
