"""TPC-H Q3 (shipping priority), substitution parameters SEGMENT (one of five) and DATE
(a day of 1995-03-01..31) (clause 2.4.3.3)."""

import numpy as np

TABLES = {"customer": ["c_custkey", "c_mktsegment"],
          "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
          "lineitem": ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"]}
VALIDATION = {"segment": "BUILDING", "date": "1995-03-15"}  # clause 2.4.3.4
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")

SQL = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = '{segment}' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '{date}'
  and l_shipdate > date '{date}'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10"""


def params(rng, config):
    return {"segment": rng.choice(SEGMENTS), "date": "1995-03-%02d" % rng.randint(1, 31)}


def render(p):
    return SQL.format(**p), None


def reference(T, p, dtype=np.float64):
    c, o, l = T["customer"], T["orders"], T["lineitem"]
    cutoff = (np.datetime64(p["date"]) - np.datetime64("1970-01-01")).astype(np.int64)
    c2 = c[c["c_mktsegment"] == p["segment"]][["c_custkey"]]
    o2 = o[o["o_orderdate"].to_numpy() < cutoff][
        ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"]]
    l2 = l[l["l_shipdate"].to_numpy() > cutoff][
        ["l_orderkey", "l_extendedprice", "l_discount"]]
    j = o2.merge(c2, left_on="o_custkey", right_on="c_custkey")
    j = l2.merge(j, left_on="l_orderkey", right_on="o_orderkey")
    hundred = dtype(100)
    revenue = (j["l_extendedprice"].to_numpy().astype(dtype) / hundred) \
        * (1 - j["l_discount"].to_numpy().astype(dtype) / hundred)
    j = j.assign(revenue=revenue)
    r = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])["revenue"].sum().reset_index()
    r = r.sort_values(["revenue", "o_orderdate"], ascending=[False, True]).head(10)
    r = r.assign(o_orderdate=r["o_orderdate"].to_numpy().astype("datetime64[D]"),
                 revenue=r["revenue"].to_numpy().astype(dtype))
    return r[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]].reset_index(drop=True)
