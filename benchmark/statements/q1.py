"""TPC-H Q1 (pricing summary report), substitution parameter DELTA in [60, 120] (clause 2.4.1.3)."""

import numpy as np
import pandas as pd

TABLES = {"lineitem": ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
                       "l_discount", "l_tax", "l_shipdate"]}
VALIDATION = {"delta": 90}  # clause 2.4.1.4
AVG_DECIMALS = {"avg_qty": 2, "avg_price": 2, "avg_disc": 2}

SQL = """
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
       sum(l_extendedprice) as sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
       avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
       avg(l_discount) as avg_disc, count(*) as count_order
from lineitem where l_shipdate <= date '1998-12-01' - interval '{delta}' day
group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus"""


def params(rng, config):
    return {"delta": rng.randint(60, 120)}


def render(p):
    return SQL.format(**p), None


def reference(T, p, dtype=np.float64):
    """One pass of ``np.bincount`` over the combined flag/status code: a pandas groupby
    over 60M rows would take longer than the window it checks."""
    c = T.columns("lineitem")
    cutoff = (np.datetime64("1998-12-01") - np.timedelta64(p["delta"], "D")
              - np.datetime64("1970-01-01")).astype(np.int64)
    m = c["l_shipdate"] <= cutoff
    code = c["l_returnflag"][m].astype(np.int64) * 16 + c["l_linestatus"][m]
    hundred = dtype(100)
    qty = c["l_quantity"][m].astype(dtype) / hundred
    price = c["l_extendedprice"][m].astype(dtype) / hundred
    disc = c["l_discount"][m].astype(dtype) / hundred
    tax = c["l_tax"][m].astype(dtype) / hundred
    disc_price = price * (1 - disc)
    count = np.bincount(code, minlength=256)
    groups = np.nonzero(count)[0]

    def total(x):
        return np.bincount(code, weights=x, minlength=256)[groups].astype(dtype)

    n = count[groups]
    out = pd.DataFrame({
        "l_returnflag": T.decode("lineitem", "l_returnflag", groups // 16),
        "l_linestatus": T.decode("lineitem", "l_linestatus", groups % 16),
        "sum_qty": total(qty), "sum_base_price": total(price),
        "sum_disc_price": total(disc_price), "sum_charge": total(disc_price * (1 + tax)),
        "avg_qty": total(qty) / n, "avg_price": total(price) / n,
        "avg_disc": total(disc) / n, "count_order": n})
    return out.sort_values(["l_returnflag", "l_linestatus"]).reset_index(drop=True)
