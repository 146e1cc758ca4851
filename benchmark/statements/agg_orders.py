"""A dashboard tile: orders by priority, the same text every time."""

import numpy as np
import pandas as pd

TABLES = {"orders": ["o_orderpriority"]}
VALIDATION = {}
SQL = ("select o_orderpriority, count(*) c from orders "
       "group by o_orderpriority order by o_orderpriority")


def params(rng, config):
    return {}


def render(p):
    return SQL, None


def reference(T, p, dtype=np.float64):
    code = T.columns("orders")["o_orderpriority"].astype(np.int64)
    count = np.bincount(code)
    groups = np.nonzero(count)[0]
    out = pd.DataFrame({"o_orderpriority": T.decode("orders", "o_orderpriority", groups),
                        "c": count[groups]})
    return out.sort_values("o_orderpriority").reset_index(drop=True)
