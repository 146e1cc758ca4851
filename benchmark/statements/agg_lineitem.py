"""A dashboard tile: a short group-by over all of lineitem, the same text every time."""

import numpy as np
import pandas as pd

TABLES = {"lineitem": ["l_returnflag", "l_quantity"]}
VALIDATION = {}
SQL = ("select l_returnflag, count(*) c, sum(l_quantity) q "
       "from lineitem group by l_returnflag order by l_returnflag")


def params(rng, config):
    return {}


def render(p):
    return SQL, None


def reference(T, p, dtype=np.float64):
    c = T.columns("lineitem")
    code = c["l_returnflag"].astype(np.int64)
    count = np.bincount(code)
    groups = np.nonzero(count)[0]
    qty = np.bincount(code, weights=c["l_quantity"].astype(dtype) / dtype(100))
    out = pd.DataFrame({"l_returnflag": T.decode("lineitem", "l_returnflag", groups),
                        "c": count[groups], "q": qty[groups].astype(dtype)})
    return out.sort_values("l_returnflag").reset_index(drop=True)
