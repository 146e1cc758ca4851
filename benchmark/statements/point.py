"""A dashboard's point lookup with the key inlined in the text: every request is a new
SQL text, identical up to its constant (the shape plan templates exist for)."""

import numpy as np
import pandas as pd

TABLES = {"customer": ["c_custkey", "c_name", "c_acctbal", "c_mktsegment"]}
VALIDATION = {"key": 1}
SQL = "select c_name, c_acctbal, c_mktsegment from customer where c_custkey = "


def params(rng, config):
    return {"key": rng.randint(1, max(int(150000 * config["sf"]) - 1, 1))}


def render(p):
    return SQL + str(p["key"]), None


def reference(T, p, dtype=np.float64):
    c = T["customer"]
    r = c[c["c_custkey"].to_numpy() == p["key"]]
    return pd.DataFrame({
        "c_name": r["c_name"].astype(str).to_numpy(),
        "c_acctbal": r["c_acctbal"].to_numpy().astype(dtype) / dtype(100),
        "c_mktsegment": r["c_mktsegment"].astype(str).to_numpy()})
