"""TPC-H Q9 (product type profit), substitution parameter COLOR: a word of the part-name
pool (clause 2.4.9.3).  The pool is the generator's own, copied here as data: a word
outside it would select nothing."""

import numpy as np

TABLES = {"part": ["p_partkey", "p_name"], "supplier": ["s_suppkey", "s_nationkey"],
          "lineitem": ["l_partkey", "l_suppkey", "l_orderkey", "l_quantity",
                       "l_extendedprice", "l_discount"],
          "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
          "orders": ["o_orderkey", "o_orderdate"], "nation": ["n_nationkey", "n_name"]}
VALIDATION = {"color": "green"}  # clause 2.4.9.4
COLORS = (
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black", "blanched",
    "blue", "blush", "brown", "burlywood", "burnished", "chartreuse", "chiffon",
    "chocolate", "coral", "cornflower", "cornsilk", "cream", "cyan", "dark", "deep",
    "dim", "dodger", "drab", "firebrick", "floral", "forest", "frosted", "gainsboro",
    "ghost", "goldenrod", "green", "grey", "honeydew", "hot", "indian", "ivory", "khaki",
    "lace", "lavender", "lawn", "lemon", "light", "lime", "linen", "magenta", "maroon",
    "medium", "metallic", "midnight", "mint", "misty", "moccasin", "navajo", "navy",
    "olive", "orange", "orchid", "pale", "papaya", "peach", "peru", "pink", "plum",
    "powder", "puff", "purple", "red", "rose", "rosy", "royal", "saddle", "salmon",
    "sandy", "seashell", "sienna", "sky", "slate", "smoke", "snow", "spring", "steel",
    "tan", "thistle", "tomato", "turquoise", "violet", "wheat", "white", "yellow")

SQL = """
select nation, o_year, sum(amount) as sum_profit from (
  select n_name as nation, extract(year from o_orderdate) as o_year,
    l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity as amount
  from part, supplier, lineitem, partsupp, orders, nation
  where s_suppkey = l_suppkey and ps_suppkey = l_suppkey and ps_partkey = l_partkey
    and p_partkey = l_partkey and o_orderkey = l_orderkey
    and s_nationkey = n_nationkey and p_name like '%{color}%') as profit
group by nation, o_year order by nation, o_year desc"""


def params(rng, config):
    return {"color": rng.choice(COLORS)}


def render(p):
    return SQL.format(**p), None


def reference(T, p, dtype=np.float64):
    part, s, l = T["part"], T["supplier"], T["lineitem"]
    ps, o, n = T["partsupp"], T["orders"], T["nation"]
    p2 = part[part["p_name"].astype(str).str.contains(p["color"], regex=False)][["p_partkey"]]
    j = l.merge(p2, left_on="l_partkey", right_on="p_partkey")
    j = j.merge(s[["s_suppkey", "s_nationkey"]], left_on="l_suppkey", right_on="s_suppkey")
    j = j.merge(ps[["ps_partkey", "ps_suppkey", "ps_supplycost"]],
                left_on=["l_partkey", "l_suppkey"], right_on=["ps_partkey", "ps_suppkey"])
    j = j.merge(o[["o_orderkey", "o_orderdate"]], left_on="l_orderkey", right_on="o_orderkey")
    j = j.merge(n[["n_nationkey", "n_name"]], left_on="s_nationkey", right_on="n_nationkey")
    hundred = dtype(100)

    def money(name):
        return j[name].to_numpy().astype(dtype) / hundred

    amount = money("l_extendedprice") * (1 - money("l_discount")) \
        - money("ps_supplycost") * money("l_quantity")
    year = j["o_orderdate"].to_numpy().astype("datetime64[D]").astype("datetime64[Y]") \
        .astype(np.int64) + 1970
    j = j.assign(sum_profit=amount, o_year=year, nation=j["n_name"].astype(str))
    r = j.groupby(["nation", "o_year"])["sum_profit"].sum().reset_index()
    r = r.assign(sum_profit=r["sum_profit"].to_numpy().astype(dtype))
    return r.sort_values(["nation", "o_year"], ascending=[True, False]).reset_index(drop=True)
