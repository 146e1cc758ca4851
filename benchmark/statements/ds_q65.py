"""TPC-DS query 65 (query65.tpl): the (store, item) pairs whose revenue of twelve months
is at most a tenth of their store's average pair revenue.  The template's one
substitution parameter is DMS, the first ``d_month_seq`` of the year (qualification
value 1176: 1176..1187); ``0.1`` is part of the template's text and is a parameter here
only so that a test at a tiny scale, where no pair falls under a tenth, can ask for an
answer that has rows.

ORDER BY s_store_name, i_item_desc leaves ties in this deployment (the repo's generator
repeats a store name every 12 stores and an item description every 18,000 items: 45 of
the answer's 100 rows at scale 10 share both keys with another row).  SQL leaves the
order inside a tie group, and which rows of a group that the LIMIT cuts are kept, to the
engine, and the harness's comparison is positional.  So the cell's text, ``SQL``, departs
from the template in ONE place: its ORDER BY goes on with the remaining SELECT columns in
SELECT order (sc.revenue, i_current_price, i_wholesale_cost, i_brand), which makes the
order total; joins, group-bys, predicate, LIMIT and every column are the template's.  The
reference sorts by exactly these six.  The template's own text stays here as
``TEMPLATE_SQL`` (``render_template``) for the tier-1 test that compares tie groups as
sets (``tests/test_tpcds_hash_cell.tie_aligned``); when the harness has a tie-aware
comparison the cell's text goes back to it (ROADMAP S7)."""

from fractions import Fraction

import numpy as np
import pandas as pd

TABLES = {"store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk", "ss_sales_price"],
          "date_dim": ["d_date_sk", "d_month_seq"],
          "store": ["s_store_sk", "s_store_name"],
          "item": ["i_item_sk", "i_item_desc", "i_current_price", "i_wholesale_cost",
                   "i_brand"]}
VALIDATION = {"dms": 1176, "factor": "0.1"}  # query65.tpl, qualification substitution

TEMPLATE_SQL = """
select s_store_name, i_item_desc, sc.revenue, i_current_price, i_wholesale_cost, i_brand
from store, item,
     (select ss_store_sk, avg(revenue) as ave
      from (select ss_store_sk, ss_item_sk, sum(ss_sales_price) as revenue
            from store_sales, date_dim
            where ss_sold_date_sk = d_date_sk and d_month_seq between {dms} and {dms}+11
            group by ss_store_sk, ss_item_sk) sa
      group by ss_store_sk) sb,
     (select ss_store_sk, ss_item_sk, sum(ss_sales_price) as revenue
      from store_sales, date_dim
      where ss_sold_date_sk = d_date_sk and d_month_seq between {dms} and {dms}+11
      group by ss_store_sk, ss_item_sk) sc
where sb.ss_store_sk = sc.ss_store_sk and
      sc.revenue <= {factor} * sb.ave and
      s_store_sk = sc.ss_store_sk and
      i_item_sk = sc.ss_item_sk
order by s_store_name, i_item_desc
limit 100"""
ORDER_BY = ["s_store_name", "i_item_desc"]  # the template's: it leaves ties
# the cell's text: the template's ORDER BY completed to a total order (module docstring)
SQL = TEMPLATE_SQL.replace(
    "order by s_store_name, i_item_desc",
    "order by s_store_name, i_item_desc, sc.revenue, i_current_price, i_wholesale_cost, i_brand")
assert SQL != TEMPLATE_SQL


def params(rng, config):
    return {"dms": rng.choice((1176, 1188, 1200, 1212)), "factor": "0.1"}


def render(p):
    return SQL.format(**p), None


def render_template(p):
    """query65.tpl as it is written."""
    return TEMPLATE_SQL.format(**p), None


def reference(T, p, dtype=np.float64, limit=100):
    """Decimal semantics in whole cents: sums exact, avg rounded half up at the scale of
    its argument, ``revenue <= factor * ave`` compared exactly.  Ties on ORDER_BY are
    broken by the remaining columns; ``limit=None`` gives every row."""
    ss, dd = T.columns("store_sales"), T.columns("date_dim")
    months = dd["d_month_seq"]
    days = dd["d_date_sk"][(months >= p["dms"]) & (months <= p["dms"] + 11)]
    keep = np.isin(ss["ss_sold_date_sk"], days)
    sc = pd.DataFrame({"store": ss["ss_store_sk"][keep], "item": ss["ss_item_sk"][keep],
                       "cents": ss["ss_sales_price"][keep].astype(np.int64)}) \
        .groupby(["store", "item"], sort=False)["cents"].sum().reset_index()
    by_store = sc.groupby("store")["cents"].agg(["sum", "count"])
    ave = (2 * by_store["sum"] + by_store["count"]) // (2 * by_store["count"])  # half up
    factor = Fraction(p["factor"])
    under = sc["cents"].to_numpy() * factor.denominator \
        <= factor.numerator * sc["store"].map(ave).to_numpy()
    j = sc[under].merge(T["store"], left_on="store", right_on="s_store_sk") \
        .merge(T["item"], left_on="item", right_on="i_item_sk")
    hundred = dtype(100)
    for out, cents in (("revenue", "cents"), ("i_current_price", "i_current_price"),
                       ("i_wholesale_cost", "i_wholesale_cost")):
        j[out] = j[cents].to_numpy().astype(dtype) / hundred
    cols = ["s_store_name", "i_item_desc", "revenue", "i_current_price",
            "i_wholesale_cost", "i_brand"]
    j = j.assign(s_store_name=j["s_store_name"].astype(str),
                 i_item_desc=j["i_item_desc"].astype(str), i_brand=j["i_brand"].astype(str))
    j = j.sort_values(cols)[cols]
    return (j if limit is None else j.head(limit)).reset_index(drop=True)

