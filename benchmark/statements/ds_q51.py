"""TPC-DS query 51 (query51.tpl): the (item, day) pairs of one year at which an item's
cumulative web sales stand above its cumulative store sales.  Each channel groups its
year's sales by (item, day) and runs ``sum(sum(price)) over (partition by item order by
day rows between unbounded preceding and current row)``; the two are FULL OUTER JOINed
on (item, day); two running ``max`` windows carry each channel's last cumulative sum
over the days on which only the other channel sold; ``web_cumulative >
store_cumulative``; ORDER BY item, day; LIMIT 100.  The template's one substitution
parameter is DMS, the first ``d_month_seq`` of the year (1200: 1200..1211, as recalled:
the sandbox holds no copy of the specification).

``web_sales`` / ``store_sales`` are NULL wherever only one channel sold that day, which
is most rows, and the harness's comparison (``benchmark/harness/compare.py``) counts a
NULL in a numeric column as a mismatch.  So the cell's text, ``SQL``, departs from the
template in ONE place: its outermost SELECT lists ``coalesce(web_sales, -1)`` and
``coalesce(store_sales, -1)`` where the template's ``select *`` hands the two on as they
are (a cumulative sum of prices is never negative, so NULL-ness stays checked exactly).
CTEs, windows, the outer join, predicate, ORDER BY and LIMIT are the template's; (item,
day) is unique after the join, so the ORDER BY is total.  The template's own text stays
here as ``TEMPLATE_SQL`` (``render_template``) for the tier-1 test that compares NULLs
as NULLs; when the harness compares NULL-aware the cell's text goes back to it
(ROADMAP S9)."""

import numpy as np
import pandas as pd

TABLES = {"web_sales": ["ws_sold_date_sk", "ws_item_sk", "ws_sales_price"],
          "store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_sales_price"],
          "date_dim": ["d_date_sk", "d_date", "d_month_seq"]}
VALIDATION = {"dms": 1200}  # query51.tpl, qualification substitution (as recalled)
NULL_SENTINEL = -1          # what the cell's two coalesces put for NULL

TEMPLATE_SQL = """
WITH web_v1 as (
 select ws_item_sk item_sk, d_date,
        sum(sum(ws_sales_price)) over (partition by ws_item_sk order by d_date rows between unbounded preceding and current row) cume_sales
 from web_sales, date_dim
 where ws_sold_date_sk=d_date_sk and d_month_seq between {dms} and {dms}+11 and ws_item_sk is not NULL
 group by ws_item_sk, d_date),
store_v1 as (
 select ss_item_sk item_sk, d_date,
        sum(sum(ss_sales_price)) over (partition by ss_item_sk order by d_date rows between unbounded preceding and current row) cume_sales
 from store_sales, date_dim
 where ss_sold_date_sk=d_date_sk and d_month_seq between {dms} and {dms}+11 and ss_item_sk is not NULL
 group by ss_item_sk, d_date)
select * from (
 select item_sk, d_date, web_sales, store_sales,
        max(web_sales) over (partition by item_sk order by d_date rows between unbounded preceding and current row) web_cumulative,
        max(store_sales) over (partition by item_sk order by d_date rows between unbounded preceding and current row) store_cumulative
 from (select case when web.item_sk is not null then web.item_sk else store.item_sk end item_sk,
              case when web.d_date is not null then web.d_date else store.d_date end d_date,
              web.cume_sales web_sales, store.cume_sales store_sales
       from web_v1 web full outer join store_v1 store on (web.item_sk = store.item_sk and web.d_date = store.d_date)) x) y
where web_cumulative > store_cumulative
order by item_sk, d_date
limit 100"""
COLUMNS = ["item_sk", "d_date", "web_sales", "store_sales", "web_cumulative",
           "store_cumulative"]
# the cell's text: the template with the two NULL-able columns coalesced (module docstring)
SQL = TEMPLATE_SQL.replace(
    "select * from (",
    "select item_sk, d_date, coalesce(web_sales, -1) web_sales, "
    "coalesce(store_sales, -1) store_sales, web_cumulative, store_cumulative from (")
assert SQL != TEMPLATE_SQL


def params(rng, config):
    return {"dms": rng.randint(1176, 1224)}  # query51.tpl: DMS = random(1176, 1224, uniform)


def render(p):
    return SQL.format(**p), None


def render_template(p):
    """query51.tpl as it is written."""
    return TEMPLATE_SQL.format(**p), None


def _channel(cols, prefix, days, dtype):
    """One channel's CTE: (item, day, cume) with cume the running sum of the day's sum
    of prices over the item's days.  Whole cents in int64, or ``dtype`` arithmetic on
    prices when the control asks for float32."""
    sold = cols[prefix + "_sold_date_sk"]
    keep = np.isin(sold, days.index.to_numpy())
    price = cols[prefix + "_sales_price"][keep].astype(np.int64)
    if dtype != np.float64:
        price = price.astype(dtype) / dtype(100)
    f = pd.DataFrame({"item_sk": cols[prefix + "_item_sk"][keep],
                      "d_date": days.reindex(sold[keep]).to_numpy(), "v": price})
    g = f.groupby(["item_sk", "d_date"], sort=True)["v"].sum().astype(price.dtype).reset_index()
    g["cume"] = g.groupby("item_sk", sort=False)["v"].cumsum().astype(price.dtype)
    return g[["item_sk", "d_date", "cume"]]


def reference(T, p, dtype=np.float64, limit=100, nulls=NULL_SENTINEL):
    """Decimal semantics in whole cents (sums, cumulative sums and the predicate exact)
    when ``dtype`` is float64; the control's float32 sums in float32.  NULLs of
    ``web_sales`` / ``store_sales`` come back as ``nulls`` (the cell's -1; ``None`` asks
    for NaN, the template's NULL); ``limit=None`` gives every row."""
    dtype = np.dtype(dtype).type
    dd = T.columns("date_dim")
    year = (dd["d_month_seq"] >= p["dms"]) & (dd["d_month_seq"] <= p["dms"] + 11)
    days = pd.Series(np.asarray(dd["d_date"])[year].astype("datetime64[D]"),
                     index=dd["d_date_sk"][year])
    web = _channel(T.columns("web_sales"), "ws", days, dtype)
    store = _channel(T.columns("store_sales"), "ss", days, dtype)
    j = web.merge(store, on=["item_sk", "d_date"], how="outer", suffixes=("_w", "_s")) \
        .sort_values(["item_sk", "d_date"], kind="stable").reset_index(drop=True)
    # NULL as -1 (a cumulative sum of prices is never negative): the running max of the
    # non-NULL values so far is then a plain running max, -1 while none was seen
    w = j["cume_w"].fillna(-1)
    s = j["cume_s"].fillna(-1)
    wc = w.groupby(j["item_sk"], sort=False).cummax()
    sc = s.groupby(j["item_sk"], sort=False).cummax()
    keep = ((wc > sc) & (sc >= 0)).to_numpy()  # NULL > x and x > NULL are not true
    scale = dtype(100) if dtype == np.float64 else dtype(1)

    def out(col, null):
        v = col.to_numpy()[keep]
        missing = v < 0
        v = v.astype(dtype) / scale
        if null is None:
            return np.where(missing, np.nan, v)
        return np.where(missing, dtype(null), v)

    f = pd.DataFrame({"item_sk": j["item_sk"].to_numpy()[keep].astype(np.int64),
                      "d_date": j["d_date"].to_numpy()[keep].astype("datetime64[ns]"),
                      "web_sales": out(w, nulls), "store_sales": out(s, nulls),
                      "web_cumulative": out(wc, None), "store_cumulative": out(sc, None)})
    assert list(f.columns) == COLUMNS
    return (f if limit is None else f.head(limit)).reset_index(drop=True)
