"""TPC-DS query 93 (query93.tpl): the net sales of every customer after the returns of one
reason.  The template's one substitution parameter is the reason's description
(qualification value 'reason 28'); ``r_reason_desc`` holds 'reason 1'..'reason 35' in the
repo's generator.

``store_sales left outer join store_returns`` is an inner join under
``sr_reason_sk = r_reason_sk`` (a NULL-extended row has no reason), which is how the
engine plans it; the reference says it as the text does.  ORDER BY sumsales,
ss_customer_sk is total: one row a customer."""

import numpy as np
import pandas as pd

TABLES = {"store_sales": ["ss_item_sk", "ss_customer_sk", "ss_ticket_number", "ss_quantity",
                          "ss_sales_price"],
          "store_returns": ["sr_item_sk", "sr_reason_sk", "sr_ticket_number",
                            "sr_return_quantity"],
          "reason": ["r_reason_sk", "r_reason_desc"]}
VALIDATION = {"reason": "reason 28"}  # query93.tpl, qualification substitution

SQL = """
select ss_customer_sk, sum(act_sales) sumsales
from (select ss_item_sk, ss_ticket_number, ss_customer_sk,
             case when sr_return_quantity is not null
                  then (ss_quantity-sr_return_quantity)*ss_sales_price
                  else (ss_quantity*ss_sales_price) end act_sales
      from store_sales left outer join store_returns
           on (sr_item_sk = ss_item_sk and sr_ticket_number = ss_ticket_number),
           reason
      where sr_reason_sk = r_reason_sk and r_reason_desc = '{reason}') t
group by ss_customer_sk
order by sumsales, ss_customer_sk
limit 100"""


def params(rng, config):
    return {"reason": "reason %d" % rng.randint(1, 35)}


def render(p):
    return SQL.format(**p), None


def reference(T, p, dtype=np.float64):
    r = T["reason"]
    wanted = r["r_reason_sk"][r["r_reason_desc"] == p["reason"]].to_numpy()
    sr = T.columns("store_returns")
    of_reason = np.isin(sr["sr_reason_sk"], wanted)
    r_item = sr["sr_item_sk"][of_reason].astype(np.int64)
    r_ticket = sr["sr_ticket_number"][of_reason].astype(np.int64)
    returns = pd.DataFrame({"item": r_item, "ticket": r_ticket,
                            "returned": sr["sr_return_quantity"][of_reason]})
    # the outer join's rows that the WHERE keeps are those with a return of the reason:
    # only sales of a returned (item, ticket) need to leave the 28.8M-row arrays
    ss = T.columns("store_sales")
    width = max(int(ss["ss_ticket_number"].max(initial=0)), int(r_ticket.max(initial=0))) + 1
    sold = np.isin(ss["ss_item_sk"].astype(np.int64) * width + ss["ss_ticket_number"],
                   r_item * width + r_ticket)
    sales = pd.DataFrame({"item": ss["ss_item_sk"][sold], "ticket": ss["ss_ticket_number"][sold],
                          "customer": ss["ss_customer_sk"][sold],
                          "quantity": ss["ss_quantity"][sold],
                          "price": ss["ss_sales_price"][sold]})
    j = sales.merge(returns, on=["item", "ticket"], how="left")
    kept = np.where(j["returned"].notna(), j["quantity"] - j["returned"].fillna(0),
                    j["quantity"])
    j = j.assign(act=kept.astype(dtype) * (j["price"].to_numpy().astype(dtype) / dtype(100)))
    out = j.groupby("customer")["act"].sum().reset_index()
    out = out.assign(act=out["act"].to_numpy().astype(dtype)) \
        .sort_values(["act", "customer"]).head(100)
    return out.rename(columns={"customer": "ss_customer_sk", "act": "sumsales"})[
        ["ss_customer_sk", "sumsales"]].reset_index(drop=True)
