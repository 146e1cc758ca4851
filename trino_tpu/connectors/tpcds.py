"""TPC-DS connector: deterministic on-device data generation.

Reference: plugin/trino-tpcds (TpcdsConnectorFactory; rows generated per split by
the external `tpcds` generator library, analogous to plugin/trino-tpch —
SURVEY.md §2.11).  Like the TPC-H connector, every column is a jit-compiled
function of the global row index (counter-based splitmix64 streams), so a scan
is itself a TPU kernel and any split regenerates identically.

Covers all 24 TPC-DS tables: the three sales channels (store_sales,
catalog_sales, web_sales) with their returns tables, inventory, and every
dimension (date_dim, time_dim, item, customer, customer_address,
customer_demographics, household_demographics, income_band, store, warehouse,
ship_mode, reason, promotion, call_center, catalog_page, web_site, web_page).
Schemas follow the TPC-DS spec; value distributions are simplified (uniform
over spec domains) where the official generator uses weighted text corpora —
row counts scale per the spec's SF table (store_sales ≈ 2.88M rows/SF,
catalog_sales ≈ 1.44M, web_sales ≈ 0.72M, inventory ≈ 11.7M).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..page import Field, Page, Schema
from ..types import BIGINT, DATE, INTEGER, DecimalType, VarcharType
from .tpch import Dictionary, WarmScans, _enum, _rand, _uniform, parse_date_literal

__all__ = ["TpcdsConnector"]

D72 = DecimalType.of(7, 2)
V = VarcharType.of(None)

# spec row counts at SF1 (scaled tables scale linearly; small dims are fixed)
BASE_ROWS = {
    "store_sales": 2_880_000,
    "customer": 100_000,
    "customer_address": 50_000,
    "item": 18_000,
    "promotion": 300,
    "store": 12,
}
DATE_LO = parse_date_literal("1990-01-01")
DATE_HI = parse_date_literal("2002-12-31")
N_DATES = DATE_HI - DATE_LO + 1  # date_dim rows (sk = julian-style day index)
JULIAN_BASE = 2450000  # d_date_sk offset so sks look spec-like

GENDERS = _enum("M", "F")
MARITAL = _enum("M", "S", "D", "W", "U")
EDUCATION = _enum("Primary", "Secondary", "College", "2 yr Degree", "4 yr Degree",
                  "Advanced Degree", "Unknown")
CREDIT = _enum("Low Risk", "High Risk", "Unknown", "Good")
CATEGORIES = _enum("Home", "Books", "Electronics", "Jewelry", "Music", "Shoes",
                   "Sports", "Women", "Men", "Children")
N_CAT = 10
BRAND_DICT = _enum(*[f"corpbrand #{i}" for i in range(1, 101)])
CLASSES = _enum(*[f"class{i:02d}" for i in range(50)])
MANAGERS = _enum(*[f"Manager {i}" for i in range(1, 101)])
STATES = _enum("TN", "CA", "TX", "NY", "OH", "GA", "IL", "WA", "NC", "VA")
COUNTIES = _enum(*[f"{w} County" for w in
                   ("Williamson", "Ziebach", "Walker", "Daviess", "Barrow",
                    "Franklin", "Luce", "Richland", "Oglethorpe", "Mobile")])
CITIES = _enum(*[f"City{i:03d}" for i in range(200)])
FIRST_NAMES = _enum(*[f"First{i:03d}" for i in range(512)])
LAST_NAMES = _enum(*[f"Last{i:03d}" for i in range(512)])
STORE_NAMES = _enum("ese", "anti", "ought", "able", "pri", "cally", "ation", "eing",
                    "n st", "bar", "cal", "ought2")
PROMO_NAMES = _enum(*[f"promo{i:03d}" for i in range(300)])
ITEM_IDS = _enum(*[f"AAAAAAAA{i:08d}" for i in range(BASE_ROWS["item"])])
CHANNELS = _enum("N", "Y")

# customer_demographics: the spec's full cross product of attribute domains
CD_GENDER, CD_MARITAL, CD_EDU = 2, 5, 7
CD_PURCHASE = 20  # purchase estimate buckets (500..10000 step 500)
CD_CREDIT = 4
CD_DEP, CD_EMP, CD_COLL = 7, 7, 7
CD_ROWS = CD_GENDER * CD_MARITAL * CD_EDU * CD_PURCHASE * CD_CREDIT \
    * CD_DEP * CD_EMP * CD_COLL  # 1,920,800 (spec row count)


def _schema(*fields):
    return Schema(tuple(Field(n, t) for n, t in fields))


SCHEMAS = {
    "date_dim": _schema(
        ("d_date_sk", BIGINT), ("d_date_id", BIGINT), ("d_date", DATE),
        ("d_month_seq", INTEGER), ("d_week_seq", INTEGER), ("d_quarter_seq", INTEGER),
        ("d_year", INTEGER), ("d_dow", INTEGER), ("d_moy", INTEGER),
        ("d_dom", INTEGER), ("d_qoy", INTEGER), ("d_fy_year", INTEGER),
        ("d_day_name", V), ("d_holiday", V), ("d_weekend", V),
        ("d_following_holiday", V), ("d_first_dom", INTEGER),
        ("d_last_dom", INTEGER), ("d_same_day_ly", INTEGER),
        ("d_same_day_lq", INTEGER), ("d_current_day", V), ("d_current_week", V),
        ("d_current_month", V), ("d_current_quarter", V), ("d_current_year", V),
    ),
    "item": _schema(
        ("i_item_sk", BIGINT), ("i_item_id", V), ("i_rec_start_date", DATE),
        ("i_rec_end_date", DATE), ("i_item_desc", V), ("i_current_price", D72),
        ("i_wholesale_cost", D72), ("i_brand_id", INTEGER), ("i_brand", V),
        ("i_class_id", INTEGER), ("i_class", V), ("i_category_id", INTEGER),
        ("i_category", V), ("i_manufact_id", INTEGER), ("i_manufact", V),
        ("i_size", V), ("i_formulation", V), ("i_color", V), ("i_units", V),
        ("i_container", V), ("i_manager_id", INTEGER), ("i_product_name", V),
    ),
    "customer": _schema(
        ("c_customer_sk", BIGINT), ("c_customer_id", BIGINT),
        ("c_current_cdemo_sk", BIGINT), ("c_current_hdemo_sk", BIGINT),
        ("c_current_addr_sk", BIGINT), ("c_first_shipto_date_sk", BIGINT),
        ("c_first_sales_date_sk", BIGINT), ("c_salutation", V),
        ("c_first_name", V), ("c_last_name", V), ("c_preferred_cust_flag", V),
        ("c_birth_day", INTEGER), ("c_birth_month", INTEGER),
        ("c_birth_year", INTEGER), ("c_birth_country", V), ("c_login", V),
        ("c_email_address", V), ("c_last_review_date_sk", BIGINT),
    ),
    "customer_address": _schema(
        ("ca_address_sk", BIGINT), ("ca_address_id", BIGINT),
        ("ca_street_number", INTEGER), ("ca_street_name", V),
        ("ca_street_type", V), ("ca_suite_number", V), ("ca_city", V),
        ("ca_county", V), ("ca_state", V), ("ca_zip", INTEGER), ("ca_country", V),
        ("ca_gmt_offset", DecimalType.of(5, 2)), ("ca_location_type", V),
    ),
    "customer_demographics": _schema(
        ("cd_demo_sk", BIGINT), ("cd_gender", V), ("cd_marital_status", V),
        ("cd_education_status", V), ("cd_purchase_estimate", INTEGER),
        ("cd_credit_rating", V), ("cd_dep_count", INTEGER),
        ("cd_dep_employed_count", INTEGER), ("cd_dep_college_count", INTEGER),
    ),
    "store": _schema(
        ("s_store_sk", BIGINT), ("s_store_id", BIGINT), ("s_rec_start_date", DATE),
        ("s_rec_end_date", DATE), ("s_closed_date_sk", BIGINT), ("s_store_name", V),
        ("s_number_employees", INTEGER), ("s_floor_space", INTEGER),
        ("s_hours", V), ("s_manager", V), ("s_market_id", INTEGER),
        ("s_geography_class", V), ("s_market_desc", V), ("s_market_manager", V),
        ("s_division_id", INTEGER), ("s_division_name", V), ("s_company_id", INTEGER),
        ("s_company_name", V), ("s_street_number", INTEGER), ("s_street_name", V),
        ("s_street_type", V), ("s_suite_number", V), ("s_city", V), ("s_county", V),
        ("s_state", V), ("s_zip", INTEGER), ("s_country", V),
        ("s_gmt_offset", DecimalType.of(5, 2)), ("s_tax_precentage", D72),
    ),
    "promotion": _schema(
        ("p_promo_sk", BIGINT), ("p_promo_id", BIGINT), ("p_start_date_sk", BIGINT),
        ("p_end_date_sk", BIGINT), ("p_item_sk", BIGINT), ("p_cost", D72),
        ("p_response_target", INTEGER), ("p_promo_name", V), ("p_channel_dmail", V),
        ("p_channel_email", V), ("p_channel_catalog", V), ("p_channel_tv", V),
        ("p_channel_radio", V), ("p_channel_press", V), ("p_channel_event", V),
        ("p_channel_demo", V), ("p_channel_details", V), ("p_purpose", V),
        ("p_discount_active", V),
    ),
    "store_sales": _schema(
        ("ss_sold_date_sk", BIGINT), ("ss_sold_time_sk", BIGINT),
        ("ss_item_sk", BIGINT), ("ss_customer_sk", BIGINT), ("ss_cdemo_sk", BIGINT),
        ("ss_hdemo_sk", BIGINT), ("ss_addr_sk", BIGINT), ("ss_store_sk", BIGINT),
        ("ss_promo_sk", BIGINT), ("ss_ticket_number", BIGINT),
        ("ss_quantity", INTEGER), ("ss_wholesale_cost", D72), ("ss_list_price", D72),
        ("ss_sales_price", D72), ("ss_ext_discount_amt", D72),
        ("ss_ext_sales_price", D72), ("ss_ext_wholesale_cost", D72),
        ("ss_ext_list_price", D72), ("ss_ext_tax", D72), ("ss_coupon_amt", D72),
        ("ss_net_paid", D72), ("ss_net_paid_inc_tax", D72), ("ss_net_profit", D72),
    ),
}

DAY_NAMES = _enum("Sunday", "Monday", "Tuesday", "Wednesday", "Thursday", "Friday",
                  "Saturday")
YN = _enum("N", "Y")

DICTS = {
    "date_dim": {"d_day_name": DAY_NAMES, "d_holiday": YN, "d_weekend": YN,
                 "d_following_holiday": YN, "d_current_day": YN,
                 "d_current_week": YN, "d_current_month": YN,
                 "d_current_quarter": YN, "d_current_year": YN},
    "item": {"i_item_id": ITEM_IDS, "i_item_desc": ITEM_IDS, "i_brand": BRAND_DICT,
             "i_class": CLASSES, "i_category": CATEGORIES, "i_manufact": BRAND_DICT,
             "i_size": _enum("small", "medium", "large", "extra large", "petite",
                             "economy", "N/A"),
             "i_formulation": ITEM_IDS, "i_color": _enum(
                 "red", "green", "blue", "yellow", "purple", "white", "black",
                 "orange", "pink", "brown"),
             "i_units": _enum("Each", "Dozen", "Case", "Pallet", "Gross", "Box"),
             "i_container": _enum("Unknown"), "i_product_name": ITEM_IDS},
    "customer": {"c_salutation": _enum("Mr.", "Mrs.", "Ms.", "Dr.", "Miss", "Sir"),
                 "c_first_name": FIRST_NAMES, "c_last_name": LAST_NAMES,
                 "c_preferred_cust_flag": YN,
                 "c_birth_country": _enum("UNITED STATES", "CANADA", "MEXICO",
                                          "GERMANY", "JAPAN", "BRAZIL", "INDIA"),
                 "c_login": FIRST_NAMES, "c_email_address": FIRST_NAMES},
    "customer_address": {"ca_street_name": CITIES,
                         "ca_street_type": _enum("Street", "Ave", "Blvd", "Way",
                                                 "Court", "Lane"),
                         "ca_suite_number": _enum(*[f"Suite {i}" for i in range(50)]),
                         "ca_city": CITIES, "ca_county": COUNTIES,
                         "ca_state": STATES,
                         "ca_country": _enum("United States"),
                         "ca_location_type": _enum("apartment", "condo",
                                                   "single family")},
    "customer_demographics": {"cd_gender": GENDERS, "cd_marital_status": MARITAL,
                              "cd_education_status": EDUCATION,
                              "cd_credit_rating": CREDIT},
    "store": {"s_store_name": STORE_NAMES, "s_hours": _enum("8AM-8PM", "8AM-4PM",
                                                            "8AM-12AM"),
              "s_manager": MANAGERS, "s_geography_class": _enum("Unknown"),
              "s_market_desc": COUNTIES, "s_market_manager": MANAGERS,
              "s_division_name": _enum("Unknown"), "s_company_name": _enum("Unknown"),
              "s_street_name": CITIES, "s_street_type": _enum("Street", "Ave"),
              "s_suite_number": _enum(*[f"Suite {i}" for i in range(50)]),
              "s_city": CITIES, "s_county": COUNTIES, "s_state": STATES,
              "s_country": _enum("United States")},
    "promotion": {"p_promo_name": PROMO_NAMES, "p_channel_dmail": CHANNELS,
                  "p_channel_email": CHANNELS, "p_channel_catalog": CHANNELS,
                  "p_channel_tv": CHANNELS, "p_channel_radio": CHANNELS,
                  "p_channel_press": CHANNELS, "p_channel_event": CHANNELS,
                  "p_channel_demo": CHANNELS, "p_channel_details": PROMO_NAMES,
                  "p_purpose": _enum("Unknown"), "p_discount_active": CHANNELS},
    "store_sales": {},
}


def _ymd(days):
    """Civil (year, month, day) from days-since-epoch (device), in int32
    arithmetic: Howard Hinnant's algorithm as sql/ir._extract_ymd has it, for
    days after 0000-03-01, where no intermediate passes 2^31.  The int64 form
    costs the TPU compiler 204 s for ``ss_sold_date_sk`` alone (nine emulated
    64-bit floor divisions over a split whose first row is traced: 18,372 HLO
    lines); this one compiles in seconds and gives the same days (PERF.md PR 36)."""
    z = days.astype(jnp.int32) + 719468
    era = z // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, yoe + era * 400 + 1, yoe + era * 400)
    return y.astype(jnp.int64), m.astype(jnp.int64), d.astype(jnp.int64)


def _seasonal_date(seed: int, i):
    """Sold-date day index with retail seasonality (reference dsdgen skews
    sales toward the year-end holiday season; round-3's uniform simplification
    made month-window selectivities unrealistic — VERDICT r3 weak #4): a
    uniform base candidate is replaced by a second candidate whenever that
    one lands in October-December, putting ~2.3x per-day weight on Q4 days
    while every calendar day keeps nonzero mass."""
    d1 = _uniform(seed, i, 0, N_DATES - 1)
    d2 = _uniform(seed * 7919 + 13, i, 0, N_DATES - 1)
    _, m2, _ = _ymd((DATE_LO + d2).astype(jnp.int32))
    return jnp.where(m2 >= 10, d2, d1)


# -- per-table generators (row index -> columns) ------------------------------------------
def gen_date_dim(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    days = (DATE_LO + i).astype(jnp.int32)
    y, m, d = _ymd(days)
    dow = ((days.astype(jnp.int64) + 4) % 7).astype(jnp.int32)  # 1970-01-01 = Thursday
    qoy = ((m - 1) // 3 + 1).astype(jnp.int32)
    month_seq = ((y - 1900) * 12 + (m - 1)).astype(jnp.int32)
    week_seq = ((DATE_LO + i) // 7).astype(jnp.int32)
    return {
        "d_date_sk": JULIAN_BASE + i,
        "d_date_id": i,
        "d_date": days,
        "d_month_seq": month_seq,
        "d_week_seq": week_seq,
        "d_quarter_seq": ((y - 1900) * 4 + qoy - 1).astype(jnp.int32),
        "d_year": y.astype(jnp.int32),
        "d_dow": dow,
        "d_moy": m.astype(jnp.int32),
        "d_dom": d.astype(jnp.int32),
        "d_qoy": qoy,
        "d_fy_year": y.astype(jnp.int32),
        "d_day_name": dow.astype(jnp.int32),
        "d_holiday": (jnp.logical_and(m == 12, d == 25)).astype(jnp.int32),
        "d_weekend": (jnp.logical_or(dow == 0, dow == 6)).astype(jnp.int32),
        "d_following_holiday": (jnp.logical_and(m == 12, d == 26)).astype(jnp.int32),
        "d_first_dom": (days - d + 1).astype(jnp.int32),
        "d_last_dom": (days + 27).astype(jnp.int32),
        "d_same_day_ly": (days - 365).astype(jnp.int32),
        "d_same_day_lq": (days - 91).astype(jnp.int32),
        "d_current_day": jnp.zeros(length, jnp.int32),
        "d_current_week": jnp.zeros(length, jnp.int32),
        "d_current_month": jnp.zeros(length, jnp.int32),
        "d_current_quarter": jnp.zeros(length, jnp.int32),
        "d_current_year": jnp.zeros(length, jnp.int32),
    }


def gen_item(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    sk = i + 1
    brand_id = _uniform(101, sk, 1, 100).astype(jnp.int32)
    class_id = _uniform(102, sk, 1, 50).astype(jnp.int32)
    cat_id = (sk % N_CAT).astype(jnp.int32) + 1
    manu_id = _uniform(104, sk, 1, 100).astype(jnp.int32)
    price = _uniform(105, sk, 99, 9999)
    return {
        "i_item_sk": sk,
        "i_item_id": (i % BASE_ROWS["item"]).astype(jnp.int32),
        "i_rec_start_date": jnp.full(length, DATE_LO, jnp.int32),
        "i_rec_end_date": jnp.full(length, DATE_HI, jnp.int32),
        "i_item_desc": (i % BASE_ROWS["item"]).astype(jnp.int32),
        "i_current_price": price,
        "i_wholesale_cost": (price * 6) // 10,
        "i_brand_id": brand_id,
        "i_brand": brand_id - 1,
        "i_class_id": class_id,
        "i_class": class_id - 1,
        "i_category_id": cat_id,
        "i_category": cat_id - 1,
        "i_manufact_id": manu_id,
        "i_manufact": manu_id - 1,
        "i_size": (sk % 7).astype(jnp.int32),
        "i_formulation": (i % BASE_ROWS["item"]).astype(jnp.int32),
        "i_color": (sk % 10).astype(jnp.int32),
        "i_units": (sk % 6).astype(jnp.int32),
        "i_container": jnp.zeros(length, jnp.int32),
        "i_manager_id": _uniform(106, sk, 1, 100).astype(jnp.int32),
        "i_product_name": (i % BASE_ROWS["item"]).astype(jnp.int32),
    }


def gen_customer(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    sk = i + 1
    n_addr = max(int(BASE_ROWS["customer_address"] * sf), 1)
    return {
        "c_customer_sk": sk,
        "c_customer_id": sk,
        "c_current_cdemo_sk": _uniform(201, sk, 1, CD_ROWS),
        "c_current_hdemo_sk": _uniform(202, sk, 1, 7200),
        "c_current_addr_sk": _uniform(203, sk, 1, n_addr),
        "c_first_shipto_date_sk": JULIAN_BASE + _uniform(204, sk, 0, N_DATES - 1),
        "c_first_sales_date_sk": JULIAN_BASE + _uniform(205, sk, 0, N_DATES - 1),
        "c_salutation": (sk % 6).astype(jnp.int32),
        "c_first_name": (_uniform(206, sk, 0, 511)).astype(jnp.int32),
        "c_last_name": (_uniform(207, sk, 0, 511)).astype(jnp.int32),
        "c_preferred_cust_flag": (sk % 2).astype(jnp.int32),
        "c_birth_day": _uniform(208, sk, 1, 28).astype(jnp.int32),
        "c_birth_month": _uniform(209, sk, 1, 12).astype(jnp.int32),
        "c_birth_year": _uniform(210, sk, 1930, 1990).astype(jnp.int32),
        "c_birth_country": (sk % 7).astype(jnp.int32),
        "c_login": (_uniform(206, sk, 0, 511)).astype(jnp.int32),
        "c_email_address": (_uniform(206, sk, 0, 511)).astype(jnp.int32),
        "c_last_review_date_sk": JULIAN_BASE + _uniform(211, sk, 0, N_DATES - 1),
    }


def gen_customer_address(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    sk = i + 1
    return {
        "ca_address_sk": sk,
        "ca_address_id": sk,
        "ca_street_number": _uniform(301, sk, 1, 999).astype(jnp.int32),
        "ca_street_name": (_uniform(302, sk, 0, 199)).astype(jnp.int32),
        "ca_street_type": (sk % 6).astype(jnp.int32),
        "ca_suite_number": (sk % 50).astype(jnp.int32),
        "ca_city": (_uniform(303, sk, 0, 199)).astype(jnp.int32),
        "ca_county": (sk % 10).astype(jnp.int32),
        "ca_state": (_uniform(304, sk, 0, 9)).astype(jnp.int32),
        "ca_zip": _uniform(305, sk, 10000, 99999).astype(jnp.int32),
        "ca_country": jnp.zeros(length, jnp.int32),
        "ca_gmt_offset": jnp.full(length, -500, jnp.int64),
        "ca_location_type": (sk % 3).astype(jnp.int32),
    }


def gen_customer_demographics(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    sk = i + 1
    # cross-product decomposition of the demo key (spec: cd is the full cross join)
    r = i
    gender = (r % CD_GENDER).astype(jnp.int32); r = r // CD_GENDER
    marital = (r % CD_MARITAL).astype(jnp.int32); r = r // CD_MARITAL
    edu = (r % CD_EDU).astype(jnp.int32); r = r // CD_EDU
    purchase = (r % CD_PURCHASE).astype(jnp.int32); r = r // CD_PURCHASE
    credit = (r % CD_CREDIT).astype(jnp.int32); r = r // CD_CREDIT
    dep = (r % CD_DEP).astype(jnp.int32); r = r // CD_DEP
    emp = (r % CD_EMP).astype(jnp.int32); r = r // CD_EMP
    coll = (r % CD_COLL).astype(jnp.int32)
    return {
        "cd_demo_sk": sk,
        "cd_gender": gender,
        "cd_marital_status": marital,
        "cd_education_status": edu,
        "cd_purchase_estimate": (purchase + 1) * 500,
        "cd_credit_rating": credit,
        "cd_dep_count": dep,
        "cd_dep_employed_count": emp,
        "cd_dep_college_count": coll,
    }


def gen_store(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    sk = i + 1
    return {
        "s_store_sk": sk,
        "s_store_id": sk,
        "s_rec_start_date": jnp.full(length, DATE_LO, jnp.int32),
        "s_rec_end_date": jnp.full(length, DATE_HI, jnp.int32),
        "s_closed_date_sk": jnp.zeros(length, jnp.int64),
        "s_store_name": (i % 12).astype(jnp.int32),
        "s_number_employees": _uniform(401, sk, 200, 300).astype(jnp.int32),
        "s_floor_space": _uniform(402, sk, 5_000_000, 9_999_999).astype(jnp.int32),
        "s_hours": (sk % 3).astype(jnp.int32),
        "s_manager": (_uniform(403, sk, 0, 99)).astype(jnp.int32),
        "s_market_id": _uniform(404, sk, 1, 10).astype(jnp.int32),
        "s_geography_class": jnp.zeros(length, jnp.int32),
        "s_market_desc": (sk % 10).astype(jnp.int32),
        "s_market_manager": (_uniform(405, sk, 0, 99)).astype(jnp.int32),
        "s_division_id": jnp.ones(length, jnp.int32),
        "s_division_name": jnp.zeros(length, jnp.int32),
        "s_company_id": jnp.ones(length, jnp.int32),
        "s_company_name": jnp.zeros(length, jnp.int32),
        "s_street_number": _uniform(406, sk, 1, 999).astype(jnp.int32),
        "s_street_name": (_uniform(407, sk, 0, 199)).astype(jnp.int32),
        "s_street_type": (sk % 2).astype(jnp.int32),
        "s_suite_number": (sk % 50).astype(jnp.int32),
        "s_city": (_uniform(408, sk, 0, 199)).astype(jnp.int32),
        "s_county": (sk % 10).astype(jnp.int32),
        "s_state": (sk % 10).astype(jnp.int32),
        "s_zip": _uniform(409, sk, 10000, 99999).astype(jnp.int32),
        "s_country": jnp.zeros(length, jnp.int32),
        "s_gmt_offset": jnp.full(length, -500, jnp.int64),
        "s_tax_precentage": _uniform(410, sk, 0, 11),
    }


def gen_promotion(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    sk = i + 1
    n_item = max(int(BASE_ROWS["item"] * sf), 1)
    start = JULIAN_BASE + _uniform(501, sk, 0, N_DATES - 60)
    return {
        "p_promo_sk": sk,
        "p_promo_id": sk,
        "p_start_date_sk": start,
        "p_end_date_sk": start + _uniform(502, sk, 10, 60),
        "p_item_sk": _uniform(503, sk, 1, n_item),
        "p_cost": jnp.full(length, 100000, jnp.int64),
        "p_response_target": jnp.ones(length, jnp.int32),
        "p_promo_name": (i % 300).astype(jnp.int32),
        "p_channel_dmail": (sk % 2).astype(jnp.int32),
        "p_channel_email": ((sk // 2) % 2).astype(jnp.int32),
        "p_channel_catalog": ((sk // 4) % 2).astype(jnp.int32),
        "p_channel_tv": ((sk // 8) % 2).astype(jnp.int32),
        "p_channel_radio": ((sk // 16) % 2).astype(jnp.int32),
        "p_channel_press": ((sk // 32) % 2).astype(jnp.int32),
        "p_channel_event": ((sk // 64) % 2).astype(jnp.int32),
        "p_channel_demo": ((sk // 128) % 2).astype(jnp.int32),
        "p_channel_details": (i % 300).astype(jnp.int32),
        "p_purpose": jnp.zeros(length, jnp.int32),
        "p_discount_active": (sk % 2).astype(jnp.int32),
    }


def _ticket(i, n_rows: int):
    """``i // 12``, the ticket of sale row ``i`` (from 0).  64-bit integers
    are emulated on the TPU and a division of them costs its compiler seconds
    (6 s here, 15 on the chip's host, for this one): where the table's row
    indexes fit 32 bits the division is made there, the same quotient."""
    if n_rows < 1 << 30:  # (a split's masked tail runs past the last row)
        return (i.astype(jnp.int32) // 12).astype(jnp.int64)
    return i // 12


def _ticket_item(i, n_items: int, n_rows: int):
    """``ss_item_sk`` of sale row ``i`` (of ``n_rows``): the twelve lines of a ticket
    (``i // 12``) take twelve DIFFERENT items, a random first one and a random
    stride of at most a twelfth of the item table, so (``ss_item_sk``,
    ``ss_ticket_number``) is the primary key the specification says it is
    (dsdgen permutes the items of a ticket).  Below twelve items they
    repeat."""
    t = _ticket(i, n_rows)
    item = _uniform(608, t, 0, n_items - 1) + (i - 12 * t) * _uniform(
        615, t, 1, max(n_items // 12, 1))  # (under 2 x n_items)
    return jnp.where(item >= n_items, item - n_items, item) + 1


def _returned_sale(sf, j):
    """The ``store_sales`` row that ``store_returns`` row ``j`` returns: one
    of every ten consecutive sales, so no sale is returned twice and about a
    tenth of the sales have a return (dsdgen returns a tenth of its sale
    lines, each return copying its sale's item, ticket, customer and store).
    The returned QUANTITY stays independent of the sold one (1..20 of 1..100,
    where dsdgen draws 1..ss_quantity): with dsdgen's rule a twentieth of the
    returns bring everything back, and TPC-DS q93's first hundred rows at
    scale 10 are all 0.00, an answer no precision can get wrong."""
    return jnp.minimum(10 * j + _uniform(2812, j, 0, 9),
                       _scaled_rows("store_sales", sf) - 1)


def gen_store_sales(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    fk = _fk_counts(sf)
    n_sales = _scaled_rows("store_sales", sf)
    # _sale_measures(601) reproduces the historical seed layout bit-for-bit
    # (601 qty .. 605 coupon); its ship measure (seed 606) is unused here and
    # dead-code-eliminated by jit, so the seed overlap with ss_sold_date_sk
    # is harmless
    m = _sale_measures(601, i)
    return {
        "ss_sold_date_sk": JULIAN_BASE + _seasonal_date(606, i),
        "ss_sold_time_sk": _uniform(607, i, 28800, 75600),
        "ss_item_sk": _ticket_item(i, fk["item"], n_sales),
        "ss_customer_sk": _uniform(609, i, 1, fk["customer"]),
        "ss_cdemo_sk": _uniform(610, i, 1, CD_ROWS),
        "ss_hdemo_sk": _uniform(611, i, 1, fk["hd"]),
        "ss_addr_sk": _uniform(612, i, 1, fk["addr"]),
        "ss_store_sk": _uniform(613, i, 1, fk["store"]),
        "ss_promo_sk": _uniform(614, i, 1, fk["promo"]),
        "ss_ticket_number": _ticket(i, n_sales) + 1,
        "ss_quantity": m["quantity"],
        "ss_wholesale_cost": m["wholesale_cost"],
        "ss_list_price": m["list_price"],
        "ss_sales_price": m["sales_price"],
        "ss_ext_discount_amt": m["ext_discount_amt"],
        "ss_ext_sales_price": m["ext_sales_price"],
        "ss_ext_wholesale_cost": m["ext_wholesale_cost"],
        "ss_ext_list_price": m["ext_list_price"],
        "ss_ext_tax": m["ext_tax"],
        "ss_coupon_amt": m["coupon_amt"],
        "ss_net_paid": m["net_paid"],
        "ss_net_paid_inc_tax": m["net_paid_inc_tax"],
        "ss_net_profit": m["net_profit"],
    }


# -- round-3 breadth: the catalog and web channels, returns, inventory, and the
# remaining dimensions (24 tables total — the full TPC-DS vocabulary minus
# dbgen text corpora; distributions stay simplified-uniform as documented)

BASE_ROWS.update({
    "catalog_sales": 1_441_548, "catalog_returns": 144_067,
    "web_sales": 719_384, "web_returns": 71_763,
    "store_returns": 287_514, "inventory": 11_745_000,
    "catalog_page": 11_718, "warehouse": 5, "web_site": 30, "web_page": 60,
    "call_center": 6,
})
FIXED_ROWS = {"time_dim": 86_400, "household_demographics": 7_200,
              "income_band": 20, "ship_mode": 20, "reason": 35}
MIN_SCALED = {"store": 1 / 12, "promotion": 1 / 300, "warehouse": 1 / 5,
              "web_site": 1 / 30, "web_page": 1 / 60, "call_center": 1 / 6,
              "catalog_page": 1 / 11_718}

D52 = DecimalType.of(5, 2)
SHIP_TYPES = _enum("EXPRESS", "NEXT DAY", "OVERNIGHT", "REGULAR", "TWO DAY")
CARRIERS = _enum("UPS", "FEDEX", "AIRBORNE", "USPS", "DHL", "TBS", "ZHOU",
                 "LATVIAN", "DIAMOND", "ALLIANCE")
REASONS = _enum(*[f"reason {i}" for i in range(1, 36)])
BUY_POTENTIAL = _enum(">10000", "5001-10000", "1001-5000", "501-1000",
                      "0-500", "Unknown")
SHIFTS = _enum("first", "second", "third")
MEALS = _enum("breakfast", "lunch", "dinner", "")
AMPM = _enum("AM", "PM")
WAREHOUSE_NAMES = _enum("Conventional childr", "Important issues liv",
                        "Doors canno", "Bad cards must make.", "eing")
URLS = _enum("http://www.foo.com", "http://www.bar.com")
PAGE_TYPES = _enum("ad", "bio", "feedback", "general", "order", "protected",
                   "welcome")
DEPARTMENTS = _enum("DEPARTMENT")
CC_NAMES = _enum("NY Metro", "Mid Atlantic", "Pacific Northwest",
                 "North Midwest", "California", "Hawaii/Alaska")
CC_CLASSES = _enum("small", "medium", "large")
CATALOG_TYPES = _enum("bi-annual", "quarterly", "monthly")

SCHEMAS.update({
    "warehouse": _schema(
        ("w_warehouse_sk", BIGINT), ("w_warehouse_id", BIGINT),
        ("w_warehouse_name", V), ("w_warehouse_sq_ft", INTEGER),
        ("w_street_number", INTEGER), ("w_street_name", V),
        ("w_street_type", V), ("w_suite_number", V), ("w_city", V),
        ("w_county", V), ("w_state", V), ("w_zip", INTEGER), ("w_country", V),
        ("w_gmt_offset", D52),
    ),
    "ship_mode": _schema(
        ("sm_ship_mode_sk", BIGINT), ("sm_ship_mode_id", BIGINT),
        ("sm_type", V), ("sm_code", V), ("sm_carrier", V), ("sm_contract", V),
    ),
    "reason": _schema(
        ("r_reason_sk", BIGINT), ("r_reason_id", BIGINT),
        ("r_reason_desc", V),
    ),
    "income_band": _schema(
        ("ib_income_band_sk", BIGINT), ("ib_lower_bound", INTEGER),
        ("ib_upper_bound", INTEGER),
    ),
    "household_demographics": _schema(
        ("hd_demo_sk", BIGINT), ("hd_income_band_sk", BIGINT),
        ("hd_buy_potential", V), ("hd_dep_count", INTEGER),
        ("hd_vehicle_count", INTEGER),
    ),
    "time_dim": _schema(
        ("t_time_sk", BIGINT), ("t_time_id", BIGINT), ("t_time", INTEGER),
        ("t_hour", INTEGER), ("t_minute", INTEGER), ("t_second", INTEGER),
        ("t_am_pm", V), ("t_shift", V), ("t_sub_shift", V), ("t_meal_time", V),
    ),
    "web_site": _schema(
        ("web_site_sk", BIGINT), ("web_site_id", BIGINT),
        ("web_rec_start_date", DATE), ("web_rec_end_date", DATE),
        ("web_name", V), ("web_open_date_sk", BIGINT),
        ("web_close_date_sk", BIGINT), ("web_class", V), ("web_manager", V),
        ("web_mkt_id", INTEGER), ("web_mkt_class", V), ("web_mkt_desc", V),
        ("web_market_manager", V), ("web_company_id", INTEGER),
        ("web_company_name", V), ("web_street_number", INTEGER),
        ("web_street_name", V), ("web_street_type", V),
        ("web_suite_number", V), ("web_city", V), ("web_county", V),
        ("web_state", V), ("web_zip", INTEGER), ("web_country", V),
        ("web_gmt_offset", D52), ("web_tax_percentage", D72),
    ),
    "web_page": _schema(
        ("wp_web_page_sk", BIGINT), ("wp_web_page_id", BIGINT),
        ("wp_rec_start_date", DATE), ("wp_rec_end_date", DATE),
        ("wp_creation_date_sk", BIGINT), ("wp_access_date_sk", BIGINT),
        ("wp_autogen_flag", V), ("wp_customer_sk", BIGINT), ("wp_url", V),
        ("wp_type", V), ("wp_char_count", INTEGER), ("wp_link_count", INTEGER),
        ("wp_image_count", INTEGER), ("wp_max_ad_count", INTEGER),
    ),
    "call_center": _schema(
        ("cc_call_center_sk", BIGINT), ("cc_call_center_id", BIGINT),
        ("cc_rec_start_date", DATE), ("cc_rec_end_date", DATE),
        ("cc_closed_date_sk", BIGINT), ("cc_open_date_sk", BIGINT),
        ("cc_name", V), ("cc_class", V), ("cc_employees", INTEGER),
        ("cc_sq_ft", INTEGER), ("cc_hours", V), ("cc_manager", V),
        ("cc_mkt_id", INTEGER), ("cc_mkt_class", V), ("cc_mkt_desc", V),
        ("cc_market_manager", V), ("cc_division", INTEGER),
        ("cc_division_name", V), ("cc_company", INTEGER),
        ("cc_company_name", V), ("cc_street_number", INTEGER),
        ("cc_street_name", V), ("cc_street_type", V), ("cc_suite_number", V),
        ("cc_city", V), ("cc_county", V), ("cc_state", V), ("cc_zip", INTEGER),
        ("cc_country", V), ("cc_gmt_offset", D52), ("cc_tax_percentage", D72),
    ),
    "catalog_page": _schema(
        ("cp_catalog_page_sk", BIGINT), ("cp_catalog_page_id", BIGINT),
        ("cp_start_date_sk", BIGINT), ("cp_end_date_sk", BIGINT),
        ("cp_department", V), ("cp_catalog_number", INTEGER),
        ("cp_catalog_page_number", INTEGER), ("cp_description", V),
        ("cp_type", V),
    ),
    "inventory": _schema(
        ("inv_date_sk", BIGINT), ("inv_item_sk", BIGINT),
        ("inv_warehouse_sk", BIGINT), ("inv_quantity_on_hand", INTEGER),
    ),
    "catalog_sales": _schema(
        ("cs_sold_date_sk", BIGINT), ("cs_sold_time_sk", BIGINT),
        ("cs_ship_date_sk", BIGINT), ("cs_bill_customer_sk", BIGINT),
        ("cs_bill_cdemo_sk", BIGINT), ("cs_bill_hdemo_sk", BIGINT),
        ("cs_bill_addr_sk", BIGINT), ("cs_ship_customer_sk", BIGINT),
        ("cs_ship_cdemo_sk", BIGINT), ("cs_ship_hdemo_sk", BIGINT),
        ("cs_ship_addr_sk", BIGINT), ("cs_call_center_sk", BIGINT),
        ("cs_catalog_page_sk", BIGINT), ("cs_ship_mode_sk", BIGINT),
        ("cs_warehouse_sk", BIGINT), ("cs_item_sk", BIGINT),
        ("cs_promo_sk", BIGINT), ("cs_order_number", BIGINT),
        ("cs_quantity", INTEGER), ("cs_wholesale_cost", D72),
        ("cs_list_price", D72), ("cs_sales_price", D72),
        ("cs_ext_discount_amt", D72), ("cs_ext_sales_price", D72),
        ("cs_ext_wholesale_cost", D72), ("cs_ext_list_price", D72),
        ("cs_ext_tax", D72), ("cs_coupon_amt", D72), ("cs_ext_ship_cost", D72),
        ("cs_net_paid", D72), ("cs_net_paid_inc_tax", D72),
        ("cs_net_paid_inc_ship", D72), ("cs_net_paid_inc_ship_tax", D72),
        ("cs_net_profit", D72),
    ),
    "web_sales": _schema(
        ("ws_sold_date_sk", BIGINT), ("ws_sold_time_sk", BIGINT),
        ("ws_ship_date_sk", BIGINT), ("ws_item_sk", BIGINT),
        ("ws_bill_customer_sk", BIGINT), ("ws_bill_cdemo_sk", BIGINT),
        ("ws_bill_hdemo_sk", BIGINT), ("ws_bill_addr_sk", BIGINT),
        ("ws_ship_customer_sk", BIGINT), ("ws_ship_cdemo_sk", BIGINT),
        ("ws_ship_hdemo_sk", BIGINT), ("ws_ship_addr_sk", BIGINT),
        ("ws_web_page_sk", BIGINT), ("ws_web_site_sk", BIGINT),
        ("ws_ship_mode_sk", BIGINT), ("ws_warehouse_sk", BIGINT),
        ("ws_promo_sk", BIGINT), ("ws_order_number", BIGINT),
        ("ws_quantity", INTEGER), ("ws_wholesale_cost", D72),
        ("ws_list_price", D72), ("ws_sales_price", D72),
        ("ws_ext_discount_amt", D72), ("ws_ext_sales_price", D72),
        ("ws_ext_wholesale_cost", D72), ("ws_ext_list_price", D72),
        ("ws_ext_tax", D72), ("ws_coupon_amt", D72), ("ws_ext_ship_cost", D72),
        ("ws_net_paid", D72), ("ws_net_paid_inc_tax", D72),
        ("ws_net_paid_inc_ship", D72), ("ws_net_paid_inc_ship_tax", D72),
        ("ws_net_profit", D72),
    ),
    "store_returns": _schema(
        ("sr_returned_date_sk", BIGINT), ("sr_return_time_sk", BIGINT),
        ("sr_item_sk", BIGINT), ("sr_customer_sk", BIGINT),
        ("sr_cdemo_sk", BIGINT), ("sr_hdemo_sk", BIGINT),
        ("sr_addr_sk", BIGINT), ("sr_store_sk", BIGINT),
        ("sr_reason_sk", BIGINT), ("sr_ticket_number", BIGINT),
        ("sr_return_quantity", INTEGER), ("sr_return_amt", D72),
        ("sr_return_tax", D72), ("sr_return_amt_inc_tax", D72),
        ("sr_fee", D72), ("sr_return_ship_cost", D72),
        ("sr_refunded_cash", D72), ("sr_reversed_charge", D72),
        ("sr_store_credit", D72), ("sr_net_loss", D72),
    ),
    "catalog_returns": _schema(
        ("cr_returned_date_sk", BIGINT), ("cr_returned_time_sk", BIGINT),
        ("cr_item_sk", BIGINT), ("cr_refunded_customer_sk", BIGINT),
        ("cr_refunded_cdemo_sk", BIGINT), ("cr_refunded_hdemo_sk", BIGINT),
        ("cr_refunded_addr_sk", BIGINT), ("cr_returning_customer_sk", BIGINT),
        ("cr_returning_cdemo_sk", BIGINT), ("cr_returning_hdemo_sk", BIGINT),
        ("cr_returning_addr_sk", BIGINT), ("cr_call_center_sk", BIGINT),
        ("cr_catalog_page_sk", BIGINT), ("cr_ship_mode_sk", BIGINT),
        ("cr_warehouse_sk", BIGINT), ("cr_reason_sk", BIGINT),
        ("cr_order_number", BIGINT), ("cr_return_quantity", INTEGER),
        ("cr_return_amount", D72), ("cr_return_tax", D72),
        ("cr_return_amt_inc_tax", D72), ("cr_fee", D72),
        ("cr_return_ship_cost", D72), ("cr_refunded_cash", D72),
        ("cr_reversed_charge", D72), ("cr_store_credit", D72),
        ("cr_net_loss", D72),
    ),
    "web_returns": _schema(
        ("wr_returned_date_sk", BIGINT), ("wr_returned_time_sk", BIGINT),
        ("wr_item_sk", BIGINT), ("wr_refunded_customer_sk", BIGINT),
        ("wr_refunded_cdemo_sk", BIGINT), ("wr_refunded_hdemo_sk", BIGINT),
        ("wr_refunded_addr_sk", BIGINT), ("wr_returning_customer_sk", BIGINT),
        ("wr_returning_cdemo_sk", BIGINT), ("wr_returning_hdemo_sk", BIGINT),
        ("wr_returning_addr_sk", BIGINT), ("wr_web_page_sk", BIGINT),
        ("wr_reason_sk", BIGINT), ("wr_order_number", BIGINT),
        ("wr_return_quantity", INTEGER), ("wr_return_amt", D72),
        ("wr_return_tax", D72), ("wr_return_amt_inc_tax", D72),
        ("wr_fee", D72), ("wr_return_ship_cost", D72),
        ("wr_refunded_cash", D72), ("wr_reversed_charge", D72),
        ("wr_account_credit", D72), ("wr_net_loss", D72),
    ),
})

DICTS.update({
    "warehouse": {"w_warehouse_name": WAREHOUSE_NAMES, "w_street_name": CITIES,
                  "w_street_type": _enum("Street", "Ave"), "w_city": CITIES,
                  "w_suite_number": _enum(*[f"Suite {i}" for i in range(50)]),
                  "w_county": COUNTIES, "w_state": STATES,
                  "w_country": _enum("United States")},
    "ship_mode": {"sm_type": SHIP_TYPES, "sm_code": _enum("AIR", "SURFACE",
                                                          "SEA"),
                  "sm_carrier": CARRIERS,
                  "sm_contract": _enum(*[f"contract{i}" for i in range(20)])},
    "reason": {"r_reason_desc": REASONS},
    "income_band": {},
    "household_demographics": {"hd_buy_potential": BUY_POTENTIAL},
    "time_dim": {"t_am_pm": AMPM, "t_shift": SHIFTS, "t_sub_shift": SHIFTS,
                 "t_meal_time": MEALS},
    "web_site": {"web_name": _enum(*[f"site_{i}" for i in range(30)]),
                 "web_class": _enum("Unknown"), "web_manager": MANAGERS,
                 "web_mkt_class": COUNTIES, "web_mkt_desc": COUNTIES,
                 "web_market_manager": MANAGERS,
                 "web_company_name": STORE_NAMES, "web_street_name": CITIES,
                 "web_street_type": _enum("Street", "Ave"),
                 "web_suite_number": _enum(*[f"Suite {i}" for i in range(50)]),
                 "web_city": CITIES, "web_county": COUNTIES,
                 "web_state": STATES, "web_country": _enum("United States")},
    "web_page": {"wp_autogen_flag": YN, "wp_url": URLS, "wp_type": PAGE_TYPES},
    "call_center": {"cc_name": CC_NAMES, "cc_class": CC_CLASSES,
                    "cc_hours": _enum("8AM-8PM", "8AM-4PM", "8AM-12AM"),
                    "cc_manager": MANAGERS, "cc_mkt_class": COUNTIES,
                    "cc_mkt_desc": COUNTIES, "cc_market_manager": MANAGERS,
                    "cc_division_name": STORE_NAMES,
                    "cc_company_name": STORE_NAMES, "cc_street_name": CITIES,
                    "cc_street_type": _enum("Street", "Ave"),
                    "cc_suite_number": _enum(*[f"Suite {i}"
                                               for i in range(50)]),
                    "cc_city": CITIES, "cc_county": COUNTIES,
                    "cc_state": STATES, "cc_country": _enum("United States")},
    "catalog_page": {"cp_department": DEPARTMENTS, "cp_description": ITEM_IDS,
                     "cp_type": CATALOG_TYPES},
    "inventory": {}, "catalog_sales": {}, "web_sales": {},
    "store_returns": {}, "catalog_returns": {}, "web_returns": {},
})


def _scaled_rows(table: str, sf: float) -> int:
    """The ONE row-count rule (shared by row_count and FK domains, so a ratio
    edit can never leave dangling foreign keys)."""
    if table in FIXED_ROWS:
        return FIXED_ROWS[table]
    if table in MIN_SCALED:
        return max(int(round(BASE_ROWS[table] * max(sf, MIN_SCALED[table]))), 1)
    return max(int(BASE_ROWS[table] * sf), 1)


def _fk_counts(sf):
    """Scaled FK domain sizes shared by every fact generator."""
    return {
        "item": _scaled_rows("item", sf),
        "customer": _scaled_rows("customer", sf),
        "addr": _scaled_rows("customer_address", sf),
        "store": _scaled_rows("store", sf),
        "promo": _scaled_rows("promotion", sf),
        "warehouse": _scaled_rows("warehouse", sf),
        "web_page": _scaled_rows("web_page", sf),
        "web_site": _scaled_rows("web_site", sf),
        "cc": _scaled_rows("call_center", sf),
        "cp": _scaled_rows("catalog_page", sf),
        "hd": FIXED_ROWS["household_demographics"],
        "ship_mode": FIXED_ROWS["ship_mode"],
        "reason": FIXED_ROWS["reason"],
    }


def _sale_measures(seed, i):
    """The shared pricing waterfall every sales channel applies (quantities,
    list/sales prices, extensions, tax, coupon, net) — cents-scaled ints."""
    qty = _uniform(seed, i, 1, 100).astype(jnp.int32)
    wholesale = _uniform(seed + 1, i, 100, 10000)
    markup = _uniform(seed + 2, i, 100, 200)
    list_price = (wholesale * markup) // 100
    discount = _uniform(seed + 3, i, 0, 90)
    sales_price = (list_price * (100 - discount)) // 100
    q64 = qty.astype(jnp.int64)
    ext_list = list_price * q64
    ext_sales = sales_price * q64
    ext_wholesale = wholesale * q64
    tax = (ext_sales * 8) // 100
    coupon = jnp.where(_uniform(seed + 4, i, 0, 9) == 0, ext_sales // 10, 0)
    ship = (ext_sales * _uniform(seed + 5, i, 0, 20)) // 100
    net_paid = ext_sales - coupon
    return {
        "quantity": qty, "wholesale_cost": wholesale,
        "list_price": list_price, "sales_price": sales_price,
        "ext_discount_amt": ext_list - ext_sales,
        "ext_sales_price": ext_sales, "ext_wholesale_cost": ext_wholesale,
        "ext_list_price": ext_list, "ext_tax": tax, "coupon_amt": coupon,
        "ext_ship_cost": ship, "net_paid": net_paid,
        "net_paid_inc_tax": net_paid + tax,
        "net_paid_inc_ship": net_paid + ship,
        "net_paid_inc_ship_tax": net_paid + ship + tax,
        "net_profit": net_paid - ext_wholesale,
    }


def _return_measures(seed, i):
    qty = _uniform(seed, i, 1, 20).astype(jnp.int32)
    amt = _uniform(seed + 1, i, 100, 20000) * qty.astype(jnp.int64)
    tax = (amt * 8) // 100
    fee = _uniform(seed + 2, i, 50, 10000)
    ship = (amt * _uniform(seed + 3, i, 0, 20)) // 100
    cash = (amt * _uniform(seed + 4, i, 0, 100)) // 100
    reversed_c = (amt - cash) // 2
    credit = amt - cash - reversed_c
    return {"quantity": qty, "amt": amt, "tax": tax,
            "amt_inc_tax": amt + tax, "fee": fee, "ship": ship,
            "cash": cash, "reversed": reversed_c, "credit": credit,
            "loss": amt + tax + fee + ship - cash}


def gen_warehouse(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    sk = i + 1
    return {
        "w_warehouse_sk": sk, "w_warehouse_id": sk,
        "w_warehouse_name": (i % 5).astype(jnp.int32),
        "w_warehouse_sq_ft": _uniform(2001, i, 50_000, 1_000_000).astype(jnp.int32),
        "w_street_number": _uniform(2002, i, 1, 999).astype(jnp.int32),
        "w_street_name": (i % 200).astype(jnp.int32),
        "w_street_type": (i % 2).astype(jnp.int32),
        "w_suite_number": (i % 50).astype(jnp.int32),
        "w_city": (i % 200).astype(jnp.int32),
        "w_county": (i % 10).astype(jnp.int32),
        "w_state": (i % 10).astype(jnp.int32),
        "w_zip": _uniform(2003, i, 10000, 99999).astype(jnp.int32),
        "w_country": jnp.zeros(length, jnp.int32),
        "w_gmt_offset": jnp.full(length, -500, jnp.int64),
    }


def gen_ship_mode(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    sk = i + 1
    return {
        "sm_ship_mode_sk": sk, "sm_ship_mode_id": sk,
        "sm_type": (i % 5).astype(jnp.int32),
        "sm_code": (i % 3).astype(jnp.int32),
        "sm_carrier": (i % 10).astype(jnp.int32),
        "sm_contract": (i % 20).astype(jnp.int32),
    }


def gen_reason(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    sk = i + 1
    return {"r_reason_sk": sk, "r_reason_id": sk,
            "r_reason_desc": (i % 35).astype(jnp.int32)}


def gen_income_band(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    sk = i + 1
    return {"ib_income_band_sk": sk,
            "ib_lower_bound": (i * 10_000).astype(jnp.int32),
            "ib_upper_bound": ((i + 1) * 10_000).astype(jnp.int32)}


def gen_household_demographics(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    sk = i + 1
    return {
        "hd_demo_sk": sk,
        "hd_income_band_sk": (i % 20) + 1,
        "hd_buy_potential": (i // 20 % 6).astype(jnp.int32),
        "hd_dep_count": (i // 120 % 10).astype(jnp.int32),
        "hd_vehicle_count": (i // 1200 % 6).astype(jnp.int32),
    }


def gen_time_dim(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    hour = (i // 3600).astype(jnp.int32)
    return {
        "t_time_sk": i, "t_time_id": i, "t_time": i.astype(jnp.int32),
        "t_hour": hour,
        "t_minute": ((i // 60) % 60).astype(jnp.int32),
        "t_second": (i % 60).astype(jnp.int32),
        "t_am_pm": (hour >= 12).astype(jnp.int32),
        "t_shift": (hour // 8).astype(jnp.int32) % 3,
        "t_sub_shift": ((hour + 4) // 8).astype(jnp.int32) % 3,
        "t_meal_time": jnp.where(
            (hour >= 6) & (hour <= 9), 0,
            jnp.where((hour >= 11) & (hour <= 14), 1,
                      jnp.where((hour >= 17) & (hour <= 21), 2, 3))
        ).astype(jnp.int32),
    }


def gen_web_site(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    sk = i + 1
    return {
        "web_site_sk": sk, "web_site_id": sk,
        "web_rec_start_date": jnp.full(length, DATE_LO, jnp.int32),
        "web_rec_end_date": jnp.full(length, DATE_HI, jnp.int32),
        "web_name": (i % 30).astype(jnp.int32),
        "web_open_date_sk": JULIAN_BASE + _uniform(2101, i, 0, N_DATES - 1),
        "web_close_date_sk": JULIAN_BASE + N_DATES - 1 + jnp.zeros(length, jnp.int64),
        "web_class": jnp.zeros(length, jnp.int32),
        "web_manager": (i % 100).astype(jnp.int32),
        "web_mkt_id": _uniform(2102, i, 1, 6).astype(jnp.int32),
        "web_mkt_class": (i % 10).astype(jnp.int32),
        "web_mkt_desc": (i % 10).astype(jnp.int32),
        "web_market_manager": (i % 100).astype(jnp.int32),
        "web_company_id": _uniform(2103, i, 1, 6).astype(jnp.int32),
        "web_company_name": (i % 12).astype(jnp.int32),
        "web_street_number": _uniform(2104, i, 1, 999).astype(jnp.int32),
        "web_street_name": (i % 200).astype(jnp.int32),
        "web_street_type": (i % 2).astype(jnp.int32),
        "web_suite_number": (i % 50).astype(jnp.int32),
        "web_city": (i % 200).astype(jnp.int32),
        "web_county": (i % 10).astype(jnp.int32),
        "web_state": (i % 10).astype(jnp.int32),
        "web_zip": _uniform(2105, i, 10000, 99999).astype(jnp.int32),
        "web_country": jnp.zeros(length, jnp.int32),
        "web_gmt_offset": jnp.full(length, -500, jnp.int64),
        "web_tax_percentage": _uniform(2106, i, 0, 12),
    }


def gen_web_page(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    sk = i + 1
    return {
        "wp_web_page_sk": sk, "wp_web_page_id": sk,
        "wp_rec_start_date": jnp.full(length, DATE_LO, jnp.int32),
        "wp_rec_end_date": jnp.full(length, DATE_HI, jnp.int32),
        "wp_creation_date_sk": JULIAN_BASE + _uniform(2201, i, 0, N_DATES - 1),
        "wp_access_date_sk": JULIAN_BASE + _uniform(2202, i, 0, N_DATES - 1),
        "wp_autogen_flag": (i % 2).astype(jnp.int32),
        "wp_customer_sk": _uniform(2203, i, 1, _fk_counts(sf)["customer"]),
        "wp_url": (i % 2).astype(jnp.int32),
        "wp_type": (i % 7).astype(jnp.int32),
        "wp_char_count": _uniform(2204, i, 100, 8000).astype(jnp.int32),
        "wp_link_count": _uniform(2205, i, 2, 25).astype(jnp.int32),
        "wp_image_count": _uniform(2206, i, 1, 7).astype(jnp.int32),
        "wp_max_ad_count": _uniform(2207, i, 0, 4).astype(jnp.int32),
    }


def gen_call_center(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    sk = i + 1
    return {
        "cc_call_center_sk": sk, "cc_call_center_id": sk,
        "cc_rec_start_date": jnp.full(length, DATE_LO, jnp.int32),
        "cc_rec_end_date": jnp.full(length, DATE_HI, jnp.int32),
        "cc_closed_date_sk": jnp.zeros(length, jnp.int64),
        "cc_open_date_sk": JULIAN_BASE + _uniform(2301, i, 0, N_DATES - 1),
        "cc_name": (i % 6).astype(jnp.int32),
        "cc_class": (i % 3).astype(jnp.int32),
        "cc_employees": _uniform(2302, i, 1, 7).astype(jnp.int32),
        "cc_sq_ft": _uniform(2303, i, 1_000, 700_000).astype(jnp.int32),
        "cc_hours": (i % 3).astype(jnp.int32),
        "cc_manager": (i % 100).astype(jnp.int32),
        "cc_mkt_id": _uniform(2304, i, 1, 6).astype(jnp.int32),
        "cc_mkt_class": (i % 10).astype(jnp.int32),
        "cc_mkt_desc": (i % 10).astype(jnp.int32),
        "cc_market_manager": (i % 100).astype(jnp.int32),
        "cc_division": _uniform(2305, i, 1, 6).astype(jnp.int32),
        "cc_division_name": (i % 12).astype(jnp.int32),
        "cc_company": _uniform(2306, i, 1, 6).astype(jnp.int32),
        "cc_company_name": (i % 12).astype(jnp.int32),
        "cc_street_number": _uniform(2307, i, 1, 999).astype(jnp.int32),
        "cc_street_name": (i % 200).astype(jnp.int32),
        "cc_street_type": (i % 2).astype(jnp.int32),
        "cc_suite_number": (i % 50).astype(jnp.int32),
        "cc_city": (i % 200).astype(jnp.int32),
        "cc_county": (i % 10).astype(jnp.int32),
        "cc_state": (i % 10).astype(jnp.int32),
        "cc_zip": _uniform(2308, i, 10000, 99999).astype(jnp.int32),
        "cc_country": jnp.zeros(length, jnp.int32),
        "cc_gmt_offset": jnp.full(length, -500, jnp.int64),
        "cc_tax_percentage": jnp.zeros(length, jnp.int64),
    }


def gen_catalog_page(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    sk = i + 1
    start = JULIAN_BASE + _uniform(2401, i, 0, N_DATES - 100)
    return {
        "cp_catalog_page_sk": sk, "cp_catalog_page_id": sk,
        "cp_start_date_sk": start,
        "cp_end_date_sk": start + 90,
        "cp_department": jnp.zeros(length, jnp.int32),
        "cp_catalog_number": (i // 108 + 1).astype(jnp.int32),
        "cp_catalog_page_number": (i % 108 + 1).astype(jnp.int32),
        "cp_description": (i % BASE_ROWS["item"]).astype(jnp.int32),
        "cp_type": (i % 3).astype(jnp.int32),
    }


def gen_inventory(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    fk = _fk_counts(sf)
    n_item, n_wh = fk["item"], fk["warehouse"]
    # weekly snapshots: row = (week, item, warehouse) in row-major order
    per_week = n_item * n_wh
    return {
        "inv_date_sk": JULIAN_BASE + (i // per_week) * 7,
        "inv_item_sk": (i // n_wh) % n_item + 1,
        "inv_warehouse_sk": i % n_wh + 1,
        "inv_quantity_on_hand": _uniform(2501, i, 0, 1000).astype(jnp.int32),
    }


def gen_catalog_sales(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    fk = _fk_counts(sf)
    m = _sale_measures(2600, i)
    sold = JULIAN_BASE + _seasonal_date(2610, i)
    return {
        "cs_sold_date_sk": sold,
        "cs_sold_time_sk": _uniform(2611, i, 28800, 75600),
        "cs_ship_date_sk": jnp.minimum(sold + _uniform(2612, i, 2, 90),
                               JULIAN_BASE + N_DATES - 1),
        "cs_bill_customer_sk": _uniform(2613, i, 1, fk["customer"]),
        "cs_bill_cdemo_sk": _uniform(2614, i, 1, CD_ROWS),
        "cs_bill_hdemo_sk": _uniform(2615, i, 1, fk["hd"]),
        "cs_bill_addr_sk": _uniform(2616, i, 1, fk["addr"]),
        "cs_ship_customer_sk": _uniform(2617, i, 1, fk["customer"]),
        "cs_ship_cdemo_sk": _uniform(2618, i, 1, CD_ROWS),
        "cs_ship_hdemo_sk": _uniform(2619, i, 1, fk["hd"]),
        "cs_ship_addr_sk": _uniform(2620, i, 1, fk["addr"]),
        "cs_call_center_sk": _uniform(2621, i, 1, fk["cc"]),
        "cs_catalog_page_sk": _uniform(2622, i, 1, fk["cp"]),
        "cs_ship_mode_sk": _uniform(2623, i, 1, fk["ship_mode"]),
        "cs_warehouse_sk": _uniform(2624, i, 1, fk["warehouse"]),
        "cs_item_sk": _uniform(2625, i, 1, fk["item"]),
        "cs_promo_sk": _uniform(2626, i, 1, fk["promo"]),
        "cs_order_number": i // 10 + 1,
        "cs_quantity": m["quantity"],
        "cs_wholesale_cost": m["wholesale_cost"],
        "cs_list_price": m["list_price"],
        "cs_sales_price": m["sales_price"],
        "cs_ext_discount_amt": m["ext_discount_amt"],
        "cs_ext_sales_price": m["ext_sales_price"],
        "cs_ext_wholesale_cost": m["ext_wholesale_cost"],
        "cs_ext_list_price": m["ext_list_price"],
        "cs_ext_tax": m["ext_tax"],
        "cs_coupon_amt": m["coupon_amt"],
        "cs_ext_ship_cost": m["ext_ship_cost"],
        "cs_net_paid": m["net_paid"],
        "cs_net_paid_inc_tax": m["net_paid_inc_tax"],
        "cs_net_paid_inc_ship": m["net_paid_inc_ship"],
        "cs_net_paid_inc_ship_tax": m["net_paid_inc_ship_tax"],
        "cs_net_profit": m["net_profit"],
    }


def gen_web_sales(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    fk = _fk_counts(sf)
    m = _sale_measures(2700, i)
    sold = JULIAN_BASE + _seasonal_date(2710, i)
    return {
        "ws_sold_date_sk": sold,
        "ws_sold_time_sk": _uniform(2711, i, 0, 86399),
        "ws_ship_date_sk": jnp.minimum(sold + _uniform(2712, i, 1, 30),
                               JULIAN_BASE + N_DATES - 1),
        "ws_item_sk": _uniform(2713, i, 1, fk["item"]),
        "ws_bill_customer_sk": _uniform(2714, i, 1, fk["customer"]),
        "ws_bill_cdemo_sk": _uniform(2715, i, 1, CD_ROWS),
        "ws_bill_hdemo_sk": _uniform(2716, i, 1, fk["hd"]),
        "ws_bill_addr_sk": _uniform(2717, i, 1, fk["addr"]),
        "ws_ship_customer_sk": _uniform(2718, i, 1, fk["customer"]),
        "ws_ship_cdemo_sk": _uniform(2719, i, 1, CD_ROWS),
        "ws_ship_hdemo_sk": _uniform(2720, i, 1, fk["hd"]),
        "ws_ship_addr_sk": _uniform(2721, i, 1, fk["addr"]),
        "ws_web_page_sk": _uniform(2722, i, 1, fk["web_page"]),
        "ws_web_site_sk": _uniform(2723, i, 1, fk["web_site"]),
        "ws_ship_mode_sk": _uniform(2724, i, 1, fk["ship_mode"]),
        "ws_warehouse_sk": _uniform(2725, i, 1, fk["warehouse"]),
        "ws_promo_sk": _uniform(2726, i, 1, fk["promo"]),
        "ws_order_number": i // 8 + 1,
        "ws_quantity": m["quantity"],
        "ws_wholesale_cost": m["wholesale_cost"],
        "ws_list_price": m["list_price"],
        "ws_sales_price": m["sales_price"],
        "ws_ext_discount_amt": m["ext_discount_amt"],
        "ws_ext_sales_price": m["ext_sales_price"],
        "ws_ext_wholesale_cost": m["ext_wholesale_cost"],
        "ws_ext_list_price": m["ext_list_price"],
        "ws_ext_tax": m["ext_tax"],
        "ws_coupon_amt": m["coupon_amt"],
        "ws_ext_ship_cost": m["ext_ship_cost"],
        "ws_net_paid": m["net_paid"],
        "ws_net_paid_inc_tax": m["net_paid_inc_tax"],
        "ws_net_paid_inc_ship": m["net_paid_inc_ship"],
        "ws_net_paid_inc_ship_tax": m["net_paid_inc_ship_tax"],
        "ws_net_profit": m["net_profit"],
    }


def gen_store_returns(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    fk = _fk_counts(sf)
    s = _returned_sale(sf, i)  # the sale this row returns
    n_sales = _scaled_rows("store_sales", sf)
    r = _return_measures(2800, i)
    return {
        "sr_returned_date_sk": JULIAN_BASE + _uniform(2810, i, 0, N_DATES - 1),
        "sr_return_time_sk": _uniform(2811, i, 28800, 75600),
        "sr_item_sk": _ticket_item(s, fk["item"], n_sales),
        "sr_customer_sk": _uniform(609, s, 1, fk["customer"]),
        "sr_cdemo_sk": _uniform(2814, i, 1, CD_ROWS),
        "sr_hdemo_sk": _uniform(2815, i, 1, fk["hd"]),
        "sr_addr_sk": _uniform(2816, i, 1, fk["addr"]),
        "sr_store_sk": _uniform(613, s, 1, fk["store"]),
        "sr_reason_sk": _uniform(2818, i, 1, fk["reason"]),
        "sr_ticket_number": _ticket(s, n_sales) + 1,
        "sr_return_quantity": r["quantity"],
        "sr_return_amt": r["amt"],
        "sr_return_tax": r["tax"],
        "sr_return_amt_inc_tax": r["amt_inc_tax"],
        "sr_fee": r["fee"],
        "sr_return_ship_cost": r["ship"],
        "sr_refunded_cash": r["cash"],
        "sr_reversed_charge": r["reversed"],
        "sr_store_credit": r["credit"],
        "sr_net_loss": r["loss"],
    }


def gen_catalog_returns(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    fk = _fk_counts(sf)
    r = _return_measures(2900, i)
    return {
        "cr_returned_date_sk": JULIAN_BASE + _uniform(2910, i, 0, N_DATES - 1),
        "cr_returned_time_sk": _uniform(2911, i, 28800, 75600),
        "cr_item_sk": _uniform(2912, i, 1, fk["item"]),
        "cr_refunded_customer_sk": _uniform(2913, i, 1, fk["customer"]),
        "cr_refunded_cdemo_sk": _uniform(2914, i, 1, CD_ROWS),
        "cr_refunded_hdemo_sk": _uniform(2915, i, 1, fk["hd"]),
        "cr_refunded_addr_sk": _uniform(2916, i, 1, fk["addr"]),
        "cr_returning_customer_sk": _uniform(2917, i, 1, fk["customer"]),
        "cr_returning_cdemo_sk": _uniform(2918, i, 1, CD_ROWS),
        "cr_returning_hdemo_sk": _uniform(2919, i, 1, fk["hd"]),
        "cr_returning_addr_sk": _uniform(2920, i, 1, fk["addr"]),
        "cr_call_center_sk": _uniform(2921, i, 1, fk["cc"]),
        "cr_catalog_page_sk": _uniform(2922, i, 1, fk["cp"]),
        "cr_ship_mode_sk": _uniform(2923, i, 1, fk["ship_mode"]),
        "cr_warehouse_sk": _uniform(2924, i, 1, fk["warehouse"]),
        "cr_reason_sk": _uniform(2925, i, 1, fk["reason"]),
        "cr_order_number": _uniform(2926, i, 1,
                                    max(int(BASE_ROWS["catalog_sales"] * sf)
                                        // 10, 1)),
        "cr_return_quantity": r["quantity"],
        "cr_return_amount": r["amt"],
        "cr_return_tax": r["tax"],
        "cr_return_amt_inc_tax": r["amt_inc_tax"],
        "cr_fee": r["fee"],
        "cr_return_ship_cost": r["ship"],
        "cr_refunded_cash": r["cash"],
        "cr_reversed_charge": r["reversed"],
        "cr_store_credit": r["credit"],
        "cr_net_loss": r["loss"],
    }


def gen_web_returns(sf, lo, length, n=0):
    i = jnp.arange(length, dtype=jnp.int64) + lo
    fk = _fk_counts(sf)
    r = _return_measures(3000, i)
    return {
        "wr_returned_date_sk": JULIAN_BASE + _uniform(3010, i, 0, N_DATES - 1),
        "wr_returned_time_sk": _uniform(3011, i, 0, 86399),
        "wr_item_sk": _uniform(3012, i, 1, fk["item"]),
        "wr_refunded_customer_sk": _uniform(3013, i, 1, fk["customer"]),
        "wr_refunded_cdemo_sk": _uniform(3014, i, 1, CD_ROWS),
        "wr_refunded_hdemo_sk": _uniform(3015, i, 1, fk["hd"]),
        "wr_refunded_addr_sk": _uniform(3016, i, 1, fk["addr"]),
        "wr_returning_customer_sk": _uniform(3017, i, 1, fk["customer"]),
        "wr_returning_cdemo_sk": _uniform(3018, i, 1, CD_ROWS),
        "wr_returning_hdemo_sk": _uniform(3019, i, 1, fk["hd"]),
        "wr_returning_addr_sk": _uniform(3020, i, 1, fk["addr"]),
        "wr_web_page_sk": _uniform(3021, i, 1, fk["web_page"]),
        "wr_reason_sk": _uniform(3022, i, 1, fk["reason"]),
        "wr_order_number": _uniform(3023, i, 1,
                                    max(int(BASE_ROWS["web_sales"] * sf)
                                        // 8, 1)),
        "wr_return_quantity": r["quantity"],
        "wr_return_amt": r["amt"],
        "wr_return_tax": r["tax"],
        "wr_return_amt_inc_tax": r["amt_inc_tax"],
        "wr_fee": r["fee"],
        "wr_return_ship_cost": r["ship"],
        "wr_refunded_cash": r["cash"],
        "wr_reversed_charge": r["reversed"],
        "wr_account_credit": r["credit"],
        "wr_net_loss": r["loss"],
    }


GENERATORS = {
    "date_dim": gen_date_dim,
    "item": gen_item,
    "customer": gen_customer,
    "customer_address": gen_customer_address,
    "customer_demographics": gen_customer_demographics,
    "store": gen_store,
    "promotion": gen_promotion,
    "store_sales": gen_store_sales,
    "warehouse": gen_warehouse,
    "ship_mode": gen_ship_mode,
    "reason": gen_reason,
    "income_band": gen_income_band,
    "household_demographics": gen_household_demographics,
    "time_dim": gen_time_dim,
    "web_site": gen_web_site,
    "web_page": gen_web_page,
    "call_center": gen_call_center,
    "catalog_page": gen_catalog_page,
    "inventory": gen_inventory,
    "catalog_sales": gen_catalog_sales,
    "web_sales": gen_web_sales,
    "store_returns": gen_store_returns,
    "catalog_returns": gen_catalog_returns,
    "web_returns": gen_web_returns,
}

_PK = {"date_dim": ("d_date_sk",), "item": ("i_item_sk",),
       "customer": ("c_customer_sk",), "customer_address": ("ca_address_sk",),
       "customer_demographics": ("cd_demo_sk",), "store": ("s_store_sk",),
       "promotion": ("p_promo_sk",), "warehouse": ("w_warehouse_sk",),
       "ship_mode": ("sm_ship_mode_sk",), "reason": ("r_reason_sk",),
       "income_band": ("ib_income_band_sk",),
       "household_demographics": ("hd_demo_sk",), "time_dim": ("t_time_sk",),
       "web_site": ("web_site_sk",), "web_page": ("wp_web_page_sk",),
       "call_center": ("cc_call_center_sk",),
       "catalog_page": ("cp_catalog_page_sk",)}

_MONOTONE_PK = {t: pk[0] for t, pk in _PK.items()}
# monotone-pk base offset: most sks start at 1; date_dim's is julian-like and
# time_dim's counts seconds from 0
_PK_BASE = {t: 1 for t in _PK}
_PK_BASE["date_dim"] = JULIAN_BASE
_PK_BASE["time_dim"] = 0


@dataclasses.dataclass(frozen=True)
class TpcdsSplit:
    table: str
    lo: int
    hi: int


class TpcdsConnector(WarmScans):
    name = "tpcds"
    supports_count_pushdown = True  # row counts are index-derived (exact)
    CACHEABLE_SCANS = True  # deterministic generator (see TpchConnector)

    def exact_row_count(self, table: str) -> int:
        return self.row_count(table)

    def __init__(self, sf: float = 1.0, split_rows: int = 1 << 20):
        self.sf = sf
        self.split_rows = split_rows
        self._warming: dict = {}  # (table, columns) -> its generator's warm thread

    def tables(self):
        return sorted(SCHEMAS)

    def schema(self, table: str) -> Schema:
        return SCHEMAS[table]

    def dictionaries(self, table: str) -> dict:
        return dict(DICTS[table])

    def primary_key(self, table: str) -> tuple:
        if table in _PK:
            return _PK[table]
        raise KeyError(table)

    def row_count(self, table: str) -> int:
        if table == "date_dim":
            return N_DATES
        if table == "customer_demographics":
            return CD_ROWS
        if table in FIXED_ROWS:
            return FIXED_ROWS[table]
        if table in MIN_SCALED:
            return max(int(round(BASE_ROWS[table]
                                 * max(self.sf, MIN_SCALED[table]))), 1)
        return max(int(BASE_ROWS[table] * self.sf), 1)

    def column_range(self, table: str, column: str):
        pk = _MONOTONE_PK.get(table)
        if pk == column:
            base = _PK_BASE[table]
            return (base, base + self.row_count(table) - 1)
        return (None, None)

    def splits(self, table: str, n_hint: int = 0):
        """Equal-size split ranges (one XLA shape class per table scan; the
        trailing overshoot past ``row_count`` is masked via the page's valid
        mask — same contract as the TPC-H connector, which is what lets the
        shard_map path drive every split through one traced program)."""
        n = self.row_count(table)
        step = min(self.split_rows, max(n, 1))
        nsplits = -(-n // step)
        if n_hint:
            nsplits = -(-nsplits // n_hint) * n_hint  # multiple of SPMD batch
        return [TpcdsSplit(table, s * step, (s + 1) * step)
                for s in range(nsplits)]

    def split_range(self, split: TpcdsSplit, column: str):
        pk = _MONOTONE_PK.get(split.table)
        if pk == column:
            base = _PK_BASE[split.table]
            return (base + split.lo, base + split.hi - 1)
        return None

    def generate(self, split: TpcdsSplit, columns=None) -> Page:
        schema = SCHEMAS[split.table]
        names = tuple(columns) if columns is not None else schema.names
        self._await_warm(split.table, names)
        cols, valid = _jit_generate(split.table, self.sf, split.lo,
                                    split.hi - split.lo, names,
                                    self.table_bound(split.table))
        out_schema = Schema(tuple(schema.field(c) for c in names))
        return Page(out_schema, cols, tuple(None for _ in cols), valid)

    def table_bound(self, table: str) -> int:
        """Mask bound of a table's split ranges: its row count (the name the
        executor's ``rows_generated`` accounting asks a connector for)."""
        return self.row_count(table)

    def generate_traced(self, table: str, lo, length: int, columns):
        """Trace-time generation with traced ``lo`` and static ``length`` (the
        in-shard_map sharded scan contract shared with
        TpchConnector.generate_traced): returns (cols tuple, valid)."""
        return _generate_cols(table, self.sf, lo, length, tuple(columns),
                              self.table_bound(table))


def _generate_cols(table: str, sf: float, lo, length: int, names: tuple,
                   n: int):
    all_cols = GENERATORS[table](sf, lo, length)
    schema = SCHEMAS[table]
    out = tuple(all_cols[c].astype(schema.field(c).type.dtype) for c in names)
    return out, (jnp.arange(length, dtype=jnp.int64) + lo) < n


_GENERATE_PROGRAMS: dict = {}  # table -> its jitted generator


def _jit_generate(table: str, sf: float, lo: int, length: int, names: tuple,
                  n: int):
    """One program per table, named ``generate.<table>`` (see tpch).  ``lo``
    is TRACED, as in tpch: every split of a scan runs the one program of its
    (table, length, column set), and the bound ``n`` masks the tail."""
    run = _GENERATE_PROGRAMS.get(table)
    if run is None:
        from ..execution.tracing import site_program

        run = _GENERATE_PROGRAMS[table] = jax.jit(  # compile-ok: host-side table generation; dispatched from connector code outside the executor's _jit paths, one compile per (table, split shape)
            site_program(partial(_generate_cols, table), f"generate.{table}"),
            static_argnums=(0, 2, 3, 4))
    return run(sf, lo, length, names, n)
