import glob
import os
import random

import pytest

from benchmark.harness.loader import ROOT, _load_module

NAMES = sorted(os.path.basename(p)[:-3]
               for p in glob.glob(os.path.join(ROOT, "benchmark", "statements", "*.py")))
CONFIG = {"sf": 1}

# TPC-H's substitution ranges (clauses 2.4.1.3, 2.4.3.3, 2.4.9.3, 2.4.18.3) and the mix's keys
RANGES = {
    "q1": lambda p: 60 <= p["delta"] <= 120,
    "q3": lambda p: p["segment"] in ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY",
                                     "HOUSEHOLD")
    and p["date"][:8] == "1995-03-" and 1 <= int(p["date"][8:]) <= 31,
    "q9": lambda p: p["color"].isalpha() and p["color"].islower(),
    "q18": lambda p: 312 <= p["quantity"] <= 315,
    "point": lambda p: 1 <= p["key"] <= 149_999,
    "param": lambda p: 1 <= p["key"] <= 149_999,
    "agg_lineitem": lambda p: p == {},
    "agg_orders": lambda p: p == {},
}


def load(name):
    return _load_module(os.path.join(ROOT, "benchmark", "statements", name + ".py"), name)


def test_every_statement_has_a_range_check():
    assert set(NAMES) == set(RANGES)


@pytest.mark.parametrize("name", NAMES)
def test_params_are_deterministic_in_the_seed_and_inside_the_ranges(name):
    st = load(name)
    draws = [st.params(random.Random(3_000_000_123), CONFIG) for _ in range(2)]
    assert draws[0] == draws[1]
    rng = random.Random(5)
    seen = [st.params(rng, CONFIG) for _ in range(200)]
    assert all(RANGES[name](p) for p in seen)
    assert RANGES[name](st.VALIDATION) or name == "q18"  # Q18's validation value is 300
    if seen[0]:
        assert len({tuple(sorted(p.items())) for p in seen}) > 1


@pytest.mark.parametrize("name", NAMES)
def test_render_gives_sql_and_protocol_parameters(name):
    st = load(name)
    p = st.params(random.Random(1), CONFIG)
    sql, bound = st.render(p)
    assert sql.lstrip().lower().startswith("select")
    assert (bound is not None) == ("?" in sql)
    assert "{" not in sql
    assert all(cols for cols in st.TABLES.values())
