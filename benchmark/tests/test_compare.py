"""The comparison that decides ``correct``: the reference against itself passes, an
answer whose sums were accumulated in float32 fails (the control, at a size a test can
hold: SF0.01 on the CPU backend)."""

import numpy as np
import pandas as pd
import pytest

from benchmark.harness import compare
from benchmark.harness.hosttables import HostTables
from benchmark.harness.loader import Cell

CELLS = ("sf1_joins", "sf10_scan", "sf1_dashboard")


@pytest.fixture(scope="module")
def deployments():
    from trino_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector(sf=0.01, split_rows=1 << 21)
    out = {}
    for name in CELLS:
        cell = Cell(name)
        wanted = {}
        for st in cell.statements.values():
            for table, cols in st.TABLES.items():
                wanted.setdefault(table, []).extend(cols)
        out[name] = (cell, HostTables(conn, wanted))
    return out


def answers(cell, tables, dtype):
    for name, st in cell.statements.items():
        p = dict(st.VALIDATION)
        if "key" in p:
            p["key"] = 1234  # an account balance that float32 cannot hold exactly
        want = st.reference(tables, p)
        got = want if dtype is None else st.reference(tables, p, dtype=dtype)
        yield name, compare.compare(got, want, getattr(st, "AVG_DECIMALS", None))


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_reference_against_itself_is_correct(deployments, cell_name):
    cell, tables = deployments[cell_name]
    for name, numbers in answers(cell, tables, None):
        assert numbers == {"exact_mismatches": 0, "max_rel_err": 0.0, "avg_err_units": 0.0}, name
        assert compare.within_limits(numbers)


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_float32_control_is_not_correct(deployments, cell_name):
    """The lower precision has to fail one of the cell's numbers, not each statement."""
    cell, tables = deployments[cell_name]
    numbers = dict(answers(cell, tables, np.float32))
    worst = compare.worst(numbers.values())
    assert not compare.within_limits(worst), numbers
    assert worst["max_rel_err"] > 3 * compare.LIMITS["max_rel_err"]
    assert worst["exact_mismatches"] == 0  # keys, strings, counts and order stay right


def test_each_kind_of_column_is_held_to_its_own_limit():
    want = pd.DataFrame({"k": [1, 2], "name": ["a", "b"],
                         "d": np.array(["1995-03-15", "1995-03-16"], dtype="datetime64[D]"),
                         "s": [100.25, 2e9], "avg_q": [25.5049, 25.5]})
    good = pd.DataFrame({"k": [1, 2], "name": ["a", "b"], "d": ["1995-03-15", "1995-03-16"],
                         "s": [100.25, 2e9 * (1 + 1e-12)], "avg_q": [25.50, 25.5]})
    n = compare.compare(good, want, {"avg_q": 2})
    assert n["exact_mismatches"] == 0 and n["max_rel_err"] < 1e-11
    assert n["avg_err_units"] == pytest.approx(0.49)
    assert compare.within_limits(n)
    for column, value in (("k", 3), ("name", "c"), ("d", "1995-03-17")):
        bad = good.copy()
        bad.loc[1, column] = value
        assert compare.compare(bad, want, {"avg_q": 2})["exact_mismatches"] == 1
    bad = good.copy()
    bad.loc[0, "s"] = 100.26
    assert not compare.within_limits(compare.compare(bad, want, {"avg_q": 2}))
    bad = good.copy()
    bad.loc[0, "avg_q"] = 25.49
    assert not compare.within_limits(compare.compare(bad, want, {"avg_q": 2}))
    swapped = good.iloc[::-1].reset_index(drop=True)  # ORDER BY order is part of the answer
    assert compare.compare(swapped, want, {"avg_q": 2})["exact_mismatches"] > 0
    assert compare.compare(good.iloc[:1], want, {"avg_q": 2})["exact_mismatches"] > 0
