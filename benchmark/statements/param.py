"""The point lookup with a ``?`` marker, its key bound through protocol parameters
(X-Trino-Execute-Parameters): one SQL text for every request."""

from benchmark.statements.point import TABLES, VALIDATION, params, reference  # noqa: F401  (the same rows, another binding)

SQL = "select c_name, c_acctbal, c_mktsegment from customer where c_custkey = ?"


def render(p):
    return SQL, [p["key"]]
