"""The regimes of the deployment ``tpch_sf10_joins_1chip`` (benchmark cell
``sf10_joins``), forced at SF0.01 on the CPU: q3 and q18 served over POST
/v1/statement and compared with the benchmark's own pandas references while

- lineitem has 26 splits and passes the page cache's per-entry cap, so every probe
  side streams split by split and is generated again by every statement, while
  orders' and customer's scans are admitted;
- q18's inner ``group by l_orderkey`` (direct-indexed at SF10: 24 bits of dense
  keys) is pushed through the hash table from a capacity that overflows, once so
  that it grows in its insert loop and once so that the next step is over the cap
  and the partitioned passes run;
- the sorted aggregation takes it where the planner picks that mode;
- every direct join table is staged inside the programs that probe it, as the 15M-slot
  table over orders is at SF10 (``hashjoin.stage_direct_table``).

Exact on integers, strings, dates and counts, 1e-9 relative on sums: the limits of
``benchmark/harness/compare.py``."""

import os

import pandas as pd
import pytest

import trino_tpu.exec.local_executor as LE
from benchmark.harness import compare
from benchmark.harness.hosttables import HostTables
from benchmark.harness.loader import _load_module
from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.execution.bufferpool import DeviceBufferPool
from trino_tpu.ops import hashagg, hashjoin
from trino_tpu.server.client import Client
from trino_tpu.server.server import CoordinatorServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SF, SPLIT_ROWS = 0.01, 4096
LINEITEM_ROWS = 60_000  # TpchConnector.row_count: four lines an order
POOL_BYTES = 4 << 20    # cap 1 MiB: orders (0.4 MB) is admitted, lineitem (1.7 MB) not

STATEMENTS = {name: _load_module(os.path.join(ROOT, "benchmark", "statements", name + ".py"), name)
              for name in ("q3", "q18")}
# q18 at its validation QUANTITY selects no order at SF0.01; 250 selects a few
CASES = [("q3", None), ("q18", None), ("q18", {"quantity": 250})]


def _regime_streamed(monkeypatch):
    return {"inner": "aggregate.direct", "regrows": 0, "passes": 0}


def _hash_from(monkeypatch, start):
    monkeypatch.setattr(hashagg, "direct_config", lambda *a, **k: None)
    monkeypatch.setattr(LE.LocalExecutor, "_streaming_agg_order",
                        lambda self, stream, node: None)
    monkeypatch.setattr(LE.LocalExecutor, "_agg_capacity_estimate",
                        lambda self, stream, node, key_ranges: None)
    monkeypatch.setattr(LE, "DEFAULT_GROUP_CAPACITY", start)


def _regime_grows(monkeypatch):
    _hash_from(monkeypatch, 1024)  # 15,000 groups: 1,024 -> 4,096 -> 16,384 in the loop
    return {"inner": "aggregate.hash", "regrows": 0, "passes": 0}


def _regime_partitioned(monkeypatch):
    _hash_from(monkeypatch, 1024)
    monkeypatch.setattr(LE, "MAX_GROUP_CAPACITY", 8192)  # 16,384 is over the cap
    return {"inner": "aggregate.partitioned", "passes": 4}


def _regime_sorted(monkeypatch):
    monkeypatch.setattr(hashagg, "direct_config", lambda *a, **k: None)
    return {"inner": "aggregate.sorted", "regrows": 0, "passes": 0}


def _regime_staged(monkeypatch):
    monkeypatch.setattr(hashjoin, "STAGE_SLOTS_MIN", 1)  # SF10: orders' 15M slots pass 2^22
    staged = []

    def counting(table, fields=None):
        out = hashjoin.stage_direct_table(table, fields)
        staged.append(out is not table)
        return out

    monkeypatch.setattr(LE, "stage_direct_table", counting)
    return dict(_regime_streamed(monkeypatch), staged=staged)


REGIMES = [_regime_streamed, _regime_grows, _regime_partitioned, _regime_sorted,
           _regime_staged]


@pytest.fixture(scope="module")
def host_tables():
    wanted = {}
    for st in STATEMENTS.values():
        for table, cols in st.TABLES.items():
            wanted.setdefault(table, []).extend(cols)
    return HostTables(TpchConnector(sf=SF, split_rows=SPLIT_ROWS), wanted)


@pytest.mark.parametrize("name,params", CASES,
                         ids=lambda v: v if isinstance(v, str) else
                         ("validation" if v is None else "q%d" % v["quantity"]))
@pytest.mark.parametrize("regime", REGIMES, ids=lambda f: f.__name__[len("_regime_"):])
def test_served_answers_match_the_reference(regime, name, params, monkeypatch, host_tables):
    want_regime = regime(monkeypatch)
    statement = STATEMENTS[name]
    p = params or statement.VALIDATION
    conn = TpchConnector(sf=SF, split_rows=SPLIT_ROWS)
    assert len(conn.splits("lineitem")) >= 20
    engine = Engine()
    engine.buffer_pool = DeviceBufferPool(budget_bytes=POOL_BYTES)
    engine.register_catalog("tpch", conn)
    server = CoordinatorServer(engine, port=0)
    server.start()
    try:
        client = Client(server.url, catalog="tpch")
        sql, bound = statement.render(p)
        runs = []
        for _ in range(3):  # cold, the advisor's re-plan if it makes one, a replay
            res = client.execute(sql, timeout=300.0, params=bound)
            runs.append((res, engine.last_query_counters,
                         [s["name"] for s in engine.last_query_trace["spans"]
                          if s["name"].startswith("aggregate.")]))
    finally:
        server.stop()
    want = statement.reference(host_tables, p)
    for res, counters, spans in runs:
        got = pd.DataFrame(res.rows, columns=res.column_names)
        numbers = compare.compare(got, want, getattr(statement, "AVG_DECIMALS", None))
        assert compare.within_limits(numbers), numbers
        assert counters.device_dispatches > 0 and counters.result_cache_hits == 0
    if params is not None:
        assert len(want) > 0, "the lowered QUANTITY has to select orders"
    if "staged" in want_regime and len(want):  # the probes were traced with staged tables
        assert want_regime["staged"] and all(want_regime["staged"])

    # the pool: lineitem is over the per-entry cap, orders and customer are resident.
    # Since PR 30 q18's semi-join is the filter of ORDERS (PushSemiJoinThroughJoin:
    # orders is its probe side, inside the first join's build), so where its build
    # comes out empty dynamic filtering prunes every split of orders and orders is
    # never scanned, nor admitted
    info = engine.buffer_pool.info()
    assert "tpch.lineitem" not in info["per_table"], info["per_table"]
    resident = {"tpch.customer"} | ({"tpch.orders"} if name == "q3" or len(want) else set())
    assert resident <= set(info["per_table"]), info["per_table"]
    if not resident >= {"tpch.orders"}:
        assert "tpch.orders" not in info["per_table"], info["per_table"]
    assert info["bytes"] <= POOL_BYTES
    # the cold run: every scan of lineitem generated it, the builds were made (where
    # q18's semi-join build comes out empty, dynamic filtering prunes every split of
    # its probe side, and only the inner group-by reads lineitem)
    _, cold, cold_spans = runs[0]
    probes = 1 if len(want) else 0
    scans = probes if name == "q3" else 1 + probes
    assert cold.rows_generated >= scans * LINEITEM_ROWS
    assert cold.join_build_rows > 0
    if name == "q18":
        assert want_regime["inner"] in cold_spans, cold_spans
        assert cold.groupby_partitioned_passes == want_regime["passes"]
        if "regrows" in want_regime:
            assert cold.groupby_regrows == want_regime["regrows"]
        assert cold.groupby_slots >= 15_000
    # the replay: the compiled streams hold the build tables (q18's inner group-by
    # is the semi-join's build side), the probe side is generated again: all of it in
    # q3; in q18 the splits that can hold one of the few orders that passed the
    # semi-join (the first join's build is now a handful of keys, so
    # ``_dynamic_pruned_pages`` takes their exact set and skips the splits with none)
    _, warm, warm_spans = runs[-1]
    assert warm.compiles == 0 and warm.join_build_rows == 0
    if name == "q3":
        assert warm.rows_generated == probes * LINEITEM_ROWS
    else:
        one_split = LINEITEM_ROWS // len(conn.splits("lineitem"))
        assert probes * one_split <= warm.rows_generated <= probes * LINEITEM_ROWS
    assert warm.page_cache_hits == 0
    assert warm_spans == ["aggregate.hash"]
