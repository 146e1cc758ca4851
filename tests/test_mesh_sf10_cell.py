"""The deployment ``tpch_sf10_4chip`` (PR 46) at a size the CPU holds: ``worker_mesh(4)``
of the suite's host devices, SF0.01 in splits of 2^11 row slots, so that lineitem is 52
splits (SF10 in splits of 2^21: 51, padded to 52) and 13 batches a scan, and a page cache
whose entry cap lies between the two scans' shares a chip: q3's four columns (29 B a slot)
are RESIDENT as quarters, q1's seven (45 B a slot) pass the cap and STREAM, every statement.

Both statements answer as the pandas references of ``benchmark/statements`` (which import
nothing of ``trino_tpu``) and, to the last digit, as ``Engine()``; the counters this
deployment brought (``mesh_scan_batches_resident``, ``mesh_scan_batches_generated``,
``exchange_bytes``) and the fact a streamed scan records say which regime a statement ran
in, in ``counters_total``, EXPLAIN ANALYZE and ``/v1/metrics``; and the cell's files load.
"""

import json
import os
import re
import urllib.request

import pandas as pd
import pytest

from benchmark.harness import compare
from benchmark.harness.hosttables import HostTables
from benchmark.harness.loader import ROOT, Cell, check_name, check_unit
from benchmark.statements import q1, q3
from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.execution.bufferpool import DeviceBufferPool
from trino_tpu.parallel.mesh import worker_mesh
from trino_tpu.server.server import CoordinatorServer

STATEMENTS = {"q1": q1, "q3": q3}
CELL = "sf10_mesh4_joins"
W = 4
SPLIT_ROWS = 1 << 11
BATCHES = 13
# a chip's share of a whole scan, bytes: 52 splits of 292 orders, seven lanes an order
Q3_SHARE = 52 * 292 * 7 * 29 // W
Q1_SHARE = 52 * 292 * 7 * 45 // W
# the entry cap is a quarter of the budget: between the two shares
BUDGET = 4 * 1_000_000
# SF0.01's orders-with-customer build passes this as SF10's passes the default 2^17
PARTITION_THRESHOLD = 1024
NEW_METRICS = {"resident_batch_share.mesh": ("%", "higher", "program_counter", "device memory"),
               "exchange_mb_per_stmt.mesh": ("MB", "lower", "program_counter", "exchange"),
               "busy_imbalance.mesh": ("%", "lower", "device_trace", "operators and kernels")}


def sql_of(name):
    statement = STATEMENTS[name]
    return statement.render(statement.VALIDATION)[0]


def run(engine, name):
    res = engine.execute_sql(sql_of(name), engine.create_session("tpch"))
    return res.rows(), list(res.names), engine.last_query_counters


@pytest.fixture(scope="module")
def deployment():
    conn = TpchConnector(sf=0.01, split_rows=SPLIT_ROWS)
    engine, plain = Engine(mesh=worker_mesh(W)), Engine()
    engine.buffer_pool = DeviceBufferPool(budget_bytes=BUDGET)
    for e in (engine, plain):
        e.register_catalog("tpch", conn)
    with engine._mesh_executor(None) as ex:
        ex.partition_threshold = PARTITION_THRESHOLD
    wanted = {}
    for statement in STATEMENTS.values():
        for table, cols in statement.TABLES.items():
            wanted.setdefault(table, []).extend(cols)
    # each text three times: the cold run, the one that compiles q3's narrowed probe
    # fragment (PR 33), and a replay
    runs = {name: [run(engine, name) for _ in range(3)] for name in STATEMENTS}
    return {"conn": conn, "engine": engine, "plain": plain, "runs": runs,
            "tables": HostTables(conn, wanted)}


def test_the_sizes_are_the_deployments(deployment):
    splits = deployment["conn"].splits("lineitem", n_hint=W)
    assert len(splits) == BATCHES * W and splits[0].hi - splits[0].lo == 292
    cap = deployment["engine"].buffer_pool.page_entry_cap()
    assert Q3_SHARE < cap < Q1_SHARE


@pytest.mark.parametrize("name", list(STATEMENTS))
def test_both_statements_answer_as_the_reference_and_as_one_chip(deployment, name):
    statement = STATEMENTS[name]
    want = statement.reference(deployment["tables"], statement.VALIDATION)
    plain_rows = run(deployment["plain"], name)[0]
    for rows, columns, _ in deployment["runs"][name]:
        numbers = compare.compare(pd.DataFrame(rows, columns=columns), want,
                                  getattr(statement, "AVG_DECIMALS", None))
        assert compare.within_limits(numbers), numbers
        assert rows == plain_rows  # to the last digit


def test_a_replayed_q3_is_handed_its_batches_from_the_pools_entry(deployment):
    first, _, replay = (c for _, _, c in deployment["runs"]["q3"])
    assert (first.mesh_scan_batches_resident, first.mesh_scan_batches_generated) == (0, BATCHES)
    assert first.rows_generated > 0 and first.page_cache_misses > 0
    assert (replay.mesh_scan_batches_resident, replay.mesh_scan_batches_generated) \
        == (BATCHES, 0), replay.as_dict()
    assert (replay.rows_generated, replay.compiles, replay.join_build_rows) == (0, 0, 0)
    assert replay.page_cache_hits > 0 and replay.page_cache_misses == 0
    info = deployment["engine"].buffer_pool.info()
    # ONE sharded entry, priced at the bytes one chip holds; beside it device 0's local
    # scans of the build side (orders, customer) under the same budget; nothing evicted
    assert info["per_table"]["tpch.lineitem"] == {"entries": 1, "bytes": Q3_SHARE}
    assert {"tpch.orders", "tpch.customer"} <= set(info["per_table"])
    assert info["evictions"] == 0 and info["bytes"] <= BUDGET


def test_a_q1_generates_every_batch_every_time_and_says_why(deployment):
    lineitem_rows = deployment["conn"].row_count("lineitem")
    for _, _, c in deployment["runs"]["q1"]:
        assert (c.mesh_scan_batches_resident, c.mesh_scan_batches_generated) == (0, BATCHES)
        assert c.rows_generated == lineitem_rows
        assert (c.page_cache_hits, c.page_cache_misses) == (0, 1)
        (site,) = [v for k, v in c.sites.items() if k.endswith("dist.scan.lineitem.cache")]
        assert site["page_cache_over_cap"] == 1 and "page_cache_store_failed" not in site
    assert deployment["runs"]["q1"][-1][2].compiles == 0
    # q3's site records a miss and no reason: its entry was stored
    (site,) = [v for k, v in deployment["runs"]["q3"][0][2].sites.items()
               if k.endswith("dist.scan.lineitem.cache")]
    assert site["page_cache_misses"] == 1 and "page_cache_over_cap" not in site


def test_a_refused_entry_is_a_recorded_fact_not_a_silent_one(deployment):
    """``_ShardedScan._gather``: a store the pool refuses (here an injected fault; on a
    deployment, a budget that live reservations fill) used to vanish in a bare except."""
    from trino_tpu.execution import faults

    engine = Engine(mesh=worker_mesh(W))
    engine.buffer_pool = DeviceBufferPool(budget_bytes=BUDGET)
    engine.register_catalog("tpch", deployment["conn"])
    sql = "select count(*) n, sum(l_quantity) q from lineitem"
    want = deployment["plain"].execute_sql(sql, deployment["plain"].create_session("tpch")).rows()
    with faults.injected(faults.FaultPlan([faults.FaultRule(
            "cache_store", site="page.lineitem", action="deny")])):
        assert engine.execute_sql(sql, engine.create_session("tpch")).rows() == want
    c = engine.last_query_counters
    (site,) = [v for k, v in c.sites.items() if k.endswith("dist.scan.lineitem.cache")]
    assert site["page_cache_store_failed"] == 1 and "page_cache_over_cap" not in site
    assert "tpch.lineitem" not in engine.buffer_pool.info()["per_table"]
    # the next statement generates again, stores, and the one after is resident
    assert engine.execute_sql(sql, engine.create_session("tpch")).rows() == want
    assert engine.last_query_counters.mesh_scan_batches_generated == BATCHES
    assert engine.execute_sql(sql, engine.create_session("tpch")).rows() == want
    assert engine.last_query_counters.mesh_scan_batches_resident == BATCHES


def test_the_partitioned_join_is_chosen_and_its_bytes_are_counted(deployment):
    _, _, c = deployment["runs"]["q3"][-1]
    engine = deployment["engine"]
    with engine._mesh_executor(None) as ex:
        tables = [k for k in ex._kept if k[1:] == ("ptable",)]
        assert ex.partition_threshold == PARTITION_THRESHOLD
    assert len(tables) == 1  # orders-with-customer: n_build over the threshold
    lineitem = deployment["tables"].columns("lineitem")
    cutoff = (pd.Timestamp(q3.VALIDATION["date"]) - pd.Timestamp("1970-01-01")).days
    routed = int((lineitem["l_shipdate"] > cutoff).sum())
    assert c.probe_exchange_rows == routed > 0 and c.probe_exchange_lanes > routed
    # the probe routes l_orderkey, l_extendedprice, l_discount (int64) and nothing of the
    # filter's l_shipdate, or all four: the width is the probe side's schema's
    probe_bytes = c.exchange_bytes - c.exchange_rows * _merge_row_bytes(c)
    assert probe_bytes > 0 and probe_bytes % routed == 0
    assert 8 <= probe_bytes // routed <= 29
    # q1 exchanges its merged groups alone: four (returnflag, linestatus) groups
    _, _, c1 = deployment["runs"]["q1"][-1]
    assert c1.probe_exchange_rows == 0 and c1.exchange_rows == 4
    assert c1.exchange_bytes == 4 * _merge_row_bytes(c1)


def _merge_row_bytes(c):
    """Bytes of one group entry of the statement's merge exchange, from the shard record
    the merge left: its per-worker ``bytes`` over its per-worker ``rows``."""
    (rec,) = [r for r in c.shard_stats if r["site"] == "dist.agg.overflow"]
    assert sum(rec["rows"]) > 0
    return sum(rec["bytes"]) // sum(rec["rows"])


def test_explain_analyze_and_metrics_say_which_scan_streams(deployment):
    engine = deployment["engine"]
    before = engine.counters_total.snapshot()
    texts = {}
    for name in STATEMENTS:
        texts[name] = "\n".join(r[0] for r in engine.execute_sql(
            "explain analyze " + sql_of(name), engine.create_session("tpch")).rows())
    m = re.search(r"Exchange: .*; (\d+) bytes exchanged; scan batches: (\d+) resident, "
                  r"(\d+) generated", texts["q3"])
    assert m, texts["q3"]
    assert int(m.group(1)) == deployment["runs"]["q3"][-1][2].exchange_bytes
    assert (int(m.group(2)), int(m.group(3))) == (BATCHES, 0)
    assert "over the entry cap" not in texts["q3"]
    assert f"scan batches: 0 resident, {BATCHES} generated" in texts["q1"]
    assert re.search(r"site .*dist\.scan\.lineitem\.cache: .*, 1 scans over the entry cap "
                     r"\(streamed\)", texts["q1"]), texts["q1"]
    total = engine.counters_total
    assert total.mesh_scan_batches_resident - before.mesh_scan_batches_resident == BATCHES
    assert total.mesh_scan_batches_generated - before.mesh_scan_batches_generated == BATCHES
    server = CoordinatorServer(engine, port=0)
    server.start()
    try:
        body = urllib.request.urlopen(server.url + "/v1/metrics", timeout=10).read().decode()
    finally:
        server.stop()
    for field in ("exchange_bytes", "mesh_scan_batches_resident", "mesh_scan_batches_generated"):
        assert f"trino_tpu_{field}_total {getattr(total, field)}\n" in body
        assert getattr(total, field) > 0
    line = re.search(r'trino_tpu_site_scans_not_resident_total\{site="([^"]*)",'
                     r'why="over_cap"\} (\d+)', body)
    assert line and line.group(1).endswith("dist.scan.lineitem.cache")
    assert int(line.group(2)) >= 4  # every q1 so far


def test_the_cells_files_load_and_pass_the_loaders_checks():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = Cell(CELL)
    assert cell.chips == 4 and cell.config["name"] == "tpch_sf10_4chip"
    built = {k: cell.config[k] for k in ("connector", "catalog", "sf", "rehearse_sf",
                                         "split_rows", "chips")}
    assert built == {"connector": "tpch", "catalog": "tpch", "sf": 10, "rehearse_sf": 0.01,
                     "split_rows": 1 << 21, "chips": 4}
    assert list(cell.statements) == ["q1", "q3"]
    traffic = dict(cell.traffic)
    assert traffic.pop("why") and traffic.pop("name") == "mesh_sf10_stream"
    assert traffic == {"loop": "closed", "clients": 1, "poll_interval": 0.002,
                       "slots": ["q1", "q3"], "order": "seeded_rounds",
                       "params": {"q1": "fixed", "q3": "fixed"}, "check": "all",
                       "statement_timeout_s": 900, "trace_seconds": 15}
    assert {m["name"] for m in cell.end_to_end} == {"stmt_s.geomean", "setup_s"}
    config = next(c for c in bench["configs"] if c["name"] == "tpch_sf10_4chip")
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    for text in (config["source"], config["why"], workload["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert config["source"] == cell.config["source"] and "scale factor 10" in config["source"]
    assert config["file"] == "benchmark/configs/tpch_sf10_4chip.json"
    for key in config["reduced"]:
        check_name(key, "reduced")
        assert key in cell.config["reduced"]
    assert set(cell.config["reduced"]) == {"queries", "substitution_parameters",
                                           "refresh_functions", "streams"}
    # the guarantees of the SF1 mesh configuration, word for word
    assert cell.config["guarantees"] == Cell("sf1_mesh4_joins").config["guarantees"]
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["sf1_mesh4_joins", CELL] and len(bench["workloads"]) == 9
    # every metric the SF1 mesh cell reports, this one reports, and the three new ones
    sf1 = {m["name"] for m in Cell("sf1_mesh4_joins").per_layer}
    assert {m["name"] for m in cell.per_layer} == sf1 >= set(NEW_METRICS)
    for entry in bench["per_layer"]:
        if entry["name"] not in NEW_METRICS:
            continue
        unit, better, source, layer = NEW_METRICS[entry["name"]]
        check_name(entry["name"], "metric")
        assert check_unit(entry["unit"], entry["name"]) == unit
        assert (entry["better"], entry["source"], entry["layer"]) == (better, source, layer)
        assert entry["moves"] == "stmt_s.geomean"
        assert entry["workloads"] == ["sf1_mesh4_joins", CELL]
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
