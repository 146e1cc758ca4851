"""DB-API federation connector (the JDBC-family analog; reference:
plugin/trino-base-jdbc BaseJdbcClient) over sqlite3."""

import sqlite3

import pytest

from trino_tpu import Engine
from trino_tpu.connectors.dbapi import DbapiConnector
from trino_tpu.connectors.tpch import TpchConnector


@pytest.fixture(scope="module")
def remote_db(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("db") / "remote.db")
    con = sqlite3.connect(path)
    con.execute("create table users (uid integer, region integer, "
                "name text, balance real)")
    rows = [(i, i % 5, None if i % 11 == 0 else f"user-{i % 7}",
             round(i * 1.5, 2)) for i in range(1000)]
    con.executemany("insert into users values (?,?,?,?)", rows)
    con.execute("create table tiny (k integer, v text)")
    con.executemany("insert into tiny values (?,?)",
                    [(1, "a"), (2, "b"), (3, None)])
    con.commit()
    con.close()
    return path


@pytest.fixture(scope="module")
def fed_engine(remote_db):
    e = Engine()
    e.register_catalog("db", DbapiConnector(
        lambda: sqlite3.connect(remote_db), split_rows=256))
    e.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=1 << 12))
    return e, e.create_session("db")


def test_remote_scan_and_aggregate(fed_engine):
    e, s = fed_engine
    rows = e.execute_sql(
        "select region, count(*) c, sum(balance) sb from users "
        "group by region order by region", s).rows()
    assert len(rows) == 5
    assert sum(r[1] for r in rows) == 1000
    assert rows[0][2] == pytest.approx(sum(i * 1.5 for i in range(0, 1000, 5)))


def test_remote_strings_and_nulls(fed_engine):
    e, s = fed_engine
    rows = e.execute_sql(
        "select name, count(*) c from users group by name "
        "order by name nulls last", s).rows()
    names = [r[0] for r in rows]
    assert names[-1] is None  # the NULL group survives
    assert set(n for n in names if n is not None) == \
        {f"user-{i}" for i in range(7)}
    assert e.execute_sql("select v from tiny where k = 3", s).rows() == \
        [(None,)]


def test_remote_federated_join(fed_engine):
    """A remote table joins a generator-connector table — cross-catalog
    federation through the shared page machinery."""
    e, s = fed_engine
    rows = e.execute_sql(
        "select count(*) c from db.users, tpch.nation "
        "where users.region = nation.n_regionkey and users.uid < 100",
        s).rows()
    assert rows == [(100 * 5,)]


def test_remote_metadata_and_splits(fed_engine, remote_db):
    e, s = fed_engine
    conn = e.catalogs["db"]
    assert conn.tables() == ["tiny", "users"]
    assert conn.row_count("users") == 1000
    assert conn.column_range("users", "uid") == (0, 999)
    splits = conn.splits("users")
    assert sum(1 for _ in splits) >= 4  # rowid ranges cover the table
    # churn detection: a new string value after the snapshot errors clearly
    import sqlite3 as _sq
    con = _sq.connect(remote_db)
    con.execute("update tiny set v='brand-new' where k=1")
    con.commit(); con.close()
    with pytest.raises(RuntimeError, match="changed since"):
        for sp in conn.splits("tiny"):
            conn.generate(sp, ["v"])
    with pytest.raises(ValueError, match="unsupported remote identifier"):
        conn.column_range('users"; drop table users; --', "uid")


# ------------------------------------------- applyTopN / applyJoin pushdown
def test_topn_pushdown_ships_n_rows(fed_engine):
    """Limit(Sort(scan)) over the federation connector issues ORDER BY ...
    LIMIT remotely (ConnectorMetadata.applyTopN analog): results identical,
    the pushed handle visible, and the remote read bounded."""
    e, s = fed_engine
    conn = e.catalogs["db"]
    before = conn.pushed_queries
    rows = e.execute_sql(
        "select uid, balance from users order by balance desc, uid limit 7",
        s).rows()
    assert conn.pushed_queries > before, "topN did not push to the remote"
    assert len(rows) == 7
    assert [r[0] for r in rows] == list(range(999, 992, -1))
    # exactness is preserved by the local Sort+Limit above the pushed scan
    assert rows[0][1] == pytest.approx(999 * 1.5)


def test_topn_pushdown_respects_nulls_ordering(fed_engine):
    e, s = fed_engine
    rows = e.execute_sql(
        "select name from users order by name desc nulls last limit 3",
        s).rows()
    assert all(r[0] is not None for r in rows)
    assert rows[0][0] == "user-6"


def _undo_churn(fed_engine, remote_db):
    """test_metadata_surfaces mutates tiny.v past its dictionary snapshot on
    purpose; restore the value and refresh the snapshot for the join tests."""
    import sqlite3 as _sq

    e, _ = fed_engine
    con = _sq.connect(remote_db)
    con.execute("update tiny set v='a' where k=1")
    con.commit()
    con.close()
    e.catalogs["db"]._tables.pop("tiny", None)


def test_join_pushdown_runs_remotely(fed_engine, remote_db):
    """An inner equi-join of two tables in the SAME remote database executes
    there (ConnectorMetadata.applyJoin analog); the engine scans the joined
    handle, split-parallel over the left side."""
    _undo_churn(fed_engine, remote_db)
    e, s = fed_engine
    conn = e.catalogs["db"]
    sql = ("select u.uid, u.balance, t.v from users u "
           "join tiny t on u.region = t.k "
           "order by u.uid limit 10")
    before = conn.pushed_queries
    got = e.execute_sql(sql, s).rows()
    assert conn.pushed_queries > before, "join did not push to the remote"
    # oracle: region in (1,2,3) joins tiny's k; v maps 1->a, 2->b, 3->NULL
    import sqlite3

    vmap = {1: "a", 2: "b", 3: None}
    want = [(i, i * 1.5, vmap[i % 5]) for i in range(1000)
            if i % 5 in vmap][:10]
    assert [(r[0], round(r[1], 2), r[2]) for r in got] \
        == [(u, round(b, 2), v) for u, b, v in want]


def test_join_pushdown_access_checks_source_tables(fed_engine):
    """The virtual handle is not a grantable object: access control checks
    the SOURCE tables, so a denial on either side still blocks the query."""
    e, s = fed_engine
    from trino_tpu.spi.security import AccessDeniedError

    class DenyTiny:
        def check_can_select(self, user, catalog, table):
            if table == "tiny":
                raise AccessDeniedError("tiny is restricted")

        def __getattr__(self, name):  # every other check allows
            return lambda *a, **k: None

    saved = e.access_control
    e.access_control = DenyTiny()
    try:
        with pytest.raises(AccessDeniedError):
            e.execute_sql("select u.uid from users u "
                          "join tiny t on u.region = t.k limit 1", s)
    finally:
        e.access_control = saved


def test_filter_blocks_join_pushdown(fed_engine, remote_db):
    """A residual filter above a side keeps the join local (the applyJoin
    contract) — results still correct, no push recorded."""
    _undo_churn(fed_engine, remote_db)
    e, s = fed_engine
    conn = e.catalogs["db"]
    before = conn.pushed_queries
    got = e.execute_sql(
        "select count(*) c from users u join tiny t on u.region = t.k "
        "where u.balance > 100 and t.v = 'a'", s).rows()
    want = sum(1 for i in range(1000)
               if i % 5 == 1 and i * 1.5 > 100)
    assert int(got[0][0]) == want


def test_pushed_spec_travels_with_split(fed_engine, remote_db):
    """A WORKER process builds its own connector and never saw the planning
    pass: the virtual-handle spec rides the split (pickled), so the scan
    reconstructs remotely (review finding: handles lived only in the
    planner's registry)."""
    import pickle
    import sqlite3 as _sq

    _undo_churn(fed_engine, remote_db)
    e, s = fed_engine
    conn = e.catalogs["db"]
    handle = conn.apply_join("users", "tiny", [("region", "k")],
                             ["l0", "l1", "r0"], ["uid", "region"], ["v"])
    splits = conn.splits(handle)
    assert splits and splits[0].pushed_spec is not None
    # fresh instance = the worker's connector (no _pushed state)
    worker_conn = DbapiConnector(lambda: _sq.connect(remote_db),
                                 split_rows=256)
    sp = pickle.loads(pickle.dumps(splits[0]))
    page = worker_conn.generate(sp, ["l0", "r0"])
    assert page.columns[0].shape[0] > 0
    # deduped registration: same spec returns the same handle
    again = conn.apply_join("users", "tiny", [("region", "k")],
                            ["l0", "l1", "r0"], ["uid", "region"], ["v"])
    assert again == handle


@pytest.fixture()
def probe_catalog(fed_engine):
    e, _ = fed_engine
    if "m2" not in e.catalogs:
        from trino_tpu.connectors.memory import MemoryConnector

        e.register_catalog("m2", MemoryConnector())
        sm = e.create_session("m2")
        e.execute_sql("create table probe (uid bigint, tag bigint)", sm)
        e.execute_sql("insert into probe values (5, 1), (9, 2), (5, 3), "
                      "(700, 4)", sm)
    return e


def test_index_join_lookup(fed_engine, probe_catalog):
    """Index join (reference: operator/index/IndexLoader): a small local
    probe ships its distinct keys into a remote WHERE-IN lookup instead of
    scanning the whole remote table."""
    e, s = fed_engine
    conn = e.catalogs["db"]
    before = conn.pushed_queries
    r = e.execute_sql(
        "select p.uid, p.tag, u.balance from m2.default.probe p, "
        "db.default.users u where p.uid = u.uid order by p.tag", s).to_pandas()
    assert list(r["tag"]) == [1, 2, 3, 4]
    assert abs(r["balance"].iloc[0] - 7.5) < 1e-9
    assert abs(r["balance"].iloc[3] - 1050.0) < 1e-9
    # the build side went through a pushed index-lookup handle
    assert conn.pushed_queries > before
    spec = list(conn._pushed.values())[-1]
    assert spec["kind"] == "index"
    assert sorted(spec["keys"]) == [5, 9, 700]
