"""Chip smoke: the served TPC-H SF1 path, end to end, on one directly attached TPU.

    python chip_smoke.py              # one chip: Engine + CoordinatorServer, q1/q3/q4/q9/q18
                                      # through POST /v1/statement, each checked against pandas
    python chip_smoke.py --mesh       # four chips: ONLY q1/q3/q18 over worker_mesh() of all
                                      # devices, checked against pandas and the one-chip answer
                                      # (cold, q1+q3 alone took 750 s: allow 1800 s)
    python chip_smoke.py --rehearse   # the same control flow on the CPU backend at SF0.01
                                      # (JAX_PLATFORMS=cpu; never reports a TPU)

ONE process, the only one to touch JAX (a chip belongs to one process at a time).
Without ``--rehearse`` a backend that is not a TPU is a non-zero exit with no result
line.  Any exception or failed check in any phase is a non-zero exit.  Data is
generated on the device from the connector's seed; nothing is read from the repo
but its code, and the compile cache is whatever ``trino_tpu/__init__.py`` placed
(``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``).

Earlier lines are facts for CHANGES.md ("one run, not a benchmark"); the LAST line
of stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.

This is a smoke, not a measure: ``python -m benchmark.run`` (BENCHMARK.json,
benchmark/README.md) is the yardstick.  The file stays, both phases, until the
four-chip mesh has a benchmark cell of its own (PERF.md section 7, ROADMAP R1):
``--mesh`` is the only four-chip path until then.  The five statement texts and
their pandas twins below are also what ``scripts/query_counters.py`` traces.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

SERVED = ("q1", "q3", "q4", "q9", "q18")
MESHED = ("q1", "q3", "q18")
# oracle columns that the pandas twins leave as scaled-decimal ints (x100)
ORACLE_SCALE = {"q18": {"o_totalprice": 100.0, "l_quantity": 100.0}}
# where the pandas twin's column order is not the statement's SELECT order
ORACLE_ORDER = {"q3": ["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]}
# avg(decimal(p,2)) is a decimal(p,2) in SQL and an exact float mean in pandas:
# those columns agree to half a unit of the last decimal place
ORACLE_DECIMALS = {"q1": {"avg_qty": 2, "avg_pr": 2, "avg_dc": 2}}


QUERIES = {
    "q1": """
    select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
           sum(l_extendedprice) as sum_base_price,
           sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
           avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
           avg(l_discount) as avg_disc, count(*) as count_order
    from lineitem where l_shipdate <= date '1998-12-01' - interval '90' day
    group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus""",
    "q3": """
    select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
           o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
      and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
      and l_shipdate > date '1995-03-15'
    group by l_orderkey, o_orderdate, o_shippriority
    order by revenue desc, o_orderdate limit 10""",
    "q4": """
    select o_orderpriority, count(*) as order_count from orders
    where o_orderdate >= date '1993-07-01'
      and o_orderdate < date '1993-07-01' + interval '3' month
      and exists (select 1 from lineitem where l_orderkey = o_orderkey
                  and l_commitdate < l_receiptdate)
    group by o_orderpriority order by o_orderpriority""",
    "q9": """
    select nation, o_year, sum(amount) as sum_profit from (
      select n_name as nation, extract(year from o_orderdate) as o_year,
        l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity as amount
      from part, supplier, lineitem, partsupp, orders, nation
      where s_suppkey = l_suppkey and ps_suppkey = l_suppkey and ps_partkey = l_partkey
        and p_partkey = l_partkey and o_orderkey = l_orderkey
        and s_nationkey = n_nationkey and p_name like '%green%') as profit
    group by nation, o_year order by nation, o_year desc""",
    "q18": """
    select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice, sum(l_quantity)
    from customer, orders, lineitem
    where o_orderkey in (select l_orderkey from lineitem group by l_orderkey
                         having sum(l_quantity) > 300)
      and c_custkey = o_custkey and o_orderkey = l_orderkey
    group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    order by o_totalprice desc, o_orderdate limit 100""",
}

# the columns the pandas twins read, per table: only these are pulled to the host
ORACLE_COLUMNS = {
    "lineitem": ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
                 "l_discount", "l_tax", "l_shipdate", "l_orderkey", "l_partkey",
                 "l_suppkey", "l_commitdate", "l_receiptdate"],
    "customer": ["c_custkey", "c_mktsegment", "c_name"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority",
               "o_totalprice", "o_orderpriority"],
    "part": ["p_partkey", "p_name"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
    "nation": ["n_nationkey", "n_name"],
}


class _HostTables:
    """Lazy, cached host-side copies of the pandas twins' input columns."""

    def __init__(self, conn):
        self.conn = conn
        self._cache: dict = {}

    def __getitem__(self, t):
        import pandas as pd

        if t in self._cache:
            return self._cache[t]
        conn = self.conn
        dicts = conn.dictionaries(t)
        names = ORACLE_COLUMNS[t]
        # ONE generate per split for every column (one compiled program per
        # table, not one per column)
        parts: dict = {name: [] for name in names}
        for sp in conn.splits(t):
            page = conn.generate(sp, list(names))
            valid = np.asarray(page.valid_mask())
            for name in names:
                parts[name].append(np.asarray(page.column(name))[valid])
        cols = {}
        for name in names:
            arr = np.concatenate(parts[name])
            d = dicts.get(name)
            if d is not None:
                arr = d.decode(arr)
            cols[name] = arr
        df = pd.DataFrame(cols)
        self._cache[t] = df
        return df


def cpu_q1(T):
    df = T["lineitem"]
    cutoff = (np.datetime64("1998-12-01") - np.timedelta64(90, "D")
              - np.datetime64("1970-01-01")).astype(np.int64)
    m = df[df["l_shipdate"].to_numpy() <= cutoff]
    disc = m["l_discount"].to_numpy() / 100.0
    tax = m["l_tax"].to_numpy() / 100.0
    price = m["l_extendedprice"].to_numpy() / 100.0
    g = m.assign(dp=price * (1 - disc), ch=price * (1 - disc) * (1 + tax),
                 qty=m["l_quantity"].to_numpy() / 100.0, pr=price, dc=disc)
    r = g.groupby(["l_returnflag", "l_linestatus"]).agg(
        sum_qty=("qty", "sum"), sum_base=("pr", "sum"), sum_dp=("dp", "sum"),
        sum_ch=("ch", "sum"), avg_qty=("qty", "mean"), avg_pr=("pr", "mean"),
        avg_dc=("dc", "mean"), cnt=("dp", "size")).reset_index()
    return r.sort_values(["l_returnflag", "l_linestatus"])


def cpu_q3(T):
    c = T["customer"]; o = T["orders"]; l = T["lineitem"]
    cutoff = (np.datetime64("1995-03-15") - np.datetime64("1970-01-01")).astype(np.int64)
    c2 = c[c["c_mktsegment"] == "BUILDING"][["c_custkey"]]
    o2 = o[o["o_orderdate"].to_numpy() < cutoff][
        ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"]]
    l2 = l[l["l_shipdate"].to_numpy() > cutoff][
        ["l_orderkey", "l_extendedprice", "l_discount"]]
    j = o2.merge(c2, left_on="o_custkey", right_on="c_custkey")
    j = l2.merge(j, left_on="l_orderkey", right_on="o_orderkey")
    rev = (j["l_extendedprice"].to_numpy() / 100.0) * (1 - j["l_discount"].to_numpy() / 100.0)
    j = j.assign(revenue=rev)
    r = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])["revenue"].sum().reset_index()
    return r.sort_values(["revenue", "o_orderdate"], ascending=[False, True]).head(10)


def cpu_q4(T):
    o = T["orders"]; l = T["lineitem"]
    lo = (np.datetime64("1993-07-01") - np.datetime64("1970-01-01")).astype(np.int64)
    hi = (np.datetime64("1993-10-01") - np.datetime64("1970-01-01")).astype(np.int64)
    od = o["o_orderdate"].to_numpy()
    o2 = o[(od >= lo) & (od < hi)]
    late = l[l["l_commitdate"].to_numpy() < l["l_receiptdate"].to_numpy()]
    keys = np.unique(late["l_orderkey"].to_numpy())
    m = o2[np.isin(o2["o_orderkey"].to_numpy(), keys)]
    r = m.groupby("o_orderpriority").size().reset_index(name="order_count")
    return r.sort_values("o_orderpriority")


def cpu_q9(T):
    p = T["part"]; s = T["supplier"]; l = T["lineitem"]
    ps = T["partsupp"]; o = T["orders"]; n = T["nation"]
    p2 = p[p["p_name"].astype(str).str.contains("green")][["p_partkey"]]
    j = l.merge(p2, left_on="l_partkey", right_on="p_partkey")
    j = j.merge(s[["s_suppkey", "s_nationkey"]], left_on="l_suppkey", right_on="s_suppkey")
    j = j.merge(ps[["ps_partkey", "ps_suppkey", "ps_supplycost"]],
                left_on=["l_partkey", "l_suppkey"], right_on=["ps_partkey", "ps_suppkey"])
    j = j.merge(o[["o_orderkey", "o_orderdate"]], left_on="l_orderkey", right_on="o_orderkey")
    j = j.merge(n[["n_nationkey", "n_name"]], left_on="s_nationkey", right_on="n_nationkey")
    amount = (j["l_extendedprice"].to_numpy() / 100.0) * (1 - j["l_discount"].to_numpy() / 100.0) \
        - (j["ps_supplycost"].to_numpy() / 100.0) * (j["l_quantity"].to_numpy() / 100.0)
    year = (j["o_orderdate"].to_numpy().astype("datetime64[D]")).astype("datetime64[Y]").astype(int) + 1970
    j = j.assign(amount=amount, o_year=year)
    r = j.groupby(["n_name", "o_year"])["amount"].sum().reset_index()
    return r.sort_values(["n_name", "o_year"], ascending=[True, False])


def cpu_q18(T):
    c = T["customer"]; o = T["orders"]; l = T["lineitem"]
    qty = l.groupby("l_orderkey")["l_quantity"].sum()
    big = qty[qty > 30000].index  # l_quantity is a scaled decimal (x100)
    o2 = o[o["o_orderkey"].isin(big)]
    j = o2.merge(c[["c_custkey", "c_name"]], left_on="o_custkey", right_on="c_custkey")
    j = j.merge(l[["l_orderkey", "l_quantity"]], left_on="o_orderkey", right_on="l_orderkey")
    r = j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice"])[
        "l_quantity"].sum().reset_index()
    return r.sort_values(["o_totalprice", "o_orderdate"],
                         ascending=[False, True]).head(100)


CPU_QUERIES = {"q1": cpu_q1, "q3": cpu_q3, "q4": cpu_q4, "q9": cpu_q9,
               "q18": cpu_q18}


class SmokeFailure(AssertionError):
    pass


def _say(**facts) -> None:
    print(json.dumps(facts, default=str), flush=True)


def _column(values, scale: float = 1.0):
    """One result column in comparable form: strings stay strings, everything
    else becomes float64 (dates as days since the epoch)."""
    import pandas as pd

    vals = list(values)
    if vals and isinstance(vals[0], str):
        try:  # ISO dates arrive as strings over HTTP
            return (pd.to_datetime(vals, format="%Y-%m-%d").to_numpy()
                    .astype("datetime64[D]").astype(np.int64).astype(np.float64))
        except ValueError:
            return np.array(vals, dtype=object)
    s = pd.Series(vals)
    if pd.api.types.is_datetime64_any_dtype(s):
        return s.to_numpy().astype("datetime64[D]").astype(np.int64).astype(np.float64)
    return s.astype(np.float64).to_numpy() / scale


def check_answer(name: str, got, want, what: str) -> None:
    """Positional, order-sensitive comparison of an engine answer (pandas frame)
    with the pandas oracle's frame — every statement has an ORDER BY."""
    want = want[ORACLE_ORDER.get(name, list(want.columns))]
    if got.shape != want.shape:
        raise SmokeFailure(f"{name} {what}: shape {got.shape} != oracle {want.shape}")
    for j, wname in enumerate(want.columns):
        g = _column(got.iloc[:, j])
        w = _column(want.iloc[:, j], ORACLE_SCALE.get(name, {}).get(wname, 1.0))
        if g.dtype == object or w.dtype == object:
            same = list(g) == list(w)
        else:
            places = ORACLE_DECIMALS.get(name, {}).get(wname)
            atol = 1e-6 if places is None else 0.5 * 10.0 ** -places + 1e-9
            same = np.allclose(g, w, rtol=1e-9, atol=atol)
        if not same:
            raise SmokeFailure(f"{name} {what}: column {j} ({got.columns[j]} vs "
                               f"oracle {wname}) differs:\n{got.head()}\n{want.head()}")


def _kernel_facts() -> dict:
    from trino_tpu.ops import pallas_kernels as pk

    on = pk.use_pallas()
    return {"use_pallas": on, "interpret": pk.pallas_interpret(),
            "hash_probe_insert_capacities": [2, pk.PALLAS_TABLE_MAX] if on else None,
            "compact_out_rows_max": pk.COMPACT_OUT_MAX if on else None}


def _cache_files() -> int:
    import jax

    d = jax.config.jax_compilation_cache_dir
    return sum(len(files) for _, _, files in os.walk(d)) if d and os.path.isdir(d) else 0


def _counters(c) -> dict:
    return {"compiles": c.compiles, "compile_s": round(c.compile_s, 3),
            "dispatches": c.device_dispatches, "host_bytes": c.host_bytes_pulled}


def served_phase(engine, oracle, on_tpu: bool) -> None:
    """q1/q3/q4/q9/q18 through Client.execute -> POST /v1/statement, each once
    cold and twice warm, every answer against the pandas oracle; warm runs
    must not compile."""
    import jax
    from trino_tpu.server.client import Client
    from trino_tpu.server.server import CoordinatorServer

    srv = CoordinatorServer(engine, port=0)
    srv.start()
    try:
        client = Client(srv.url, catalog="tpch")
        for name in SERVED:
            sql = QUERIES[name]
            t0 = time.perf_counter()
            want = CPU_QUERIES[name](oracle)
            oracle_s = round(time.perf_counter() - t0, 2)

            def run(what):
                t0 = time.perf_counter()
                res = client.execute(sql, timeout=1100.0)
                wall = time.perf_counter() - t0
                c = _counters(engine.last_query_counters)
                check_answer(name, res.to_pandas(), want, what)
                if not c["dispatches"]:
                    raise SmokeFailure(f"{name} {what}: no device dispatch — the "
                                       "answer did not come from the execute path")
                return {"s": round(wall, 4), "rows": len(res.rows), **c}

            cold = run("cold")
            # the adaptive advisor may re-plan ONCE from the cold run's actuals,
            # and the new plan compiles: that run is cold too, not warm
            replan = None
            warm = [run("warm1")]
            if warm[0]["compiles"]:
                replan, warm = warm[0], [run("warm1")]
            warm.append(run("warm2"))
            for w in warm:
                if w["compiles"]:
                    raise SmokeFailure(f"{name} warm run compiled {w['compiles']} "
                                       f"programs ({w['compile_s']}s)")
            _say(query=name, rows=cold["rows"], cold_s=cold["s"],
                 warm_s=[w["s"] for w in warm],
                 cold_compiles=cold["compiles"], cold_compile_s=cold["compile_s"],
                 replan_run=replan, warm_dispatches=warm[-1]["dispatches"],
                 warm_host_bytes=warm[-1]["host_bytes"], oracle="equal",
                 oracle_s=oracle_s)
    finally:
        srv.stop()

    pool = engine.buffer_pool.info()
    resident = pool["per_table"].get("tpch.lineitem", {})
    if not resident.get("entries"):
        raise SmokeFailure(f"buffer pool holds no lineitem pages: {pool['per_table']}")
    stats = jax.devices()[0].memory_stats()
    if on_tpu and not stats:
        raise SmokeFailure("memory_stats() on the device is empty")
    if on_tpu and stats["bytes_in_use"] < resident["bytes"]:
        raise SmokeFailure(f"device holds {stats['bytes_in_use']} bytes, fewer than "
                           f"the {resident['bytes']} of resident lineitem pages")
    _say(lineitem_pages_resident=resident["entries"],
         lineitem_resident_bytes=resident["bytes"],
         page_cache_budget_bytes=pool["budget_bytes"],
         peak_bytes_in_use=(stats or {}).get("peak_bytes_in_use"),
         bytes_limit=(stats or {}).get("bytes_limit"))


def mesh_phase(engine, oracle, on_tpu: bool) -> None:
    """q1/q3/q18 over worker_mesh() of every device (one process, SPMD), each
    against the pandas oracle and the one-chip answer; every device must hold
    bytes after the scans, and the per-worker shard stats are printed."""
    import jax
    from trino_tpu.parallel.mesh import worker_mesh

    n = len(jax.devices())
    if n < 4:
        raise SmokeFailure(f"--mesh needs four devices, found {n}")
    mesh = worker_mesh(n)
    session = engine.create_session("tpch")
    for name in MESHED:
        sql = QUERIES[name]
        want = CPU_QUERIES[name](oracle)
        t0 = time.perf_counter()
        local = engine.execute_sql(sql, session).to_pandas()
        local_s = time.perf_counter() - t0
        check_answer(name, local, want, "one-chip")
        walls = []
        for what in ("mesh-cold", "mesh-warm"):
            t0 = time.perf_counter()
            dist = engine.execute_sql(sql, session, distributed=True,
                                      mesh=mesh).to_pandas()
            walls.append(round(time.perf_counter() - t0, 3))
            check_answer(name, dist, want, what)
            check_answer(name, dist, local, what + " vs one-chip")
        c = engine.last_query_counters
        _say(query=name, workers=n, one_chip_cold_s=round(local_s, 3),
             mesh_cold_s=walls[0], mesh_warm_s=walls[1], oracle="equal",
             one_chip="equal", **_counters(c))
        for rec in c.shard_stats:
            _say(query=name, shard_stats=rec)
        if on_tpu:  # per statement: a cut run still shows where the scan lived
            in_use = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in jax.devices()]
            _say(query=name, per_device_peak_bytes_in_use=in_use)
            if not all(in_use):
                raise SmokeFailure(f"{name}: a device never held a byte: {in_use}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: only the one-process mesh phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU backend at SF0.01 (the one way onto the CPU)")
    args = ap.parse_args(argv)
    if args.rehearse:
        # the CPU backend's page cache defaults to OFF; the smoke's residency
        # check needs it, as on the chip where it is a quarter of HBM
        os.environ.setdefault("TRINO_TPU_PAGE_CACHE", str(1 << 30))
        if args.mesh and "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                       + " --xla_force_host_platform_device_count=4").strip()

    import jax

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if on_tpu == args.rehearse:
        print(f"chip_smoke: backend is {dev.platform!r}; "
              + ("--rehearse is for the CPU backend" if on_tpu else
                 "a TPU is required (--rehearse runs the CPU rehearsal)"),
              file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}

    from trino_tpu import Engine
    from trino_tpu.connectors.tpch import TpchConnector

    sf = 0.01 if args.rehearse else 1
    files_before = _cache_files()
    _say(phase="mesh" if args.mesh else "served", sf=sf, device=device,
         cache_dir=jax.config.jax_compilation_cache_dir,
         cache_files_before=files_before, **_kernel_facts())

    t0 = time.perf_counter()
    conn = TpchConnector(sf=sf, split_rows=1 << 21)
    engine = Engine()
    engine.register_catalog("tpch", conn)
    oracle = _HostTables(conn)
    if args.mesh:
        mesh_phase(engine, oracle, on_tpu)
    else:
        served_phase(engine, oracle, on_tpu)
    _say(total_s=round(time.perf_counter() - t0, 1),
         cache_files_written=_cache_files() - files_before,
         lineitem_rows=conn.row_count("lineitem"), orders_rows=conn.row_count("orders"))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
