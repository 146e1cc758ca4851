"""Kernel-level microbenchmarks (the JMH-suite analog).

Reference: core/trino-main/src/test/java/io/trino/operator/Benchmark*.java
(BenchmarkHashAndStreamingAggregationOperators, BenchmarkHashJoinOperators,
BenchmarkGroupByHash, ...) — per-operator throughput isolated from SQL.

Runs on the default backend (the TPU where one is attached; JAX_PLATFORMS=cpu
selects the CPU backend).  Prints one JSON line per kernel:
  {"kernel": ..., "rows": N, "ms": median_ms, "rows_per_sec": r}

Usage:  python bench_micro.py [--rows 4000000] [--kernels a,b,...]
"""

import argparse
import json
import os
import sys
import time

import jax

from benchenv import env_info

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def _timeit(fn, *args, runs=5):
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(runs):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def bench_hashagg_insert(n):
    """Group-by insert: n rows into ~n/4 distinct int64 keys."""
    from trino_tpu.ops import hashagg
    from trino_tpu.types import BIGINT

    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, n // 4, n))
    vals = jnp.asarray(rng.random(n))
    state = hashagg.groupby_init(n, (np.int64,), ((np.float64, 0.0),))

    @jax.jit
    def step(state, keys, vals):
        return hashagg.groupby_insert(
            state, (keys,), (BIGINT,), jnp.ones((n,), bool),
            [(vals, None)], ["sum"])

    return _timeit(step, state, keys, vals)


def bench_join_build(n):
    from trino_tpu.ops.hashjoin import build_insert, build_table_init
    from trino_tpu.page import Field, Page, Schema
    from trino_tpu.types import BIGINT

    key = jnp.asarray((np.arange(n, dtype=np.int64) * 7919) % (1 << 40))
    page = Page(Schema((Field("k", BIGINT),)), (key,), (None,), None)

    @jax.jit
    def build(key):
        jt = build_table_init(4 * n, page)
        return build_insert(jt, (key,), (BIGINT,), jnp.ones((n,), bool))

    return _timeit(build, key)


def bench_join_probe(n):
    from trino_tpu.ops.hashjoin import build_insert, build_table_init, probe
    from trino_tpu.page import Field, Page, Schema
    from trino_tpu.types import BIGINT

    nb = max(n // 8, 1)
    rng = np.random.default_rng(0)
    bkey = np.unique((np.arange(nb, dtype=np.int64) * 7919) % (1 << 40))
    page = Page(Schema((Field("k", BIGINT),)), (jnp.asarray(bkey),), (None,),
                None)
    jt = jax.jit(lambda k: build_insert(
        build_table_init(4 * len(bkey), page), (k,), (BIGINT,),
        jnp.ones((len(bkey),), bool)))(jnp.asarray(bkey))
    pkeys = jnp.asarray(rng.choice(bkey, n))

    @jax.jit
    def run(jt, pkeys):
        return probe(jt, (pkeys,), (BIGINT,), jnp.ones((n,), bool))

    return _timeit(run, jt, pkeys)


# ------------------------------------------------------- XLA-vs-Pallas A/B
# Round-13 kernels (ops/pallas_kernels.py) benchmarked against the XLA paths
# they shadow, with result equality asserted per the parity contract (probe/
# compact byte-identical; build/insert observable-identical — slot layouts
# are backend-private).  Each _ab kernel prints its own one-JSON-line payload
# with both throughputs.  On CPU the pallas half runs INTERPRETED (correctness
# signal only — the wall time is the interpreter's, not Mosaic's); the row
# counts are capped so that stays tractable.  On TPU both halves are compiled
# and the speedup column is the A/B (not yet run on the chip).

_AB_ROWS_CAP = 1 << 13


def _ab_line(name, n, t_xla, t_pallas, extra=None):
    import jax as _jax
    rec = {"kernel": name, "rows": n,
           "xla_ms": round(t_xla * 1000, 3),
           "pallas_ms": round(t_pallas * 1000, 3),
           "xla_rows_per_sec": round(n / t_xla),
           "pallas_rows_per_sec": round(n / t_pallas),
           "pallas_speedup": round(t_xla / t_pallas, 3),
           "equal": True,
           "interpret": _jax.default_backend() != "tpu",
           "env": env_info()}
    rec.update(extra or {})
    print(json.dumps(rec), flush=True)


def _per_backend(fn_builder):
    """Build + run one timed closure per backend.  pallas_kernels.force is a
    TRACE-time switch, so each backend gets its own freshly-traced jit."""
    from trino_tpu.ops import pallas_kernels as pk

    out = {}
    for mode in (False, True):
        pk.force(mode)
        try:
            out[mode] = fn_builder()
        finally:
            pk.force(None)
    return out[False], out[True]


def bench_join_probe_ab(n):
    """hashjoin.probe: XLA while_loop gathers vs the Pallas inversion probe —
    byte-identical (row_ids, matched) over the SAME table."""
    import numpy as np

    from trino_tpu.ops.hashjoin import build_insert, build_table_init, probe
    from trino_tpu.page import Field, Page, Schema
    from trino_tpu.types import BIGINT

    n = min(n, _AB_ROWS_CAP)
    nb = max(n // 8, 1)
    rng = np.random.default_rng(0)
    bkey = np.unique((np.arange(nb, dtype=np.int64) * 7919) % (1 << 40))
    page = Page(Schema((Field("k", BIGINT),)), (jnp.asarray(bkey),), (None,),
                None)
    jt = jax.jit(lambda k: build_insert(
        build_table_init(4 * len(bkey), page), (k,), (BIGINT,),
        jnp.ones((len(bkey),), bool)))(jnp.asarray(bkey))
    pkeys = jnp.asarray(rng.choice(bkey, n))

    def build():
        # all-ones masks build INSIDE the trace: a closed-over device array
        # is baked into the executable as a constant, and the A/B must time
        # the kernel, not a constant's upload
        run = jax.jit(lambda jt, pkeys: probe(jt, (pkeys,), (BIGINT,),
                                              jnp.ones((n,), bool)))
        t = _timeit(run, jt, pkeys)
        return t, run(jt, pkeys)

    (t_x, (r_x, m_x)), (t_p, (r_p, m_p)) = _per_backend(build)
    assert np.array_equal(np.asarray(r_x), np.asarray(r_p))
    assert np.array_equal(np.asarray(m_x), np.asarray(m_p))
    _ab_line("join_probe_ab", n, t_x, t_p,
             {"capacity": int(jt.capacity), "hits": int(np.asarray(m_x).sum())})
    return None


def bench_join_build_ab(n):
    """hashjoin build insertion: XLA scatter-min claims vs the Pallas in-kernel
    claim loop — observable-identical (word sets, dup/overflow counters, probe
    results over either table)."""
    import numpy as np

    from trino_tpu.ops.hashjoin import build_insert, build_table_init, probe
    from trino_tpu.page import Field, Page, Schema
    from trino_tpu.types import BIGINT

    n = min(n, _AB_ROWS_CAP)
    key = jnp.asarray((np.arange(n, dtype=np.int64) * 7919) % (1 << 40))
    schema = Schema((Field("k", BIGINT),))

    def build():
        # the page is (re)built from the traced argument INSIDE the jit: a
        # closed-over device page would bake its columns in as constants —
        # the A/B must time the kernel, not constant uploads
        run = jax.jit(lambda key: build_insert(
            build_table_init(4 * n, Page(schema, (key,), (None,), None)),
            (key,), (BIGINT,), jnp.ones((n,), bool)))
        t = _timeit(run, key)
        return t, run(key)

    (t_x, jt_x), (t_p, jt_p) = _per_backend(build)
    assert np.array_equal(np.sort(np.asarray(jt_x.table)),
                          np.sort(np.asarray(jt_p.table)))
    assert int(jt_x.dup_count) == int(jt_p.dup_count)
    assert bool(jt_x.overflow) == bool(jt_p.overflow)
    from trino_tpu.ops import pallas_kernels as pk
    pk.force(False)
    try:
        px = jax.jit(lambda jt, key: probe(jt, (key,), (BIGINT,),
                                           jnp.ones((n,), bool)))
        r1, m1 = px(jt_x, key)
        r2, m2 = px(jt_p, key)
    finally:
        pk.force(None)
    assert np.array_equal(np.asarray(r1), np.asarray(r2))
    assert np.array_equal(np.asarray(m1), np.asarray(m2))
    _ab_line("join_build_ab", n, t_x, t_p, {"capacity": int(jt_x.capacity)})
    return None


def bench_hashagg_insert_ab(n):
    """Group-by slot insertion: XLA rounds of gather + scatter-min vs the
    Pallas claim kernel — identical key -> accumulator maps."""
    import numpy as np

    from trino_tpu.ops import hashagg
    from trino_tpu.types import BIGINT

    n = min(n, _AB_ROWS_CAP)
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, n // 4, n))
    vals = jnp.asarray(rng.random(n))

    def build():
        def step_fn(state, keys, vals):
            # mask built in-trace: no closed-over device constants (CLAUDE.md)
            return hashagg.groupby_insert(state, (keys,), (BIGINT,),
                                          jnp.ones((n,), bool),
                                          [(vals, None)], ["sum"])
        run = jax.jit(step_fn)
        state = hashagg.groupby_init(n, (np.int64,), ((np.float64, 0.0),))
        t = _timeit(run, state, keys, vals)
        out = run(state, keys, vals)
        occ, (k,), (acc,) = hashagg.agg_finalize(out)
        occ = np.asarray(occ)
        return t, dict(zip(np.asarray(k)[occ].tolist(),
                           np.round(np.asarray(acc)[occ], 9).tolist()))

    (t_x, g_x), (t_p, g_p) = _per_backend(build)
    assert g_x == g_p
    _ab_line("hashagg_insert_ab", n, t_x, t_p, {"groups": len(g_x)})
    return None


def bench_compact_ab(n):
    """The pipeline-boundary masked-lane pack at 1/16 selectivity: XLA
    index-then-gather vs the Pallas prefix-sum + one-hot matmul — byte-identical."""
    import numpy as np

    from trino_tpu.ops.arrays import compact_rows

    n = min(n, 1 << 16)
    rng = np.random.default_rng(0)
    valid = jnp.asarray(rng.random(n) < 1 / 16)
    # no DOUBLE column: compact_enabled keeps those on the XLA path
    cols = (jnp.asarray(rng.integers(0, 1 << 40, n)),
            jnp.asarray(rng.random(n).astype(np.float32)),
            jnp.asarray(rng.random(n) < 0.5))
    bucket = n // 8

    def build():
        run = jax.jit(lambda cols, valid: compact_rows(cols, valid, bucket))
        t = _timeit(run, cols, valid)
        packed, total = run(cols, valid)
        return t, ([np.asarray(p) for p in packed], int(total))

    (t_x, (p_x, c_x)), (t_p, (p_p, c_p)) = _per_backend(build)
    assert c_x == c_p
    for a, b in zip(p_x, p_p):
        assert np.array_equal(a, b)
    _ab_line("compact_ab", n, t_x, t_p, {"bucket": bucket, "live": c_x})
    return None


def bench_exchange_route(n):
    """bucketize + all_to_all over an 8-worker mesh (or fewer devices)."""
    from functools import partial

    from jax.sharding import NamedSharding, PartitionSpec as PS

    from jax import shard_map

    from trino_tpu.ops.exchange import bucketize, exchange_all_to_all
    from trino_tpu.parallel.mesh import WORKER_AXIS, worker_mesh

    W = min(8, len(jax.devices()))
    if W < 2:
        return None
    mesh = worker_mesh(W)
    per = n // W
    rng = np.random.default_rng(0)
    cols = jnp.asarray(rng.integers(0, 1 << 40, (W, per)))
    sharded = NamedSharding(mesh, PS(WORKER_AXIS))
    cols = jax.device_put(cols, sharded)

    @partial(shard_map, mesh=mesh, in_specs=PS(WORKER_AXIS),
             out_specs=PS(WORKER_AXIS))
    def route(c):
        c = c[0]
        pid = (c % W).astype(jnp.int32)
        packed, pvalid, _ = bucketize((c,), jnp.ones_like(c, bool), pid, W,
                                      per)
        recv, rvalid = exchange_all_to_all(packed, pvalid, WORKER_AXIS, W)
        return recv[0][None], rvalid[None]

    return _timeit(jax.jit(route), cols)


def bench_exchange_append(n):
    """The round-18 device-resident exchange batch step: bucketize +
    all_to_all + append_rows into the carried [cap+1] receive buffer — the
    per-batch device cost that replaced a per-batch host materialize.  Pair
    with exchange_route to price the append itself."""
    from functools import partial

    from jax.sharding import NamedSharding, PartitionSpec as PS

    from jax import shard_map
    from trino_tpu.ops.arrays import append_rows
    from trino_tpu.ops.exchange import bucketize, exchange_all_to_all
    from trino_tpu.parallel.mesh import WORKER_AXIS, worker_mesh

    W = min(8, len(jax.devices()))
    if W < 2:
        return None
    mesh = worker_mesh(W)
    per = n // W
    cap = 2 * per  # headroom for skewed receives, like the capacity ladder
    rng = np.random.default_rng(0)
    sharded = NamedSharding(mesh, PS(WORKER_AXIS))
    cols = jax.device_put(jnp.asarray(rng.integers(0, 1 << 40, (W, per))),
                          sharded)
    bufs = jax.device_put(jnp.zeros((W, cap + 1), cols.dtype), sharded)
    cursor = jax.device_put(jnp.zeros((W,), jnp.int64), sharded)

    @partial(shard_map, mesh=mesh,
             in_specs=(PS(WORKER_AXIS),) * 3,
             out_specs=(PS(WORKER_AXIS),) * 3)
    def step(c, bufs, cursor):
        c, bufs, cursor = c[0], bufs[0], cursor[0]
        pid = (c % W).astype(jnp.int32)
        packed, pvalid, _ = bucketize((c,), jnp.ones_like(c, bool), pid, W,
                                      per)
        recv, rvalid = exchange_all_to_all(packed, pvalid, WORKER_AXIS, W)
        nb, ncur, of = append_rows((bufs,), cursor,
                                   (recv[0].reshape(-1),), rvalid.reshape(-1))
        return nb[0][None], ncur[None], of[None]

    return _timeit(jax.jit(step), cols, bufs, cursor)


def bench_sort(n):
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, 1 << 40, n))
    return _timeit(jax.jit(jnp.sort), keys)


def bench_window_scan(n):
    """Segmented prefix sums over ~n/64 partitions (the window-frame core)."""
    from trino_tpu.ops import window as W

    rng = np.random.default_rng(0)
    part = np.sort(rng.integers(0, n // 64, n))
    starts = jnp.asarray(np.concatenate([[True], part[1:] != part[:-1]]))
    vals = jnp.asarray(rng.random(n))

    @jax.jit
    def run(vals, starts):
        return W.segmented_scan_sum(vals, starts, starts)

    return _timeit(run, vals, starts)


def bench_compact(n):
    """The pipeline-boundary scatter-pack at 1/16 selectivity."""
    rng = np.random.default_rng(0)
    valid = jnp.asarray(rng.random(n) < 1 / 16)
    col = jnp.asarray(rng.integers(0, 1 << 40, n))
    bucket = n // 8

    @jax.jit
    def run(col, valid):
        pos = jnp.cumsum(valid) - 1
        dst = jnp.where(valid & (pos < bucket), pos, bucket).astype(jnp.int32)
        return jnp.zeros((bucket + 1,), col.dtype).at[dst].set(col)[:bucket]

    return _timeit(run, col, valid)


def bench_exchange_stream_vs_spool(n):
    """Inter-process exchange latency: one fragment-output envelope handed
    producer->consumer through the STREAMING buffer endpoint (in-memory,
    long-poll + token ack) vs the spooled filesystem exchange.  Prints its own
    line with both numbers; returns None (not a rows/sec kernel)."""
    import tempfile

    from trino_tpu.exec.fte import (SpoolingExchange,
                                    deserialize_fragment_output,
                                    serialize_fragment_output)
    from trino_tpu.server.cluster import _OutputBuffer

    rng = np.random.default_rng(0)
    nrows = min(n, 1 << 20)
    cols = [rng.integers(0, 1 << 40, nrows), rng.random(nrows)]
    env = serialize_fragment_output(cols, [None, None], (None, None))

    def via_spool():
        with tempfile.TemporaryDirectory() as d:
            ex = SpoolingExchange(d)
            ex.commit("t0", 0, env)
            return deserialize_fragment_output(ex.read("t0"))

    def via_stream():
        buf = _OutputBuffer()
        buf.add(env)
        buf.finish()
        out, _, _ = buf.get(0, max_wait=0.1)
        assert buf.get(1, max_wait=0.01)[1]  # ack + complete
        return deserialize_fragment_output(out)

    def med(fn, runs=7):
        fn()
        ts = []
        for _ in range(runs):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    t_spool, t_stream = med(via_spool), med(via_stream)
    print(json.dumps({
        "kernel": "exchange_stream_vs_spool", "rows": nrows,
        "spool_ms": round(t_spool * 1000, 3),
        "stream_ms": round(t_stream * 1000, 3),
        "stream_speedup": round(t_spool / t_stream, 2),
        "env": env_info(),
    }), flush=True)
    return None


def bench_dispatch_coalesce(nrows):
    """Dispatch-coalescing overhead curve: a fixed-size grouped aggregation
    over 16 uniform splits, executed at batch K in {1,2,4,8,16} — the
    per-dispatch overhead is (warm wall at K=1 - warm wall at K=16)/Δdispatch.
    On the CPU mesh the deltas are python+dispatch overhead (~ms); what a
    saved dispatch is worth on the chip is the curve this benchmark exists to
    capture (not measured yet)."""
    from trino_tpu import Engine
    from trino_tpu.connectors.tpch import TpchConnector

    n_splits = 16
    sf = max(nrows / 1_500_000, 16 / 1_500_000)  # orders rows = 1.5M * sf
    engine = Engine()
    engine.register_catalog(
        "tpch", TpchConnector(sf=sf, split_rows=max(nrows // n_splits, 1)))
    sql = ("select o_orderstatus, count(*) c, sum(o_totalprice) s "
           "from orders group by o_orderstatus order by o_orderstatus")
    curve = []
    for k in (1, 2, 4, 8, 16):
        s = engine.create_session("tpch")
        engine.session_properties.set_property(s, "dispatch_batch", k)
        engine.execute_sql(sql, s)  # cold: plan + XLA compile
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            engine.execute_sql(sql, s)
            ts.append(time.perf_counter() - t0)
        c = engine.last_query_counters
        curve.append({"batch": k, "warm_ms": round(sorted(ts)[1] * 1000, 3),
                      **c.as_dict()})
    print(json.dumps({"kernel": "dispatch_coalesce", "rows": nrows,
                      "splits": n_splits, "curve": curve, "env": env_info()}),
          flush=True)
    return None


def bench_h2d_transfer(nrows):
    """Host->device staging bandwidth curve over page sizes — the transfer
    the device buffer pool's page tier saves on every warm scan.  For each
    page size: median wall of jax.device_put(numpy int64 column) +
    block_until_ready, reported as bytes/s.  On the CPU backend this is a
    memcpy (upper bound); on the chip it is the real H2D bill, and
    (bytes_saved from bench.py per_query) / (bytes/s here) estimates the
    wall-clock the cache bought."""
    import jax

    import numpy as np

    curve = []
    size = 1 << 16
    while size <= max(nrows, 1 << 16):
        arr = np.arange(size, dtype=np.int64)
        def put(arr=arr):
            jax.device_put(arr).block_until_ready()
        put()  # warm: allocator + executable paths
        ts = []
        for _ in range(7):
            t0 = time.perf_counter()
            put()
            ts.append(time.perf_counter() - t0)
        med = sorted(ts)[len(ts) // 2]
        curve.append({"rows": size, "bytes": size * 8,
                      "ms": round(med * 1000, 4),
                      "bytes_per_sec": round(size * 8 / med)})
        size <<= 2
    print(json.dumps({"kernel": "h2d_transfer", "rows": nrows,
                      "curve": curve, "env": env_info()}), flush=True)
    return None


KERNELS = {
    "hashagg_insert": bench_hashagg_insert,
    "join_build": bench_join_build,
    "join_probe": bench_join_probe,
    "exchange_route": bench_exchange_route,
    "exchange_append": bench_exchange_append,
    "sort": bench_sort,
    "window_scan": bench_window_scan,
    "compact": bench_compact,
    "exchange_stream_vs_spool": bench_exchange_stream_vs_spool,
    "dispatch_coalesce": bench_dispatch_coalesce,
    "h2d_transfer": bench_h2d_transfer,
    # round-13 XLA-vs-Pallas A/B variants (result equality asserted)
    "join_probe_ab": bench_join_probe_ab,
    "join_build_ab": bench_join_build_ab,
    "hashagg_insert_ab": bench_hashagg_insert_ab,
    "compact_ab": bench_compact_ab,
}


def _filter_stderr():
    """XLA:CPU's AOT cache floods fd 2 with 'cpu_aot_loader' warnings
    (CLAUDE.md: harmless).  They come from C++ logging, so a python-level
    sys.stderr wrapper never sees them — pump the real fd through a filter
    thread so captured A/B output (2> redirected to a .log)
    stays readable.  An atexit hook restores fd 2 and JOINS the pump: a
    daemon thread alone dies at interpreter exit before forwarding whatever
    is still in the pipe — which is exactly where a crashing run's traceback
    sits, and an empty .log from a chip run is an undiagnosable failure."""
    import atexit
    import threading

    r, w = os.pipe()
    orig = os.dup(2)
    os.dup2(w, 2)
    os.close(w)

    def pump():
        buf = b""
        while True:
            try:
                chunk = os.read(r, 65536)
            except OSError:
                break
            if not chunk:
                break
            buf += chunk
            *lines, buf = buf.split(b"\n")
            for ln in lines:
                if b"cpu_aot_loader" not in ln:
                    os.write(orig, ln + b"\n")
        if buf and b"cpu_aot_loader" not in buf:
            os.write(orig, buf + b"\n")

    t = threading.Thread(target=pump, daemon=True, name="stderr-filter")
    t.start()

    def restore():
        try:
            sys.stderr.flush()
        except Exception:
            pass
        # putting orig back on fd 2 closes the pipe's only write end: the
        # pump sees EOF, forwards the tail (e.g. an uncaught traceback
        # printed during shutdown) to the real stderr, and exits
        os.dup2(orig, 2)
        t.join(timeout=10)

    atexit.register(restore)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=4_000_000)
    ap.add_argument("--kernels", type=str, default=",".join(KERNELS),
                    help="comma list from KERNELS; *_ab variants run the "
                         "XLA-vs-Pallas comparison (row counts capped; "
                         "interpret mode off-TPU)")
    args = ap.parse_args()
    _filter_stderr()
    env = env_info()
    for name in args.kernels.split(","):
        fn = KERNELS.get(name.strip())
        if fn is None:
            continue
        try:
            t = fn(args.rows)
        except Exception as e:  # one kernel must not kill the suite
            print(json.dumps({"kernel": name, "error": f"{type(e).__name__}: {e}",
                              "env": env}),
                  flush=True)
            continue
        if t is None:
            continue
        print(json.dumps({
            "kernel": name, "rows": args.rows, "ms": round(t * 1000, 3),
            "rows_per_sec": round(args.rows / t), "env": env,
        }), flush=True)


if __name__ == "__main__":
    main()
