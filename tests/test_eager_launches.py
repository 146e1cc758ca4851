"""A warm group-by statement launches nothing outside ``_jit`` (PR 39).

A census of launches, as ``tests/test_hidden_syncs.py`` is one of syncs.  An eager
``jnp`` call is a device program of its own that no counter of the executor sees: a
primitive bound with no trace open goes through ``EvalTrace.process_primitive`` (whose
``impl`` is ``dispatch.apply_primitive``, held by a ``partial`` and so not patchable
where it is defined), a jitted ``jnp`` wrapper (``jnp.where``, ``lexsort``, ``c[idx]``'s
``less`` and ``select``) through ``pjit._pjit_call_impl_python`` -- once, and from then
on through jax's C++ fast path, which no Python sees.  So the module runs with the fast
path off (``_get_fastpath_data`` answers None, the caches cleared first): every launch
passes Python, and one whose stack holds a frame under ``trino_tpu/`` but neither
``_jit``'s ``run`` nor ``_generate`` (the two counted launch chokepoints) is eager.
Launches inside a launch (``apply_primitive`` jits its primitive) count once.

Before PR 39 a warm q1 made 283 of them at SF0.01 (the direct group-by's init, the
finalize's slices, the Sort's rank gather, keys, ``lexsort``, a gather a column and a
mask), ``agg_lineitem`` 94, ``agg_orders`` 69, q3 118, and q1 pulled seven times after
its last step (the flag, the group count, the envelope flag, the projection's
``compact.counts``, ``sort.count``, ``sort.pull``, ``history.actuals``; and ``page``,
which moves no byte).
"""

import pathlib
import sys
import threading

import jax
import pytest
from jax._src import core as jax_core
from jax._src import pjit as jax_pjit

import trino_tpu
from benchmark.statements import agg_lineitem, agg_orders, q1, q3
from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.exec import boundary

ROOT = str(pathlib.Path(trino_tpu.__file__).resolve().parent)
BOUNDARY = str(pathlib.Path(boundary.__file__).resolve())


class avg_of_sums:
    """(PR 44) A group-by over a group-by's one page: the outer one reads its key's bounds
    with ONE counted program (``agg.key_bounds``) and ONE pull before its only step, and
    is direct-indexed; nothing of it is eager."""

    VALIDATION = {}

    @staticmethod
    def render(p):
        return ("select o_orderstatus, k, avg(t) a from (select o_orderstatus, "
                "o_custkey % 5 k, o_orderpriority, sum(o_totalprice) t from orders "
                "group by o_orderstatus, o_custkey % 5, o_orderpriority) x "
                "group by o_orderstatus, k order by o_orderstatus, k"), None


STATEMENTS = {"q1": q1, "agg_lineitem": agg_lineitem, "agg_orders": agg_orders, "q3": q3,
              "avg_of_sums": avg_of_sums}
# (eager launches at most, pulls after the last group-by step at most).  q3's hash
# group-by reads its overflow flag at the end of its last chunk and once more with the
# count (two pulls that were four), and its history record pulls a join's build rows
CEILINGS = {"q1": (8, 3), "agg_lineitem": (8, 3), "agg_orders": (8, 3), "q3": (12, 5),
            "avg_of_sums": (8, 3)}
TAILS = {"avg_of_sums": 3}  # two finalizes and the sort; the others one and the sort
STEPS = ("agg.direct.step", "agg.direct.batch", "agg.hash.insert_masked",
         "agg.hash.insert_compact")


def sql_of(name):
    statement = STATEMENTS[name]
    return statement.render(statement.VALIDATION)[0]


class Launches:
    """While open, every launch this thread makes from under ``trino_tpu/`` outside
    ``_jit`` and ``_generate`` is listed in ``eager`` as "primitive file:line function"."""

    def __init__(self):
        self.eager = []
        self._thread = threading.get_ident()
        self._depth = 0
        self._bind = jax_core.EvalTrace.process_primitive
        self._call = jax_pjit._pjit_call_impl_python

    def _note(self, what):
        if self._depth or threading.get_ident() != self._thread:
            return
        where, f = None, sys._getframe(2)
        while f is not None:
            code = f.f_code
            if code.co_filename == BOUNDARY and code.co_name in ("run", "_generate"):
                return  # a counted launch: a program of `_jit`, a generator's
            if where is None and code.co_filename.startswith(ROOT):
                where = f"{code.co_filename[len(ROOT) + 1:]}:{f.f_lineno} {code.co_name}"
            f = f.f_back
        if where is not None:
            self.eager.append(f"{what} {where}")

    def __enter__(self):
        census = self

        def process_primitive(trace, primitive, args, params):
            census._note(str(primitive))
            census._depth += 1
            try:
                return census._bind(trace, primitive, args, params)
            finally:
                census._depth -= 1

        def call_impl(*args, **params):
            census._note("jit:" + str(params.get("name")))
            census._depth += 1
            try:
                return census._call(*args, **params)
            finally:
                census._depth -= 1

        jax_core.EvalTrace.process_primitive = process_primitive
        jax_pjit._pjit_call_impl_python = call_impl
        return self

    def __exit__(self, *exc):
        jax_core.EvalTrace.process_primitive = self._bind
        jax_pjit._pjit_call_impl_python = self._call


@pytest.fixture(scope="module")
def engine():
    """``Engine()`` over TPC-H at SF0.01 (lineitem in 13 splits), in a process whose
    jitted calls all pass Python: no fast path is kept from here on, none from before."""
    real = jax_pjit._get_fastpath_data
    jax_pjit._get_fastpath_data = lambda *args, **kwargs: None
    jax.clear_caches()
    engine = Engine()
    engine.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=1 << 13))
    yield engine
    engine._invalidate()
    jax_pjit._get_fastpath_data = real


def test_the_census_sees_an_eager_call_and_a_jitted_wrapper(engine):
    """Not blind: a primitive, a jitted ``jnp`` wrapper called twice (the second call
    would take the fast path), and a counted program that is none of its business."""
    import jax.numpy as jnp

    def under_the_program():  # a frame under trino_tpu/, by file name
        code = compile("a = jnp.arange(8) + 1\nb = jnp.where(a > 2, a, 0)\n"
                       "b = jnp.where(a > 3, a, 0)\nc = program(a)\n",
                       BOUNDARY, "exec")
        exec(code, {"jnp": jnp,
                    "program": boundary._jit(lambda x: x * 2, site="test.program")})

    with Launches() as census:
        under_the_program()
    assert len(census.eager) >= 5, census.eager  # iota, add, gt x2, where x2 at least
    assert sum("jit:_where" in e for e in census.eager) == 2, census.eager
    assert not any("test.program" in e or "mul" in e for e in census.eager), census.eager


@pytest.mark.parametrize("name", sorted(STATEMENTS))
def test_a_warm_replay_launches_nothing_outside_jit(engine, name):
    session = engine.create_session("tpch")
    for _ in range(3):  # cold, the advisor's re-plan or the learned bucket, warm
        engine.execute_sql(sql_of(name), session)
    with Launches() as census:
        result = engine.execute_sql(sql_of(name), session)
    assert len(result) > 0
    eager, pulls_after = CEILINGS[name]
    assert len(census.eager) <= eager, census.eager
    counters = engine.last_query_counters
    assert counters.tail_compiled == TAILS.get(name, 2) and counters.tail_eager == 0
    assert counters.groupby_observed_direct == (name == "avg_of_sums")
    spans = sorted((s for s in engine.last_query_trace["spans"]
                    if s["name"] in ("dispatch", "host_pull")),
                   key=lambda s: s["start_s"])
    sites = [(s["name"], s["attributes"].get("site")) for s in spans]
    last_step = max(i for i, (kind, site) in enumerate(sites)
                    if kind == "dispatch" and site in STEPS)
    after = [site for kind, site in sites[last_step + 1:] if kind == "host_pull"]
    assert len(after) <= pulls_after, after
    # the group count and the envelope flag ride the overflow flag's pull, a page that
    # knows its live count is neither counted nor packed again
    for gone in ("agg.group_count", "agg.finalize.envelope", "sort.count",
                 "compact.counts"):
        assert gone not in after, after
    assert "sort.pull" in after, after
