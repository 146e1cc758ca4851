"""Static device-boundary lint over ``trino_tpu/exec/*.py``.

CLAUDE.md's rule — executor code MUST go through ``_jit`` (not bare
``jax.jit``) and ``_host`` (never a loose ``np.asarray`` of device values) or
the dispatch/transfer is invisible to the per-query budget counters — was a
doc note until round 6.  This test makes it an enforced invariant:

- ``jax.jit`` may be REFERENCED only inside the ``_jit`` helper itself (the
  one place the accounting wrapper is built).  Round 11 tightened this from
  call-sites to attribute references: ``partial(jax.jit, ...)`` smuggled an
  uncounted/uninjectable dispatch past the call-only check for four rounds
  (exec/spill's old ``_route_sorted`` was the escapee).
- ``jax.device_get(`` is an unbatched, uncounted device->host pull — it may
  appear only inside ``_host`` or on a line annotated ``# host-ok[: reason]``
  asserting the value is already host-resident.
- ``np.asarray(`` may appear only
  (a) inside a small set of allowlisted HOST-SIDE helpers (below, each with
      the reason it is exempt), or
  (b) on a line annotated ``# host-ok[: reason]`` asserting the value is
      already host-resident (python lists, dictionary values, arrays
      previously pulled through ``_host``/``jax.device_get``).

A new un-annotated np.asarray is treated as an unaccounted device pull until
proven otherwise — the failure mode this PR's sweep fixed dozens of times
over (per-column pulls in exchange/serialize/merge paths that never showed on
the budget).  If your np.asarray really is host-side, say so with the marker;
if it isn't, batch it through ``_host``.

Round 7 adds the ATTRIBUTION rule over the same files (local_executor.py,
distributed.py, fte.py, ...): every ``_host(...)`` call must pass a
``site=`` tag (or carry ``# site-ok: <reason>`` on the call line), and every
``_jit(...)`` call whose function argument is anonymous (a lambda/closure
expression) must too — a named function self-labels through ``__name__``.
Without this, per-site boundary attribution (EXPLAIN ANALYZE's site table,
the budget-failure dump, /v1/metrics site series) silently rots to
"untagged" as new call sites land.
"""

import ast
import pathlib

import pytest

EXEC_DIR = pathlib.Path(__file__).resolve().parent.parent / "trino_tpu" / "exec"
OPS_DIR = pathlib.Path(__file__).resolve().parent.parent / "trino_tpu" / "ops"

# functions whose BODY may use np.asarray freely, with why:
ASARRAY_ALLOWED_FUNCS = {
    "_host",              # the accounting chokepoint itself
    "_host_page",         # batched page pull built on _host
    "_page_to_device",    # host->device direction (no pull)
    "_finalize_aggs",     # host finalize over accumulators its callers pulled
    "_combine_limbs_vec",  # host two-limb recombine (input already pulled)
}

MARKER = "# host-ok"

# functions whose BODY may call jax.device_get freely, with why:
DEVICE_GET_ALLOWED_FUNCS = {
    "_host",              # the accounting chokepoint itself
}

# functions whose BODY may call jax.device_put freely, with why:
DEVICE_PUT_ALLOWED_FUNCS = {
    "_page_to_device",    # THE sanctioned H2D chokepoint: prefetch staging
    # and buffer-pool stores funnel through it (execution/bufferpool has its
    # own _to_device twin outside exec/)
}

DEVICE_MARKER = "# device-ok"


def _exec_files():
    files = sorted(EXEC_DIR.glob("*.py"))
    assert files, EXEC_DIR
    return files


SITE_MARKER = "# site-ok"

# functions whose BODY may call _host/_jit without a site tag (the helpers
# that thread their caller's site through):
SITE_ALLOWED_FUNCS = {
    "_host_page",  # passes its own ``site`` parameter through to _host
}

STATS_MARKER = "# stats-ok"

# functions whose BODY may touch ``.stats.setdefault`` directly, with why:
STATS_ALLOWED_FUNCS = {
    "_node_stats",  # THE registration chokepoint: captures the structural
    # node path + CBO estimate the plan-history feed needs (round 15)
}


class _Scan(ast.NodeVisitor):
    def __init__(self, lines):
        self.lines = lines
        self.func_stack = []
        self.jit_hits = []      # (lineno, enclosing function)
        self.asarray_hits = []  # (lineno, enclosing function)
        self.device_put_hits = []  # (lineno, enclosing function)
        self.device_get_hits = []  # (lineno, enclosing function)
        self.site_hits = []     # (lineno, enclosing function, callee)
        self.stats_hits = []    # (lineno, enclosing function)

    def visit_FunctionDef(self, node):
        self.func_stack.append(node.name)
        self.generic_visit(node)
        self.func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def _check_site(self, node, callee):
        """_host calls always need site=/marker; _jit calls need one unless
        the wrapped function is a NAME (self-labeling via __name__)."""
        if set(self.func_stack) & SITE_ALLOWED_FUNCS:
            return
        if any(kw.arg == "site" for kw in node.keywords):
            return
        if SITE_MARKER in self.lines[node.lineno - 1]:
            return
        if callee == "_jit" and node.args \
                and isinstance(node.args[0], (ast.Name, ast.Attribute)):
            return  # named step fn: _jit derives the site from __name__
        where = self.func_stack[-1] if self.func_stack else "<module>"
        self.site_hits.append((node.lineno, where, callee))

    def visit_Attribute(self, node):
        # ATTRIBUTE references, not just calls: `partial(jax.jit, ...)` and
        # `f = jax.device_get` alias the boundary away from the call-site
        # checks, so the raw reference is what the lint must flag
        if isinstance(node.value, ast.Name) and node.value.id == "jax":
            where = self.func_stack[-1] if self.func_stack else "<module>"
            if node.attr == "jit" and "_jit" not in self.func_stack:
                self.jit_hits.append((node.lineno, where))
            if node.attr == "device_get":
                if not (set(self.func_stack) & DEVICE_GET_ALLOWED_FUNCS) \
                        and MARKER not in self.lines[node.lineno - 1]:
                    self.device_get_hits.append((node.lineno, where))
        self.generic_visit(node)

    def visit_Call(self, node):
        f = node.func
        if isinstance(f, ast.Name) and f.id in ("_jit", "_host"):
            self._check_site(node, f.id)
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            where = self.func_stack[-1] if self.func_stack else "<module>"
            if f.value.id == "np" and f.attr == "asarray":
                if not (set(self.func_stack) & ASARRAY_ALLOWED_FUNCS) \
                        and MARKER not in self.lines[node.lineno - 1]:
                    self.asarray_hits.append((node.lineno, where))
            if f.value.id == "jax" and f.attr == "device_put":
                if not (set(self.func_stack) & DEVICE_PUT_ALLOWED_FUNCS) \
                        and DEVICE_MARKER not in self.lines[node.lineno - 1]:
                    self.device_put_hits.append((node.lineno, where))
        # round-15 rule: `<anything>.stats.setdefault(` outside _node_stats —
        # a raw registration skips the structural-path/estimate capture the
        # plan-history feed relies on
        if isinstance(f, ast.Attribute) and f.attr == "setdefault" \
                and isinstance(f.value, ast.Attribute) \
                and f.value.attr == "stats":
            where = self.func_stack[-1] if self.func_stack else "<module>"
            if not (set(self.func_stack) & STATS_ALLOWED_FUNCS) \
                    and STATS_MARKER not in self.lines[node.lineno - 1]:
                self.stats_hits.append((node.lineno, where))
        self.generic_visit(node)


def _scan(path):
    src = path.read_text()
    s = _Scan(src.splitlines())
    s.visit(ast.parse(src))
    return s


@pytest.mark.parametrize("path", _exec_files(), ids=lambda p: p.name)
def test_no_bare_jax_jit(path):
    s = _scan(path)
    assert not s.jit_hits, (
        f"{path.name}: bare jax.jit reference at "
        + ", ".join(f"line {ln} (in {fn})" for ln, fn in s.jit_hits)
        + " — use exec.boundary._jit so the dispatch is counted "
          "against the query budget (partial(jax.jit, ...) counts too)")


@pytest.mark.parametrize("path", _exec_files(), ids=lambda p: p.name)
def test_no_bare_device_get(path):
    """Round-11 rule: jax.device_get is an unbatched, uncounted D2H pull —
    invisible to the budget counters, the in-flight registry and the chaos
    injector.  Pull through _host (batched, counted) or annotate
    '# host-ok: <reason>' when the value is already host-resident."""
    s = _scan(path)
    assert not s.device_get_hits, (
        f"{path.name}: bare jax.device_get at "
        + ", ".join(f"line {ln} (in {fn})" for ln, fn in s.device_get_hits)
        + " — batch the pull through _host, or annotate "
          "'# host-ok: <reason>'")


@pytest.mark.parametrize("path", _exec_files(), ids=lambda p: p.name)
def test_no_loose_np_asarray(path):
    s = _scan(path)
    assert not s.asarray_hits, (
        f"{path.name}: loose np.asarray at "
        + ", ".join(f"line {ln} (in {fn})" for ln, fn in s.asarray_hits)
        + " — a device value must pull through _host (batched, counted); "
          "a host value needs a '# host-ok: <reason>' annotation")


@pytest.mark.parametrize("path", _exec_files(), ids=lambda p: p.name)
def test_no_bare_device_put(path):
    """Round-9 rule: H2D staging goes through the sanctioned chokepoints
    (_page_to_device / the buffer pool's store path) or carries a
    '# device-ok: <reason>' annotation — a loose jax.device_put is H2D
    traffic the page cache can neither serve nor account."""
    s = _scan(path)
    assert not s.device_put_hits, (
        f"{path.name}: bare jax.device_put at "
        + ", ".join(f"line {ln} (in {fn})" for ln, fn in s.device_put_hits)
        + " — stage through _page_to_device (or the buffer pool) so cached "
          "scans can serve it, or annotate '# device-ok: <reason>'")


@pytest.mark.parametrize("path", _exec_files(), ids=lambda p: p.name)
def test_every_boundary_call_is_attributed(path):
    """Every _jit/_host call site carries a site tag (or is self-labeling /
    explicitly marked), so per-site boundary attribution cannot silently rot
    back to 'untagged' as new executor code lands."""
    s = _scan(path)
    assert not s.site_hits, (
        f"{path.name}: unattributed boundary call at "
        + ", ".join(f"line {ln} ({callee} in {fn})"
                    for ln, fn, callee in s.site_hits)
        + " — pass site=\"<op.tag>\" (or '# site-ok: <reason>' if the call "
          "is intentionally untagged); named functions self-label for _jit")


@pytest.mark.parametrize("path", _exec_files(), ids=lambda p: p.name)
def test_stats_register_via_node_stats(path):
    """Round-15 rule: blocking operators register per-node stats through
    LocalExecutor._node_stats, never a bare ``self.stats.setdefault(`` —
    the helper captures the structural node path and CBO row estimate at
    registration, which is what lets clean-completion plan-history
    collection merge records across executors and the cluster.  Annotate
    '# stats-ok: <reason>' for a deliberate bypass."""
    s = _scan(path)
    assert not s.stats_hits, (
        f"{path.name}: bare self.stats.setdefault at "
        + ", ".join(f"line {ln} (in {fn})" for ln, fn in s.stats_hits)
        + " — register through _node_stats(node) so the plan-history feed "
          "sees the node, or annotate '# stats-ok: <reason>'")


PKG_DIR = EXEC_DIR.parent
COMPILE_MARKER = "# compile-ok"


def _pkg_files_outside_exec():
    """Every trino_tpu module OUTSIDE exec/ (exec/ has the stricter rule:
    jax.jit is banned there outright — only _jit may build one)."""
    files = sorted(p for p in PKG_DIR.rglob("*.py")
                   if EXEC_DIR not in p.parents
                   and "__pycache__" not in p.parts)
    assert files, PKG_DIR
    return files


def _untracked_jit_refs(path):
    """jax.jit attribute references outside exec/ missing a
    ``# compile-ok: <reason>`` annotation — each is an XLA compilation the
    round-17 compile observatory cannot see (no seen-signature detection,
    no compile span, no census record, no compile-aware stall verdict)."""
    src = path.read_text()
    lines = src.splitlines()
    hits = []
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "jax" and node.attr in ("jit", "pjit"):
            if COMPILE_MARKER not in lines[node.lineno - 1]:
                hits.append(node.lineno)
    return hits


@pytest.mark.parametrize("path", _pkg_files_outside_exec(),
                         ids=lambda p: str(p.relative_to(PKG_DIR)))
def test_jit_outside_exec_is_annotated(path):
    """Round-17 rule: a ``jax.jit`` reference outside exec/ is an XLA
    compile the observatory at the ``_jit`` chokepoint never sees — the new
    loose np.asarray.  Route it through the tracked wrapper, or annotate
    ``# compile-ok: <reason>`` stating why it is exempt (module-level
    kernels dispatched inside exec's _jit steps, host-side generation)."""
    hits = _untracked_jit_refs(path)
    assert not hits, (
        f"{path.relative_to(PKG_DIR)}: untracked jax.jit reference at "
        f"line(s) {', '.join(map(str, hits))} — route through "
        "exec.boundary._jit so the compile is observed (counted, "
        "span'd, census'd, compile-aware-stall-judged), or annotate "
        "'# compile-ok: <reason>'")


def _unnamed_generator_jits(path):
    """jax.jit calls in a connector whose first argument is not a
    ``site_program(...)`` call: a generator program the device trace would
    show as ``jit__lambda_`` or under one name for every table."""
    hits = []
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "jax" and node.attr in ("jit", "pjit"):
            hits.append(node.lineno)  # a reference that is not a call: unnamed
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id == "jax" \
                and node.func.attr in ("jit", "pjit"):
            first = node.args[0] if node.args else None
            if isinstance(first, ast.Call) \
                    and getattr(first.func, "id", None) == "site_program":
                hits.remove(node.func.lineno)
    return hits


@pytest.mark.parametrize(
    "path", sorted((PKG_DIR / "connectors").glob("*.py")),
    ids=lambda p: p.name)
def test_connector_generator_programs_are_named(path):
    """PR 25: a connector's jitted generator passes through
    ``tracing.site_program`` (``generate.<table>``), so that the device
    plane names page generation apart from the operators."""
    hits = _unnamed_generator_jits(path)
    assert not hits, (
        f"{path.name}: jax.jit at line(s) {', '.join(map(str, hits))} "
        "compiles a program without a site name — wrap the function in "
        "execution.tracing.site_program(fn, 'generate.<table>')")


def _pallas_call_hits(path):
    """pallas_call(...) invocations missing an ``interpret=`` keyword —
    both attribute form (pl.pallas_call) and a direct-imported name."""
    src = path.read_text()
    hits = []
    for node in ast.walk(ast.parse(src)):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        named = (isinstance(f, ast.Attribute) and f.attr == "pallas_call") \
            or (isinstance(f, ast.Name) and f.id == "pallas_call")
        if named and not any(kw.arg == "interpret" for kw in node.keywords):
            hits.append(node.lineno)
    return hits


def _ops_files():
    files = sorted(OPS_DIR.glob("*.py"))
    assert files, OPS_DIR
    return files


@pytest.mark.parametrize("path", _ops_files(), ids=lambda p: p.name)
def test_pallas_call_plumbs_interpret(path):
    """Round-13 rule: every pl.pallas_call in trino_tpu/ops/ must plumb an
    ``interpret=`` parameter.  A hard-coded device-only kernel can never run
    on the CPU mesh, which silently exempts it from the tier-1 parity tests —
    the interpret knob is what makes a Mosaic kernel testable off-device
    (pallas_kernels.pallas_interpret() is the standard source).  Kernel
    DISPATCH accounting needs no extra rule: ops kernels only run inside
    exec's _jit-wrapped step functions, which the exec-side lints above
    already police, so counters/faults/in-flight coverage is automatic."""
    hits = _pallas_call_hits(path)
    assert not hits, (
        f"{path.name}: pl.pallas_call without interpret= at line(s) "
        + ", ".join(map(str, hits))
        + " — plumb interpret (default pallas_kernels.pallas_interpret()) so "
          "the kernel body runs under the CPU-mesh parity tests")


SQL_DIR = PKG_DIR / "sql"
ADAPTIVE_MARKER = "# adaptive-ok"


def _adaptive_read_hits(path):
    """``.plan_history`` / ``.compile_log`` attribute reads in exec/ or sql/
    missing a ``# adaptive-ok: <reason>`` annotation.  Round-19 rule: the
    AdaptiveAdvisor (execution/adaptive.py) is THE chokepoint where recorded
    history and compile costs turn into plan decisions — an executor or
    planner module reading the stores directly grows a second, unaccounted
    decision path (no win-vs-price gate, no probation/demotion, no
    counters/EXPLAIN/flight visibility)."""
    src = path.read_text()
    lines = src.splitlines()
    hits = []
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Attribute) \
                and node.attr in ("plan_history", "compile_log"):
            if ADAPTIVE_MARKER not in lines[node.lineno - 1]:
                hits.append((node.lineno, node.attr))
    return hits


def _decision_input_files():
    files = sorted(list(EXEC_DIR.glob("*.py")) + list(SQL_DIR.rglob("*.py")))
    assert files, (EXEC_DIR, SQL_DIR)
    return files


@pytest.mark.parametrize("path", _decision_input_files(),
                         ids=lambda p: str(p.relative_to(PKG_DIR)))
def test_history_reads_route_through_advisor(path):
    """Round-19 rule: nothing under trino_tpu/exec/ or trino_tpu/sql/ reads
    ``plan_history``/``compile_log`` directly — decision logic lives in
    execution/adaptive.py (the engine consults it at admission; the planner
    consumes only the emitted correction facts).  Annotate
    '# adaptive-ok: <reason>' for a deliberate, non-decision read."""
    hits = _adaptive_read_hits(path)
    assert not hits, (
        f"{path.relative_to(PKG_DIR)}: direct decision-input read at "
        + ", ".join(f"line {ln} (.{attr})" for ln, attr in hits)
        + " — route the decision through execution.adaptive.AdaptiveAdvisor,"
          " or annotate '# adaptive-ok: <reason>'")


PULL_MARKER = "# pull-ok"

# The FROZEN set of device->host pull sites in exec/distributed.py (round
# 20).  The device-resident exchange's whole point is that the warm path
# pulls at exactly these sites — the distributed-budget suite pins the warm
# subset dynamically, and this rule pins the SITE NAMESPACE statically: a
# new `_host(..., site="dist...")` call is a new pull site until proven
# otherwise, the same failure mode the round-6 loose-np.asarray rule
# closed for the local executor.  The round-20 skew derivation consumes
# ints already pulled at these existing sites and must never need a new
# one.  Adding a site here is a deliberate act that should come with a
# budget-suite re-derivation (scripts/query_counters.py --distributed).
DIST_PULL_SITES = {
    "dist.build.dupcheck",
    "dist.hostfed.pull",
    "dist.shards.concat",
    "dist.shards.pull",
    "dist.join.buildsize",
    "dist.join.build_exchange",
    "dist.join.overflow",
    "dist.sort.sample",
    "dist.exchange.collect",
    "dist.exchange.route",
    "dist.exchange.flags",
    "dist.topn.states",
    "dist.agg.overflow",
    "dist.agg.compact",
    "dist.agg.groups",
    "dist.agg.states",
    "dist.stream.collect",
    "dist.stream.route",
    "dist.stream.flags",
}


def _dist_pull_hits(path, allowed=None):
    """``_host(...)`` calls in exec/distributed.py whose ``site=`` literal is
    NOT in the frozen pull-site set and whose line lacks a
    ``# pull-ok: <reason>`` annotation.  A site= that is not a string
    literal cannot be verified statically and needs the marker too."""
    allowed = DIST_PULL_SITES if allowed is None else allowed
    src = path.read_text()
    lines = src.splitlines()
    hits = []
    for node in ast.walk(ast.parse(src)):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "_host"):
            continue
        site = None
        for kw in node.keywords:
            if kw.arg == "site":
                if isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, str):
                    site = kw.value.value
                break
        if site is not None and site in allowed:
            continue
        if PULL_MARKER in lines[node.lineno - 1]:
            continue
        hits.append((node.lineno, site))
    return hits


def test_distributed_pull_sites_frozen():
    """Round-20 rule: the warm distributed path's host-pull bill is a
    handful of known sites (one batched flags pull per exchange run, the
    occupancy-sized agg pulls, ...).  Any NEW ``_host`` call in
    exec/distributed.py must either reuse a frozen site name or carry
    ``# pull-ok: <reason>`` — the per-shard skew derivation in particular
    is required to consume ints already pulled at existing sites, never to
    add a pull of its own."""
    path = EXEC_DIR / "distributed.py"
    hits = _dist_pull_hits(path)
    assert not hits, (
        f"distributed.py: _host call outside the frozen pull-site set at "
        + ", ".join(f"line {ln} (site={site!r})" for ln, site in hits)
        + " — reuse an existing dist.* site, or annotate "
          "'# pull-ok: <reason>' and re-derive the distributed budget "
          "ceilings (scripts/query_counters.py --distributed --sites)")


def test_pull_site_lint_catches_violations(tmp_path):
    """The pull-site rule must actually flag what it claims to."""
    bad = tmp_path / "dist.py"
    bad.write_text(
        "def f(x, _host, s):\n"
        "    a = _host([x], site='dist.exchange.flags')\n"   # frozen -> ok
        "    b = _host([x], site='dist.skew.extra')\n"       # line 3: flagged
        "    c = _host([x], site='dist.skew.extra')  # pull-ok: test\n"
        "    d = _host([x], site=s)\n"                       # line 5: flagged
        "    e = _host([x], site=s)  # pull-ok: test\n"
        "    return a, b, c, d, e\n")
    assert [(ln, site) for ln, site in _dist_pull_hits(bad)] == \
        [(3, "dist.skew.extra"), (5, None)]


def test_lint_catches_violations(tmp_path):
    """The lint must actually flag what it claims to (guards against the
    visitor silently matching nothing after a refactor)."""
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax, numpy as np\n"
        "from functools import partial\n"
        "def f(x):\n"
        "    g = jax.jit(lambda a: a)\n"               # line 4: flagged
        "    g2 = partial(jax.jit, static_argnames=('n',))\n"  # 5: flagged
        "    return np.asarray(x)\n"                   # line 6: flagged
        "def _jit(fn):\n"
        "    return jax.jit(fn)\n"
        "def _host(arrays):\n"
        "    return [np.asarray(a) for a in arrays]\n"
        "ok = np.asarray([1, 2])  # host-ok: literal\n"
        "def h(x):\n"
        "    y = jax.device_put(x)\n"                  # line 13: flagged
        "    z = jax.device_put(x)  # device-ok: test\n"
        "    w = jax.device_get(x)\n"                  # line 15: flagged
        "    w2 = jax.device_get(x)  # host-ok: test\n"
        "    return y, z, w, w2\n"
        "def _page_to_device(p):\n"
        "    return jax.device_put(p)\n"
        "def g(x, step):\n"
        "    a = _host([x])\n"                  # line 21: missing site
        "    b = _host([x], site='g.pull')\n"        # tagged -> ok
        "    c = _host([x])  # site-ok: test\n"      # marked -> ok
        "    d = _jit(lambda v: v)\n"            # line 24: anonymous
        "    e = _jit(step)\n"                       # named -> self-labels
        "    f2 = _jit(lambda v: v, site='g.step')\n"  # tagged -> ok
        "    return a, b, c, d, e, f2\n"
        "class X:\n"
        "    def reg(self, node):\n"
        "        s = self.stats.setdefault(id(node), {})\n"  # line 30: flagged
        "        s2 = self.stats.setdefault(id(node), {})  # stats-ok: test\n"
        "        return s, s2\n"
        "    def _node_stats(self, node):\n"
        "        return self.stats.setdefault(id(node), {})\n")  # chokepoint
    s = _scan(bad)
    assert [ln for ln, _ in s.jit_hits] == [4, 5]
    assert [ln for ln, _ in s.asarray_hits] == [6]
    assert [ln for ln, _ in s.device_put_hits] == [13]
    assert [ln for ln, _ in s.device_get_hits] == [15]
    assert [(ln, callee) for ln, _, callee in s.site_hits] == \
        [(21, "_host"), (24, "_jit")]
    assert [ln for ln, _ in s.stats_hits] == [30]
    # the round-17 outside-exec rule flags un-annotated jax.jit refs and
    # accepts the compile-ok marker
    jitmod = tmp_path / "jitmod.py"
    jitmod.write_text(
        "import jax\n"
        "from functools import partial\n"
        "@partial(jax.jit, static_argnums=(0,))\n"       # line 3: flagged
        "def f(n, x):\n"
        "    return x\n"
        "@partial(jax.jit, static_argnums=(0,))  # compile-ok: test\n"
        "def g(n, x):\n"
        "    return x\n"
        "h = jax.jit(lambda x: x)\n")                    # line 9: flagged
    assert _untracked_jit_refs(jitmod) == [3, 9]
    kern = tmp_path / "kern.py"
    kern.write_text(
        "from jax.experimental import pallas as pl\n"
        "from jax.experimental.pallas import pallas_call\n"
        "def f(x):\n"
        "    return pl.pallas_call(lambda r, o: None, out_shape=x)(x)\n"  # 4: flagged
        "def g(x, interp):\n"
        "    return pl.pallas_call(lambda r, o: None, out_shape=x,\n"
        "                          interpret=interp)(x)\n"
        "def h(x):\n"
        "    return pallas_call(lambda r, o: None, out_shape=x)(x)\n"  # 9: flagged
        "def k(x, interp):\n"
        "    return pallas_call(lambda r, o: None, out_shape=x,\n"
        "                       interpret=interp)(x)\n")
    assert _pallas_call_hits(kern) == [4, 9]
    # the round-19 rule flags un-annotated plan_history/compile_log reads
    # and accepts the adaptive-ok marker
    adap = tmp_path / "adap.py"
    adap.write_text(
        "def f(engine):\n"
        "    h = engine.plan_history\n"                  # line 2: flagged
        "    c = engine.compile_log.snapshot()\n"        # line 3: flagged
        "    h2 = engine.plan_history  # adaptive-ok: test\n"
        "    return h, c, h2\n")
    assert _adaptive_read_hits(adap) == \
        [(2, "plan_history"), (3, "compile_log")]


# Every TRINO_TPU_* environment name the package reads, with why it is an
# option and not a constant.  A new name fails this test until it is listed
# here with its reason, so that it is seen in review.
ENV_OPTIONS = {
    # deployment settings: paths, secrets, sizes of the machine
    "TRINO_TPU_CLUSTER_SECRET": "deployment: the cluster's shared secret",
    "TRINO_TPU_EXCHANGE_KEY": "deployment: AES key of spooled exchange pages",
    "TRINO_TPU_FLIGHT_DIR": "deployment: where flight records are written",
    "TRINO_TPU_SPILL_DIR": "deployment: where spilled partitions are written",
    "TRINO_TPU_NO_COMPILE_CACHE": "deployment: no persistent compile cache "
                                  "(read-only checkouts)",
    "TRINO_TPU_PAGE_CODEC": "deployment: zstd, zlib or none, by what the "
                            "container has",
    "TRINO_TPU_WORKER_EXEC_SLOTS": "deployment: fragments a worker runs at once",
    "TRINO_TPU_SCHED_QUANTUM": "deployment: a worker slot's time slice",
    # byte and entry budgets
    "TRINO_TPU_PAGE_CACHE": "byte budget: device page cache (0 = off)",
    "TRINO_TPU_RESULT_CACHE": "byte budget: result tier (unset = off)",
    "TRINO_TPU_RESULT_CACHE_MAX_ENTRY": "byte budget: one result entry",
    "TRINO_TPU_SPILL_HOST_BYTES": "byte budget: host tier of the spill",
    "TRINO_TPU_FLIGHT_BYTES": "byte budget: flight records on disk",
    "TRINO_TPU_FLIGHT_RECORDS": "entry budget: flight records in memory",
    "TRINO_TPU_COMPILE_LOG": "entry budget: retained compile records",
    "TRINO_TPU_PLAN_HISTORY": "entry budget: retained plan histories",
    # operations: armed by whoever runs the process, off when unset
    "TRINO_TPU_STALL_S": "operations: arms the stall watchdog",
    "TRINO_TPU_STALL_COMPILE_S": "operations: the watchdog's bar for a "
                                 "first-seen signature",
    "TRINO_TPU_STALL_KILL_S": "operations: the watchdog aborts the stuck thread",
    "TRINO_TPU_FAULTS": "operations: arms fault injection for a whole process "
                        "(scripts/chaos.py)",
    "TRINO_TPU_COMPILE_MEMSTATS": "operations: executable sizes, at a second "
                                  "compile a signature",
    # a fork with no verdict yet (ROADMAP D2a)
    "TRINO_TPU_PALLAS": "A/B reference of tests/test_pallas_kernels.py and "
                        "tests/test_compaction.py",
}


def test_env_options_are_the_listed_ones():
    import re

    pkg = EXEC_DIR.parent
    read = set()
    for path in pkg.rglob("*.py"):
        read |= set(re.findall(r"TRINO_TPU_[A-Z0-9_]+", path.read_text()))
    assert read == set(ENV_OPTIONS), (
        f"not listed: {sorted(read - set(ENV_OPTIONS))}; "
        f"listed but gone: {sorted(set(ENV_OPTIONS) - read)}")
    assert len(ENV_OPTIONS) == 22


# PR 45: arrows point one way under the executor.  What another module needs
# does not live in an executor (exec/boundary.py, exec/groupby.py,
# exec/pages.py hold it), and the layers PERF.md section 3 draws below the
# executors import none of them.
EXECUTORS = ("local_executor", "distributed", "fte")


def _imports(path):
    """(module path, level, imported names, line) of every import statement of
    ``path``, at any depth of nesting."""
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom):
            yield (node.module or "", node.level,
                   [a.name for a in node.names], node.lineno)
        elif isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, 0, [], node.lineno


def _private_executor_imports(path):
    return [(line, mod, name) for mod, _, names, line in _imports(path)
            if mod.split(".")[-1] in EXECUTORS and path.stem != mod.split(".")[-1]
            for name in names if name.startswith("_")]


def test_no_private_import_from_an_executor():
    pkg = EXEC_DIR.parent
    hits = {str(p.relative_to(pkg)): h for p in sorted(pkg.rglob("*.py"))
            if (h := _private_executor_imports(p))}
    assert not hits, (
        f"{hits}: a name another module needs does not live in an executor: "
        "move it to exec/boundary.py, exec/groupby.py or exec/pages.py")


def _executor_imports(path):
    hits = []
    for mod, level, names, line in _imports(path):
        parts = mod.split(".")
        if parts[-1] in EXECUTORS and "exec" in parts[:-1]:
            hits.append((line, mod))
        elif parts[-1] == "exec" and set(names) & set(EXECUTORS):
            hits.append((line, mod))
    return hits


def test_execution_layer_does_not_import_exec_executors():
    pkg = EXEC_DIR.parent
    hits = {str(p.relative_to(pkg)): h
            for d in ("execution", "ops") for p in sorted((pkg / d).glob("*.py"))
            if (h := _executor_imports(p))}
    assert not hits, (
        f"{hits}: execution/ and ops/ sit below the executors and import "
        "none of them (exec/boundary.py is theirs to import)")


def test_the_import_lints_flag_what_they_are_for(tmp_path):
    bad = tmp_path / "history.py"
    bad.write_text(
        "from ..exec.boundary import _host\n"                    # fine
        "def f():\n"
        "    from ..exec.local_executor import _host, LocalExecutor\n"
        "    from ..exec import fte\n"
        "    import trino_tpu.exec.distributed\n")
    assert _private_executor_imports(bad) == [(3, "exec.local_executor", "_host")]
    assert [line for line, _ in _executor_imports(bad)] == [3, 4, 5]
    own = tmp_path / "fte.py"
    own.write_text("from .fte import _x\nfrom .boundary import _jit\n")
    assert _private_executor_imports(own) == []
    assert _executor_imports(tmp_path / "fte.py") == []


def test_the_boundary_imports_nothing_of_exec():
    hits = [(line, mod) for mod, level, names, line
            in _imports(EXEC_DIR / "boundary.py")
            if (level == 1) or "exec" in mod.split(".")]
    assert not hits, f"exec/boundary.py imports {hits}"
