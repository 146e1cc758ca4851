"""The device boundary: every way work or bytes cross between the host and the device.

``_jit`` is the one launch of an executor's own programs, ``_generate`` of a connector's
generator, ``_page_to_device`` the one host-to-device copy and ``_host`` the one
device-to-host pull; the parameter scope, the dispatch coalescing and the prefetch queue
sit beside them because they decide what a launch carries.  Every counter, span, fault
point and in-flight entry of a statement hangs on these, and tests/test_boundary_lint.py,
test_hidden_syncs.py and test_eager_launches.py hold the rest of the package to them.

This module imports ``page``, ``execution/tracing`` and ``execution/faults`` and
nothing of ``exec/``: both executors, the spill ladder and the layers under them
import it.  Its names keep their underscore: they are the package's boundary, not its API.
"""

from __future__ import annotations

import contextlib
import os
import queue as _queue
import threading
import time as _time

import jax
import jax.numpy as jnp
import numpy as np

from ..execution import faults, tracing
from ..page import Page


_WRAPPER_SEQ = [0]  # monotonic _jit-wrapper ids (storm-detection identity)
_WRAPPER_SEQ_LOCK = threading.Lock()


def _compile_memstats_enabled() -> bool:
    """Opt-in executable-size capture (TRINO_TPU_COMPILE_MEMSTATS=1): the
    AOT ``lower().compile().memory_analysis()`` path is NOT served by the
    jit cache, so reading the executable size pays a SECOND trace+compile
    per first-seen signature — off by default, worth it only on device
    captures where executable HBM footprint is the question."""
    return os.environ.get("TRINO_TPU_COMPILE_MEMSTATS", "") == "1"


def _executable_bytes(compiled, args, kw):
    """Generated-code size of the executable for this call signature via the
    AOT memory_analysis(), or None when unavailable (CPU reports 0 — treated
    as unavailable; any failure is swallowed: the census never fails a
    dispatch)."""
    try:
        ma = compiled.lower(*args, **kw).compile().memory_analysis()
        return int(getattr(ma, "generated_code_size_in_bytes", 0) or 0) or None
    except Exception:
        return None


def _jit(fn, site=None, **kwargs):
    """``jax.jit`` + per-query dispatch accounting: every invocation of the
    compiled function records one device dispatch on the active query's
    counters (execution/tracing.QueryCounters).  Each dispatch is a host-side
    launch, so this count IS the budget the warm-query tests pin.  ``site`` labels the call site for per-site
    attribution (defaults to the wrapped function's name — bare ``@_jit`` on a
    named step function self-labels; lambdas must pass ``site=``, enforced by
    tests/test_boundary_lint.py); each invocation's wall time also feeds the
    per-query + engine-total dispatch-latency histograms.  ``__wrapped__``
    stays the original python function (callers use it to run the step eagerly
    for untraceable object columns).  The site is also the device program's
    name (tracing.site_program: XLA module ``jit_<site>``, body under
    ``jax.named_scope(site)``), so a device trace can be read by site, and
    each dispatch is a ``trino_tpu:dispatch`` annotation of the profiler.

    Round 17 — the compile observatory lives HERE, so the boundary lint that
    forces all executor code through ``_jit`` guarantees compile coverage the
    same way it guarantees counters/in-flight/faults coverage.  Each wrapper
    keeps a seen-signature set of the ABSTRACT arg signatures it has
    dispatched (tracing.arg_signature — a host-side pytree walk, zero
    dispatches/pulls, so the warm budget ceilings are untouched).  A
    first-seen signature is a compile: the in-flight entry is flagged
    ``compiling`` (the stall watchdog judges it against
    TRINO_TPU_STALL_COMPILE_S and verdicts "compiling", not "stalled"), the
    jax.monitoring compile events captured on this thread supply the
    authoritative XLA duration (fallback: the dispatch wall), and the event
    records to the query counters, a "compile" span, and the process-global
    CompileLog census."""
    label = site or getattr(fn, "__name__", "jit")
    compiled = jax.jit(tracing.site_program(fn, label), **kwargs)
    # two signature sets, both under `lock` (an unsynchronized check-then-
    # act would double-record when concurrent queries race a shared
    # MODULE-LEVEL wrapper's first dispatch):
    #   claimed — signatures some in-flight dispatch owns RECORDING for
    #             (claimed at entry, released on failure so the retry
    #             re-claims and records THE compile);
    #   done    — signatures that completed at least once.  The in-flight
    #             `compiling` flag reads done, not claimed: a second
    #             concurrent dispatch of a first-seen signature BLOCKS on
    #             jax's compile just like the claimant, and must also read
    #             as "compiling" to the watchdog, it just must not record
    #             a second census event.
    claimed: set = set()
    done: set = set()
    lock = threading.Lock()
    # storm identity: distinct signatures are counted per WRAPPER (one
    # compiled stream), not per label — "Aggregate#3" labels from different
    # queries sharing one label must not pool into a phantom storm
    with _WRAPPER_SEQ_LOCK:
        _WRAPPER_SEQ[0] += 1
        wrapper_id = _WRAPPER_SEQ[0]

    def run(*args, **kw):
        sig_key = tracing.arg_signature(args, kw)
        with lock:
            owns = sig_key not in claimed
            if owns:
                claimed.add(sig_key)
            compiling = sig_key not in done
        # in-flight registry entry/exit brackets the dispatch: a stuck
        # device call is VISIBLE (site + operator + thread + elapsed
        # + compiling flag) to the stall watchdog while it hangs, not just
        # as a post-hoc latency-histogram blow-up
        reg = tracing.current_inflight()
        tok = reg.enter("dispatch", label, compiling=compiling)
        cap = tracing.begin_compile_capture() if owns else None
        t0 = _time.perf_counter()
        ok = False
        try:
            if tracing.DISPATCH_TEST_HOOK is not None:
                tracing.DISPATCH_TEST_HOOK(label)
            # chaos chokepoint: an armed FaultPlan can raise/delay HERE, so
            # every dispatch in the engine is injectable (disarmed = one
            # global None test, nothing on the budget counters)
            faults.maybe_inject("dispatch", label)
            with tracing.annotate("dispatch"):
                out = compiled(*args, **kw)
            ok = True
            return out
        finally:
            reg.exit(tok)
            dt = _time.perf_counter() - t0
            if owns:
                xla_s = tracing.end_compile_capture(cap)
                if ok:
                    with lock:
                        done.add(sig_key)
                    exe = _executable_bytes(compiled, args, kw) \
                        if _compile_memstats_enabled() else None
                    tracing.record_compile(
                        xla_s if xla_s is not None else dt, site=label,
                        signature=tracing.signature_summary(sig_key),
                        sig_key=f"{hash(sig_key) & 0xffffffffffffffff:016x}",
                        exe_bytes=exe, wrapper=wrapper_id,
                        cache_misses=tracing.compile_capture_misses(cap))
                else:
                    # a first-seen dispatch that raises (injected fault,
                    # transient device error) records nothing and releases
                    # the claim — the RETRY is the run that really
                    # compiles, and it must still flag `compiling` or a
                    # tight STALL_S reads the legit compile as a wedge
                    with lock:
                        claimed.discard(sig_key)
            tracing.record_dispatch(site=label, seconds=dt)

    run.__wrapped__ = fn
    run.lower = compiled.lower  # the program as XLA will name it (tests)
    return run


# one process-wide registration of the jax.monitoring compile-event listener
# (the /jax/core/compile/* duration family): idempotent, and harmless when
# the runtime lacks monitoring (captures then fall back to dispatch wall)
tracing.install_compile_listener()


_PARAM_TLS = threading.local()


@contextlib.contextmanager
def _params_scope(values, host_values=(), batch_hosts=()):
    """Publish the CURRENT query's bound parameter values (tuple of
    ``(0-d device value, 0-d device isnull)`` pairs, one per plan-template
    slot) for this thread.  The jitted step wrappers read it at CALL time and
    pass it into the compiled function as an argument — parameters ride every
    dispatch exactly like ``_Stream.aux`` (never closed over; round-5
    invariant), so a warm template re-executes the SAME XLA executable with
    new inputs.  Empty tuple = no parameters (zero pytree leaves, identical
    compiled signature).  ``host_values`` keeps the pre-staging numpy pairs:
    host-side consumers (bind-time split pruning) read them without paying a
    device->host sync.  ``batch_hosts`` (round 21, continuous template
    batching) carries the numpy runtime tuples of EVERY request in a fused
    same-template batch: split pruning takes the UNION of the batch's kept
    splits so one scan feeds all the stacked predicates.  A fused batch
    publishes ONLY batch_hosts — ``values`` stays empty so a code path that
    consumes per-request scalars outside the bindings-vmapped step fails
    loudly instead of silently computing one member's answer for all."""
    old = getattr(_PARAM_TLS, "values", ())
    old_host = getattr(_PARAM_TLS, "host_values", ())
    old_batch = getattr(_PARAM_TLS, "batch_hosts", ())
    _PARAM_TLS.values = values
    _PARAM_TLS.host_values = host_values
    _PARAM_TLS.batch_hosts = batch_hosts
    try:
        yield
    finally:
        _PARAM_TLS.values = old
        _PARAM_TLS.host_values = old_host
        _PARAM_TLS.batch_hosts = old_batch


def _current_params() -> tuple:
    return getattr(_PARAM_TLS, "values", ())


def _current_host_params() -> tuple:
    return getattr(_PARAM_TLS, "host_values", ())


def _current_batch_host_params() -> tuple:
    """Host runtime tuples of every member of the CURRENT fused template
    batch, or () outside one (see _params_scope)."""
    return getattr(_PARAM_TLS, "batch_hosts", ())


# Engine-wide dispatch-coalescing width: how many shape-uniform scan splits fold
# into ONE device dispatch.  Each dispatch is a host-side launch, so batch K
# divides the per-split dispatch bill by ~K with zero regeneration cost: pages
# are still produced once per split (a whole scan fused into one program
# regenerated them, and lost on the chip).  The ``dispatch_batch`` session
# property overrides per query (1 is exact per-split behaviour) and rides the
# plan-cache key via engine._plan_shape_props.
DISPATCH_BATCH = 4


def _page_batch_sig(page):
    """Shape-class signature for dispatch coalescing, or None when the page
    must never coalesce (exact wide-decimal object columns run eagerly; an
    empty page has nothing to batch).  Pages group only with identical
    signatures, so a stacked batch is one XLA shape class."""
    for c in page.columns:
        if isinstance(c, np.ndarray) and c.dtype == object:
            return None
    if page.capacity == 0:
        return None
    return (tuple((str(c.dtype), tuple(c.shape)) for c in page.columns),
            tuple(m is not None for m in page.null_masks),
            page.valid is not None)


def _coalesced_batches(pages_iter, batch: int):
    """Group consecutive shape-uniform pages for dispatch coalescing.

    Yields ``(pages, live)``: a singleton ``([page], None)`` runs the ordinary
    per-page path; a group runs the batched path with ``pages`` padded to
    EXACTLY ``batch`` entries (short remainders repeat their last page) and
    ``live`` a [batch] bool mask zeroing the padding's validity inside the
    trace.  Fixed-K groups mean ONE compiled batch executable per page shape
    — group-size-shaped executables (a 4-batch AND a 2-batch, etc.) would
    multiply cold-compile time across every multi-split query.  Padding is
    masked work the engine's mask-respecting operators already skip
    semantically; it costs device FLOPs only, never a dispatch.  ``batch<=1``
    degrades to singleton groups — byte-identical to un-batched iteration.
    Groups record their REAL split count on the query counters (EXPLAIN
    ANALYZE's "splits coalesced")."""
    # closing THIS generator closes its source too (the finally below):
    # consumer loops that unwind on an exception propagate the close down to
    # the prefetch wrapper, whose own finally stops the producer thread —
    # without it, the traceback pins the loop frame and the producer would
    # sit pumping against a full queue until the traceback is released
    try:
        if batch <= 1:
            for pg in pages_iter:
                yield [pg], None
            return
        buf: list = []
        sig = None

        def flush():
            while buf:
                group, buf[:] = buf[:batch], buf[batch:]
                if len(group) == 1:
                    yield group, None
                    continue
                tracing.record_coalesced(len(group))
                live = np.arange(batch) < len(group)
                while len(group) < batch:  # pad: repeated page, live=False
                    group.append(group[-1])
                yield group, live

        for pg in pages_iter:
            s = _page_batch_sig(pg)
            if s is None:
                yield from flush()
                sig = None
                yield [pg], None
                continue
            if sig is not None and s != sig:
                yield from flush()
            sig = s
            buf.append(pg)
            if len(buf) >= batch:
                yield from flush()
        yield from flush()
    finally:
        close = getattr(pages_iter, "close", None)
        if close is not None:
            close()


def _stack_pages(pages, live=None):
    """Concatenate K uniform pages into one (cols, nulls, valid) triple INSIDE
    a trace: the coalescing itself costs no dispatch, and row order is split
    order, so every row-wise stream transform (filters, projections, LUT
    gathers, join probes) computes exactly what K per-page runs would — the
    engine's masks-not-shrinking page model is what makes plain concatenation
    sound.  ``live`` ([K] bool) invalidates padding pages appended by
    ``_coalesced_batches`` to hold the group at a fixed K.  Called only under
    jit (from jitted_batch / the batched agg steps)."""
    ncol = len(pages[0].columns)
    n = pages[0].capacity
    cols = tuple(jnp.concatenate([p.columns[ci] for p in pages])
                 for ci in range(ncol))
    nulls = tuple(
        None if all(p.null_masks[ci] is None for p in pages)
        else jnp.concatenate([
            p.null_masks[ci] if p.null_masks[ci] is not None
            else jnp.zeros((p.columns[ci].shape[0],), bool) for p in pages])
        for ci in range(ncol))
    valid = jnp.concatenate([p.valid_mask() for p in pages])
    if live is not None:
        valid = valid & jnp.repeat(jnp.asarray(live), n)
    return cols, nulls, valid


@contextlib.contextmanager
def _statement_scopes(counters, qid, tracer):
    """Another thread's work recorded as the statement's (the prefetch
    producer, a connector's warm thread): its counters, its query id and its
    tracer on this thread, each where the statement has one.  track_counters
    enters BEFORE query_scope: live-counter registration keys on the qid
    active at entry, and the query thread already registered this set."""
    with contextlib.ExitStack() as scopes:
        if counters is not None:
            scopes.enter_context(tracing.track_counters(counters))
        if qid is not None:
            scopes.enter_context(tracing.query_scope(qid))
        if tracer is not None:
            scopes.enter_context(tracing.activate_tracer(tracer))
        yield


def _prefetched_pages(pages_fn, depth: int = 2, to_device: bool = False,
                      warmup: int = 0, owner=None, table: str = ""):
    """Wrap a page generator with background-thread prefetch: up to ``depth``
    pages decode ahead of the consumer.  ``to_device`` additionally moves each
    page's host (numpy) arrays onto the device FROM THE PRODUCER THREAD
    (async host->device pipelining: the copy overlaps the consumer's current
    dispatch instead of serializing in front of the next one; object-dtype
    wide-decimal columns stay host-side).  ``warmup`` pages are produced
    SYNCHRONOUSLY before the thread starts: a short-circuiting consumer
    (LIMIT) that stops within the warmup window generates exactly the pages
    it consumed — the thread only runs ahead once the consumer proved it
    wants a long scan.  Exceptions re-raise at the consume site.  An abandoned
    consumer (LIMIT short-circuit, error unwind) closes the generator; the
    producer observes the ``closed`` flag on its next bounded put and exits,
    releasing its decoded pages and file handles instead of blocking on the
    full queue for the process lifetime.  ``owner`` (the LocalExecutor that
    compiled the scan) additionally registers the producer's stop flag +
    thread so ``close_producers()`` can stop it on exception paths where the
    consumer generator is never closed — a mid-query error's traceback pins
    the consumer frames (and so the generators) alive, which used to leave
    the producer pumping against a full queue until the traceback was
    released.

    Both sides of the queue are timed where they block (PR 38): each of the
    consumer's ``q.get()`` is a finished ``scan.wait`` span (bucket scan_wait,
    ``table`` its attribute) and a ``trino_tpu:scan.wait`` annotation; the
    producer sums the seconds its ``put`` found the queue full into
    ``put_wait_s`` of its ``prefetch`` span, beside the thread's ``cpu_s``."""

    def pages():
        it = pages_fn()
        for _ in range(warmup):
            try:
                p = next(it)
            except StopIteration:
                return
            yield _page_to_device(p) if to_device else p
        q: _queue.Queue = _queue.Queue(maxsize=depth)
        done = object()
        closed = threading.Event()
        # explicit parent handoff: Tracer parenting is thread-local, so the
        # producer thread's spans would be orphans — capture the consumer
        # thread's active span HERE (first iteration, on the query thread) and
        # pass it across.  The producer's span parents correctly into the
        # query's tree even though it opens on another thread.
        tracer = tracing.current_tracer()
        parent = tracer.current() if tracer is not None else None
        # counters/query-id handoff, same idea as the span parent: generate
        # and h2d fault injections fire ON this thread, and without the
        # query's counters installed here record_fault would no-op — a chaos
        # run over the default prefetch path would read 0 faults_injected.
        # Beside faults the producer records its generator launches
        # (_generate) and never touches executor state (the round-6 rule).
        counters = tracing.current_counters()
        qid = tracing.current_query_id()

        def producer():
            put_wait = [0.0]

            def put(item) -> bool:
                t0 = _time.perf_counter()
                try:
                    while not closed.is_set():
                        try:
                            q.put(item, timeout=0.1)
                            return True
                        except _queue.Full:
                            continue
                    return False
                finally:
                    put_wait[0] += _time.perf_counter() - t0

            def pump(span):
                n = 0
                cpu0 = _time.thread_time()
                try:
                    for p in it:
                        if to_device:
                            p = _page_to_device(p)
                        n += 1
                        if not put(p):
                            return
                    put(done)
                except BaseException as e:  # surfaces in the consumer
                    put(e)
                finally:
                    if span is not None:
                        span.attributes["pages"] = n
                        span.attributes["put_wait_s"] = round(put_wait[0], 6)
                        span.attributes["cpu_s"] = round(
                            _time.thread_time() - cpu0, 6)
                    # the producer owns the source iterator once the thread
                    # starts: close it HERE so connector state (file handles,
                    # decode buffers) releases with the thread, not at GC
                    close = getattr(it, "close", None)
                    if close is not None:
                        try:
                            close()
                        except Exception:
                            pass

            with _statement_scopes(counters, qid, tracer):
                if tracer is None:
                    pump(None)
                else:
                    # (the tracer is active on this thread too, so that the
                    # generator launches it runs, _generate, are spans of
                    # the statement, under this one)
                    with tracer.span("prefetch", parent=parent,
                                     to_device=to_device) as span:
                        pump(span)

        # named so leak checks (tests/test_chaos.py, scripts/chaos.py) can
        # assert "no prefetch producer survived the query" by thread name
        t = threading.Thread(target=producer, daemon=True,
                             name="prefetch-producer")
        if owner is not None:
            owner._producers.append((closed, t))
        t.start()
        try:
            while True:
                t0 = _time.perf_counter()
                with tracing.annotate("scan.wait"):
                    item = q.get()
                if tracer is not None:
                    tracer.add_completed("scan.wait",
                                         _time.perf_counter() - t0,
                                         table=table)
                if item is done:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            closed.set()

    return pages


def _page_to_device(page: Page) -> Page:
    """Start async host->device copies for a page's numpy arrays (device
    arrays pass through; object columns cannot live on device).  device_put is
    an enqueue, not a sync — safe from the prefetch thread, and by the time
    the consumer dispatches over the page the copy has overlapped."""
    faults.maybe_inject("h2d", "page_to_device")

    def up(a):
        if isinstance(a, np.ndarray) and a.dtype != object:
            return jax.device_put(a)
        return a

    if not any(isinstance(c, np.ndarray) and c.dtype != object
               for c in tuple(page.columns) + tuple(
                   m for m in page.null_masks if m is not None)):
        return page
    return Page(page.schema, tuple(up(c) for c in page.columns),
                tuple(None if m is None else up(m) for m in page.null_masks),
                None if page.valid is None else up(page.valid))


_GENERATED: set = set()  # generator shapes that have run once (_generate)


def _generate(conn, table: str, split, cols, count: bool = True):
    """One page of ``table`` from its connector: the chokepoint of the
    generator launches, as ``_jit`` is of the executor's own programs.  A
    device generator (connectors/tpch.py ``_jit_generate``, tpcds.py alike) is
    a bare ``jax.jit`` the connector owns, so it is counted HERE, on whichever
    thread runs the scan source (the prefetch producer's mostly): one
    ``generator_dispatches``, a finished ``generate`` span with
    ``site=generate.<table>``, a ``trino_tpu:generate`` annotation, an
    in-flight entry while it runs, and what XLA compiled inside it as a
    ``record_compile`` event of that site.  ``count=False``: a launch that no
    scan source asked for (the connector's warm thread) is all of that but
    the count."""
    site = "generate." + table
    # what a device generator compiles once for: the stall watchdog judges a
    # first launch as a compile (TRINO_TPU_STALL_COMPILE_S), as _jit's
    # first-seen signatures are
    shape = (type(conn), getattr(conn, "sf", None), table, tuple(cols),
             getattr(split, "hi", 0) - getattr(split, "lo", 0))
    reg = tracing.current_inflight()
    tok = reg.enter("generate", site, compiling=shape not in _GENERATED)
    cap = tracing.begin_compile_capture()
    t0 = _time.perf_counter()
    try:
        with tracing.annotate("generate"):
            page = conn.generate(split, list(cols))
        _GENERATED.add(shape)
        return page
    finally:
        reg.exit(tok)
        dt = _time.perf_counter() - t0
        xla_s = tracing.end_compile_capture(cap)
        if xla_s is not None:  # compile events fired: the generator's first
            # launch at this (length, column set), or one served by the
            # persistent cache
            tracing.record_compile(
                xla_s, site=site,
                signature=f"{table}[{shape[-1]}]({', '.join(shape[3])})",
                cache_misses=tracing.compile_capture_misses(cap))
        tracing.record_generate(table, dt, count=count)


def _host(arrays, site=None):
    """Device->host transfer of many arrays with ONE round-trip of latency: start
    async copies for every array first, then materialize.  Each serial
    np.asarray is a blocking sync of its own; batching overlaps the copies.

    This is THE transfer chokepoint (CLAUDE.md: batch ALL transfers through
    ``_host``): each call records one host transfer and the device bytes it
    pulls on the active query's counters, which the warm-query budget tests
    assert against — a stray bulk pull added anywhere upstream fails them.
    ``site`` labels the pull for per-site attribution (every call site must
    pass one or carry a ``# site-ok`` marker — tests/test_boundary_lint.py).
    Each pull also holds an in-flight registry entry while it runs, so a pull
    stuck on a dead device shows up in the stall watchdog's report."""
    reg = tracing.current_inflight()
    tok = reg.enter("host_pull", site)
    t0 = _time.perf_counter()
    try:
        faults.maybe_inject("host_pull", site)
        nbytes = 0
        for a in arrays:
            if hasattr(a, "copy_to_host_async"):
                try:
                    a.copy_to_host_async()
                    nbytes += a.nbytes
                except Exception:
                    pass
        tracing.record_host_pull(nbytes, site=site)
        with tracing.annotate("host_pull"):
            return [None if a is None else np.asarray(a) for a in arrays]
    finally:
        reg.exit(tok)
        # wall-decomposition feed: each batched pull is one "host_pull" span
        # (same fast path as dispatch spans — no-op without an active tracer)
        tr = tracing.current_tracer()
        if tr is not None:
            tr.add_completed("host_pull", _time.perf_counter() - t0,
                             site=site or "")
