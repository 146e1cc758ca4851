#!/usr/bin/env python
"""Flight-recorder reader: post-mortem on a DEAD process's record directory.

The recorder (trino_tpu/execution/flightrecorder.py) mirrors every statement
record into an on-disk JSONL ring when TRINO_TPU_FLIGHT_DIR is set; this
reader needs only that directory — no engine, no jax, no live process — so a
run that stalled or was killed leaves an artifact this script can decompose
hours later.

    python scripts/flight.py DIR                 # one summary line per record
    python scripts/flight.py DIR --id query_7    # one record, full JSON
    python scripts/flight.py DIR --json          # every record, JSON lines
    python scripts/flight.py DIR --stalls        # stall events only
    python scripts/flight.py DIR --compiles      # per-statement compile events
    python scripts/flight.py DIR --adaptive      # per-statement plan decisions
    python scripts/flight.py DIR --skew          # per-shard load / stragglers

Summary columns: query id, state, wall, dispatch/byte counters, the compile
census (count + seconds — round 17), and the top wall-breakdown bucket —
"where did the time go" per statement, from disk.
"""

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reader():
    """Load flightrecorder.py DIRECTLY (not through the trino_tpu package,
    whose __init__ imports jax): the module is stdlib-pure, so this reader
    runs on boxes — and in moments — where jax cannot even initialize
    (exactly when a post-mortem is wanted)."""
    import importlib.util

    path = os.path.join(_REPO, "trino_tpu", "execution", "flightrecorder.py")
    spec = importlib.util.spec_from_file_location("_flightrecorder", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read_flight_dir, mod.summarize_compiles, mod.summarize_skew


read_flight_dir, summarize_compiles, summarize_skew = _load_reader()

WALL_BUCKETS = ("plan", "compile", "admission_queue", "split_generation",
                "h2d", "device_dispatch", "host_pull", "scan_wait",
                "exchange_wait", "retry_backoff", "unattributed")


def _top_bucket(bd):
    if not bd:
        return "-"
    best = max((b for b in WALL_BUCKETS), key=lambda b: bd.get(b) or 0.0)
    v = bd.get(best) or 0.0
    if v <= 0:
        return "-"
    wall = bd.get("wall_s") or 0.0
    pct = f" ({v / wall * 100:.0f}%)" if wall else ""
    return f"{best} {v * 1000:.1f}ms{pct}"


def _summary_line(rec) -> str:
    if rec.get("kind") == "stall":
        stuck = ", ".join(e.get("label", "?")
                          for e in rec.get("stalled") or [])[:60]
        return (f"{'<stall>':<14} {'-':<9} {'-':>9} {'-':>6} {'-':>10} "
                f"{'-':>12}  stuck: {stuck}")
    c = rec.get("counters") or {}
    wall = rec.get("wall_s")
    nc, cs = summarize_compiles(rec)
    comp = f"{nc}/{cs:.2f}s" if nc else "-"
    return (f"{rec.get('query_id') or '?':<14} "
            f"{rec.get('state') or '?':<9} "
            f"{('%.3fs' % wall) if wall is not None else '-':>9} "
            f"{c.get('device_dispatches') or 0:>6} "
            f"{c.get('host_bytes_pulled') or 0:>10} "
            f"{comp:>12}  "
            f"{_top_bucket(rec.get('wall_breakdown'))}"
            + (f"  ERROR: {rec['error'][:60]}" if rec.get("error") else ""))


def _print_compiles(recs) -> None:
    """--compiles detail: every statement record's compile events (site, op
    label, signature, duration) from the census the engine embedded.  The
    count is the CLUSTER truth (merged worker counters); the event lines
    are coordinator-local — a distributed statement legitimately shows
    fewer events than compilations (worker-side compiles live in the
    workers' own census rings)."""
    for rec in recs:
        if rec.get("kind") != "query":
            continue
        nc, cs = summarize_compiles(rec)
        events = rec.get("compile_events") or []
        if not nc and not events:
            continue
        note = "" if len(events) >= nc else \
            f" ({len(events)} local events; rest worker-side)"
        print(f"{rec.get('query_id') or '?'}: {nc} compilations, "
              f"{cs:.3f}s{note}")
        for ev in events:
            exe = f", exe {ev['exe_bytes']}B" if ev.get("exe_bytes") else ""
            print(f"  {ev.get('label') or ev.get('site'):<44} "
                  f"{(ev.get('duration_s') or 0.0) * 1000:>9.1f} ms{exe}  "
                  f"sig: {(ev.get('signature') or '')[:70]}")


def _print_adaptive(recs) -> None:
    """--adaptive detail: the advisor decision each statement ran under
    (round 19), from the record's embedded decision dict — verdict,
    win-vs-price reasons, frozen corrections.  Statements the advisor had
    no opinion on carry no field and are skipped."""
    for rec in recs:
        if rec.get("kind") != "query" or not rec.get("adaptive"):
            continue
        dec = rec["adaptive"]
        win, price = dec.get("predicted_win_s"), dec.get("compile_price_s")
        arith = "" if win is None else (
            f"  win {win:.4f}s x {dec.get('horizon', 0):g} vs "
            + (f"price {price:.4f}s" if price is not None else "unknown price"))
        print(f"{rec.get('query_id') or '?'}: {dec.get('verdict', '?')}"
              f"{arith}")
        for r in (dec.get("reasons") or []):
            print(f"  {r}")


def _print_skew(recs) -> None:
    """--skew detail: every statement record's per-shard attribution
    (round 20) — one line per statement with the worst max/mean ratio and
    summed recoverable imbalance wall, then one line per ShardStats record
    (site, kind, per-worker rows, argmax worker).  Statements that never
    crossed a mesh/cluster exchange carry no field and are skipped."""
    for rec in recs:
        if rec.get("kind") != "query":
            continue
        worst, imb, n = summarize_skew(rec)
        if not n:
            continue
        stats = rec.get("shard_stats") \
            or (rec.get("counters") or {}).get("shard_stats") or []
        print(f"{rec.get('query_id') or '?'}: {n} shard records, "
              f"worst {worst:.1f}x, {imb * 1000:.1f} ms imbalance")
        for s in stats:
            rows = s.get("rows") or []
            rows_str = ",".join(str(int(v)) for v in rows[:16])
            if len(rows) > 16:
                rows_str += ",..."
            lbl = s.get("op") or "-"
            print(f"  {s.get('site', '?'):<28} {s.get('kind', '?'):<10} "
                  f"{lbl:<12} {s.get('ratio', 1.0):>6.1f}x "
                  f"worker {s.get('worker', 0):<3} "
                  f"{s.get('imbalance_s', 0.0) * 1000:>8.1f} ms  "
                  f"rows [{rows_str}]")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dir", help="flight directory (TRINO_TPU_FLIGHT_DIR)")
    ap.add_argument("--id", default=None,
                    help="print ONE record (full JSON) by query id")
    ap.add_argument("--json", action="store_true",
                    help="dump every record as JSON lines")
    ap.add_argument("--stalls", action="store_true",
                    help="stall events only")
    ap.add_argument("--compiles", action="store_true",
                    help="per-statement compile events (site, signature, "
                         "duration) from the embedded census")
    ap.add_argument("--adaptive", action="store_true",
                    help="per-statement adaptive decisions (verdict, "
                         "win-vs-price reasons, corrections) from the "
                         "embedded advisor decision")
    ap.add_argument("--skew", action="store_true",
                    help="per-shard attribution (worker load per exchange, "
                         "max/mean skew, imbalance wall, cluster straggler "
                         "records) from the embedded shard stats")
    args = ap.parse_args(argv)
    recs = read_flight_dir(args.dir)
    if not recs:
        print(f"no flight records under {args.dir}", file=sys.stderr)
        return 1
    if args.id is not None:
        hits = [r for r in recs if r.get("query_id") == args.id]
        if not hits:
            print(f"no record for {args.id}", file=sys.stderr)
            return 1
        print(json.dumps(hits[-1], indent=1))
        return 0
    if args.compiles:
        _print_compiles(recs)
        return 0
    if args.adaptive:
        _print_adaptive(recs)
        return 0
    if args.skew:
        _print_skew(recs)
        return 0
    if args.stalls:
        recs = [r for r in recs if r.get("kind") == "stall"]
    if args.json:
        for r in recs:
            print(json.dumps(r))
        return 0
    print(f"{'query':<14} {'state':<9} {'wall':>9} {'disp':>6} "
          f"{'bytes':>10} {'compiles':>12}  top bucket")
    for r in recs:
        print(_summary_line(r))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # | head closed the pipe: not an error
        sys.exit(0)
