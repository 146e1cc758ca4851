"""The closed loop's two ways of ordering and closing, with the statement itself faked."""

import time
from types import SimpleNamespace

from benchmark.harness import loop

STEP = 0.05


def cell(order, slots=("a", "b", "c")):
    traffic = {"clients": 1, "slots": list(slots), "params": {s: "fixed" for s in slots},
               "statement_timeout_s": 7}
    if order:
        traffic["order"] = order
    return SimpleNamespace(traffic=traffic, config={"catalog": "elsewhere"},
                           statements={s: SimpleNamespace(VALIDATION={}) for s in slots})


def drive(monkeypatch, order, seed, seconds):
    def fake(client, statement, name, p, timeout_s, engine=None, annotate=False):
        assert timeout_s == 7 and client.catalog == "elsewhere"  # the cell's files say both
        t0 = time.perf_counter()
        time.sleep(STEP)
        t1 = time.perf_counter()
        return {"name": name, "params": p, "error": None, "t0": t0, "t1": t1,
                "seconds": t1 - t0, "lost": 0}

    monkeypatch.setattr(loop, "execute", fake)
    records, start = loop.closed_loop("http://127.0.0.1:9", cell(order), seed, seconds, "window")
    return [r["name"] for r in records], max(r["t1"] for r in records) - start


def test_seeded_rounds_run_whole_rounds_that_fit_into_the_window(monkeypatch):
    names, window_s = drive(monkeypatch, "seeded_rounds", 3_000_000_001, 8.4 * STEP)
    assert len(names) == 6 and window_s <= 8.4 * STEP  # a third round of 3 would not fit
    assert sorted(names[:3]) == sorted(names[3:]) == ["a", "b", "c"]
    again, _ = drive(monkeypatch, "seeded_rounds", 3_000_000_001, 8.4 * STEP)
    assert again == names  # the same seed gives the same statements in the same order
    orders = {tuple(drive(monkeypatch, "seeded_rounds", seed, STEP)[0]) for seed in range(12)}
    assert len(orders) > 1 and all(sorted(o) == ["a", "b", "c"] for o in orders)


def test_the_first_round_runs_even_where_it_does_not_fit(monkeypatch):
    names, window_s = drive(monkeypatch, "seeded_rounds", 5, STEP)
    assert sorted(names) == ["a", "b", "c"] and window_s > STEP


def test_a_cycle_closes_with_the_statement_in_flight(monkeypatch):
    names, window_s = drive(monkeypatch, None, 5, 3.5 * STEP)
    assert names == ["a", "b", "c", "a"] and window_s >= 3.5 * STEP


def test_the_statement_timeout_reaches_the_client():
    calls = []

    class Client:
        last_id = "q1"

        def execute(self, sql, timeout, params):
            calls.append((sql, timeout, params))
            return SimpleNamespace(column_names=["c"], rows=[[1]])

    statement = SimpleNamespace(render=lambda p: ("select 1", None))
    rec = loop.execute(Client(), statement, "one", {}, 900)
    assert calls == [("select 1", 900, None)] and rec["error"] is None and rec["rows"] == [[1]]
