"""The readers of the engine's stage spans and starved-device counters
(``benchmark/harness/step_stages.py`` and the six metrics that call
it): each on a hand-built ring and on
``benchmark/fixtures/stage_trace.json``, and each where there is
nothing to read."""

import json
import os
import time
import types

import pytest

import cellkit

from benchmark.harness import manifest as manifest_lib
from benchmark.harness import step_stages, trace

STAGED = os.path.join(cellkit.REPO, "benchmark", "fixtures",
                      "stage_trace.json")
SCOPED = os.path.join(cellkit.REPO, "benchmark", "fixtures",
                      "scoped_trace.json")
NEW = ("device_starved_pct.serve", "device_starved_pct.longprompt",
       "driver_away_ms.serve", "step_unnamed_ms.serve",
       "idle_unowned_pct.serve", "prefill_piece_ms_at_8k.serve")
RING = NEW[:4]
TRACED = NEW[4:]
#: the ring's clock reads this much more than the capture's
AHEAD = 1000.0


def reader(name):
    return manifest_lib.Manifest(cellkit.REPO).layer_reader(name)


@pytest.fixture
def rec(monkeypatch):
    from tensorflow_train_distributed_tpu.runtime import events

    rec = events.Recorder(512)
    monkeypatch.setattr(events, "get_recorder", lambda: rec)
    return rec


def ctx_for(logs, t_open=100.0, seconds=10.0, **more):
    ctx = {"result": {"counters": {"t_open": t_open, "seconds": seconds}},
           "log": lambda **rec: logs.append(rec)}
    ctx.update(more)
    return ctx


def staged_step(rec, t0, dur, inner=(), **attrs):
    """One ``engine/step`` with spans ``(name, offset, duration)``
    inside it, recorded as the engine does: a span at its exit."""
    for name, off, span_dur in inner:
        rec.record_at(name, "X", t0 + off, span_dur)
    rec.record_at("engine/step", "X", t0, dur, attrs)


@pytest.fixture
def ring(rec):
    """A window [100, 110) of four steps with their stages and
    counters, a step before it and one after."""
    staged_step(rec, 99.0, 0.5, starved_ms=900.0, drains=9, away_ms=9.0)
    # 1.0 s: a dispatch whose prelude is most of it, a piece with its
    # cache, its dispatch and a wait; 0.1 s of it under no span.
    staged_step(rec, 100.0, 1.0, [
        ("decode/stage", 0.0, 0.15), ("decode/dispatch", 0.0, 0.2),
        ("prefill/cache", 0.25, 0.1), ("prefill/dispatch", 0.35, 0.2),
        ("prefill/wait", 0.6, 0.3), ("prefill/piece", 0.2, 0.75)],
        starved_ms=50.0, drains=1, away_ms=0.0)
    # 1.0 s after 0.5 s with the caller: two spans that overlap without
    # nesting (0.2-0.6 and 0.4-0.9) count once: 0.3 s under no span.
    staged_step(rec, 101.5, 1.0, [
        ("prefill/stage", 0.2, 0.4), ("decode/stage", 0.4, 0.5)],
        starved_ms=150.0, drains=2, away_ms=500.0)
    staged_step(rec, 103.0, 2.0, [("decode/wait", 0.1, 1.7)],
                starved_ms=0.0, drains=0, away_ms=500.0)
    staged_step(rec, 105.5, 0.5, [("prefill/insert", 0.0, 0.5)],
                starved_ms=100.0, drains=1, away_ms=500.0)
    staged_step(rec, 110.0, 1.0, starved_ms=900.0, drains=9, away_ms=9.0)
    return rec


def test_a_steps_time_goes_to_the_innermost_span_and_the_rest_has_no_name(
        ring):
    steps, dropped = step_stages.window_steps(ctx_for([]))
    assert [s.t0 for s in steps] == [100.0, 101.5, 103.0, 105.5]
    assert dropped == 0
    first, second = (step_stages.self_seconds(s) for s in steps[:2])
    assert first == pytest.approx({
        "decode/stage": 0.15, "decode/dispatch": 0.05,
        "prefill/piece": 0.05 + 0.05 + 0.05, "prefill/cache": 0.1,
        "prefill/dispatch": 0.2, "prefill/wait": 0.3,
        step_stages.UNNAMED: 0.05})
    # the union rule: 0.2-0.9 is covered once, the later span first
    assert second == pytest.approx({
        "prefill/stage": 0.2, "decode/stage": 0.5,
        step_stages.UNNAMED: 0.3})
    assert sum(first.values()) == pytest.approx(1.0)


def test_step_unnamed_is_the_median_and_its_log_the_stage_table(ring):
    logs = []
    got = reader("step_unnamed_ms.serve")(ctx_for(logs))
    # 50, 300, 300 (2.0 - 1.7) and 0 ms of the four steps
    assert got == pytest.approx(175.0)
    (table,) = logs
    assert (table["n"], table["ring_dropped"]) == (4, 0)
    rows = table["by_span"]
    assert rows["decode/stage"]["ms_mean"] == pytest.approx(650.0 / 4)
    assert rows["decode/stage"]["self_ms_mean"] == pytest.approx(650.0 / 4)
    assert rows["decode/stage"]["steps"] == 2
    assert rows["prefill/piece"]["ms_mean"] == pytest.approx(750.0 / 4)
    assert rows["prefill/piece"]["self_ms_mean"] == pytest.approx(150 / 4)
    assert rows[step_stages.UNNAMED]["self_ms_p75"] == pytest.approx(300.0)
    assert rows["prefill/insert"]["self_ms_p50"] == 0.0   # one step of 4
    # slowest by what lies outside its waits: the second, all of 1.0 s
    assert table["slowest"]["self_ms"] == pytest.approx(1000.0)
    assert table["slowest"]["attrs"]["starved_ms"] == 150.0
    assert table["slowest"]["self_ms_by_span"] == pytest.approx({
        "decode/stage": 500.0, step_stages.UNNAMED: 300.0,
        "prefill/stage": 200.0})


def test_starved_share_is_over_the_steps_and_the_callers_passes(ring):
    for name, phase in (("device_starved_pct.serve", "device_starved.serve"),
                        ("device_starved_pct.longprompt",
                         "device_starved.longprompt")):
        logs = []
        got = reader(name)(ctx_for(logs))
        # 300 ms of 4.5 s of steps + 1.5 s away
        assert got == pytest.approx(100.0 * 300.0 / 6000.0)
        (table,) = logs
        assert table["phase"] == phase
        assert table["drains_a_step"] == pytest.approx(1.0)
        assert (table["away_ms"], table["starved_cover"]) == (1500.0, None)


def test_the_steps_a_capture_overlapped_are_read_apart(ring):
    logs = []
    ctx = ctx_for(logs, tracer=types.SimpleNamespace(t0=101.0, t1=102.0))
    assert reader("driver_away_ms.serve")(ctx) == pytest.approx(500.0)
    assert logs[0]["away_ms"]["n"] == 3
    assert logs[0]["traced_away_ms"]["n"] == 1
    assert logs[0]["share_pct"] == pytest.approx(100.0 * 1000 / 4500)
    assert reader("device_starved_pct.serve")(ctx) == pytest.approx(
        100.0 * 150.0 / 4500.0)
    assert logs[1]["traced"]["starved_ms"] == pytest.approx(150.0)
    assert reader("step_unnamed_ms.serve")(ctx) == pytest.approx(50.0)
    assert logs[2]["traced"]["n"] == 1 and logs[2]["n"] == 3


@pytest.mark.parametrize("name", RING)
@pytest.mark.parametrize("why", ["no-steps", "older-program",
                                 "parent-commit"])
def test_ring_reader_without_its_spans_or_counters_reads_nothing(
        rec, monkeypatch, name, why):
    """A window without steps, a recorder that predates
    ``spans_between``, and the parent commit's steps (its spans, none
    of the stages, none of the counters) all give None and no log."""
    from tensorflow_train_distributed_tpu.runtime import events

    if why == "older-program":
        monkeypatch.setattr(events, "get_recorder", lambda: (
            types.SimpleNamespace(events=lambda: [])))
    if why == "parent-commit":
        staged_step(rec, 100.0, 1.0, [
            ("decode/dispatch", 0.0, 0.2), ("prefill/piece", 0.2, 0.7),
            ("prefill/wait", 0.6, 0.3)], lanes=2, committed=8)
    logs = []
    assert reader(name)(ctx_for(logs)) is None and logs == []


# -- the capture ------------------------------------------------------------

#: attrs of the fixture's spans that carry some, by (name, start)
ATTRS = {
    ("prefill/dispatch", 0.41): dict(rid=1, piece=0, tokens=1024,
                                     rows=2048, draft=0),
    ("prefill/dispatch", 0.63): dict(rid=1, piece=1, tokens=1024,
                                     rows=4096, draft=0),
    ("prefill/dispatch", 1.33): dict(rid=2, piece=7, tokens=1024,
                                     rows=8192, draft=0),
    ("prefill/dispatch", 1.63): dict(rid=3, piece=0, tokens=700,
                                     rows=1024, draft=0),
    ("prefill/dispatch", 1.92): dict(rid=3, piece=1, tokens=700,
                                     rows=1024, draft=1),
    ("engine/step", 0.2): dict(starved_ms=0.0, drains=0, away_ms=0.0),
    ("engine/step", 1.2): dict(starved_ms=80.0, drains=1, away_ms=100.0),
    ("engine/step", 2.7): dict(starved_ms=120.0, drains=1, away_ms=150.0),
    ("engine/step", 3.65): dict(starved_ms=190.0, drains=2,
                                away_ms=100.0),
}


def traced_ctx(path, logs, rec=None):
    """A traced run's ``ctx`` over a fixture capture; with ``rec``, the
    ring is given the twins of the capture's contract spans, ``AHEAD``
    seconds later on its own clock, among spans of the same names
    before and after the capture."""
    from tensorflow_train_distributed_tpu.runtime import events

    tr = trace.load_json(path)
    lo, hi = trace.window(tr)
    if rec is not None:
        rec.record_at("prefill/dispatch", "X", AHEAD - 0.4, 0.1,
                      dict(rid=0, piece=3, tokens=1024, rows=512, draft=0))
        rec.record_at("engine/step", "X", AHEAD - 0.9, 0.8,
                      dict(starved_ms=7.0, drains=1, away_ms=1.0))
        for ev in sorted(tr.host, key=lambda ev: ev.start + ev.dur):
            if events.in_contract(ev.name):
                rec.record_at(ev.name, "X", AHEAD + ev.start, ev.dur,
                              ATTRS.get((ev.name, ev.start)))
        rec.record_at("prefill/dispatch", "X", AHEAD + hi + 0.3, 0.1,
                      dict(rid=4, piece=0, tokens=1024, rows=512, draft=0))
    return ctx_for(logs, t_open=AHEAD, seconds=hi - lo, trace=tr,
                   trace_window=(lo, hi),
                   tracer=types.SimpleNamespace(
                       directory=path, t0=AHEAD + lo, t1=AHEAD + hi))


def test_idle_goes_to_the_stage_that_holds_the_device(rec):
    """0.5 s idle: 1.3-1.4 under the third piece's dispatch (0.07) and
    its piece (0.01), 3.4-3.6 under an insert (0.06) and its piece
    (0.04), 6.0-6.2 under a staging (0.04); the rest under a step alone
    (0.02 + 0.05 + 0.11), between two steps (0.05) and after the last
    span (0.05)."""
    logs = []
    got = reader("idle_unowned_pct.serve")(traced_ctx(STAGED, logs, rec))
    assert got == pytest.approx(100.0 * 0.28 / 0.5)
    (table,) = logs
    assert {k: v for k, v in table["by_span_s"].items() if v} == (
        pytest.approx({"prefill/dispatch": 0.07, "prefill/insert": 0.06,
                       "prefill/piece": 0.05, "prefill/stage": 0.04}))
    assert table["unowned_by_place_s"] == pytest.approx({
        "engine/step alone": 0.18, "*/wait": 0.0, "between steps": 0.05,
        "capture edges": 0.05})
    assert (table["idle_s"], table["window_s"]) == pytest.approx((0.5, 7.2))


def test_idle_unowned_serve_is_the_longprompt_readers_reduction():
    """On that reader's own fixture, number and log: one reduction,
    here as a sweep over the spans' boundaries."""
    theirs = []
    ctx = traced_ctx(SCOPED, theirs)
    want = reader("idle_unowned_pct.longprompt")(ctx)
    idle_s, by_span, unowned, places = step_stages.idle_by_owner(
        step_stages.program_spans(ctx), step_stages.device_idle(ctx))
    assert 100.0 * unowned / idle_s == pytest.approx(want)
    assert (idle_s, unowned, by_span, places) == pytest.approx(
        tuple(theirs[0][key] for key in (
            "idle_s", "unowned_s", "by_span_s", "unowned_by_place_s")))
    # ... and on the staged fixture, where every place holds something
    theirs, mine = [], []
    want = reader("idle_unowned_pct.longprompt")(
        traced_ctx(STAGED, theirs))
    assert reader("idle_unowned_pct.serve")(
        traced_ctx(STAGED, mine)) == pytest.approx(want)
    assert mine[0]["by_span_s"] == pytest.approx(theirs[0]["by_span_s"])
    assert mine[0]["unowned_by_place_s"] == pytest.approx(
        theirs[0]["unowned_by_place_s"], abs=1e-12)


def test_a_piece_is_read_at_8k_rows_off_the_line_over_its_pairs(rec):
    """Four whole target pieces after the level point (the decode/wait
    that ends at 2.402): 0.2 s + rows x 100/1024 ms each.  The piece
    launched before the capture and the draft's are in no pair."""
    logs = []
    got = reader("prefill_piece_ms_at_8k.serve")(
        traced_ctx(STAGED, logs, rec))
    assert got == pytest.approx(1000.0)
    (table,) = logs
    assert table["by_pair"] == [(2048, 400.0), (4096, 600.0),
                                (8192, 1000.0), (1024, 300.0)]
    assert (table["fit"], table["pairs"]) == ("line", 4)
    assert table["intercept_ms"] == pytest.approx(200.0)
    assert table["slope_us_a_row"] == pytest.approx(1e5 / 1024)
    assert table["residual_spread_ms"] == pytest.approx(0.0, abs=1e-9)
    assert table["mean_ms"] == pytest.approx(575.0)
    assert table["level_at_s"] == pytest.approx(2.4)


def test_starved_counter_beside_the_captures_idle(rec):
    """The capture's four steps, each from the caller's pass before it:
    the counter reads 390 ms where the device idled 450."""
    logs = []
    reader("device_starved_pct.serve")(traced_ctx(STAGED, logs, rec))
    cover = logs[0]["starved_cover"]
    assert cover["by_step_ms"] == [(0.0, 0.0), (80.0, 100.0),
                                   (120.0, 150.0), (190.0, 200.0)]
    assert cover["covered"] == pytest.approx(390.0 / 450.0)


@pytest.mark.parametrize("name", TRACED)
@pytest.mark.parametrize("path", [cellkit.FIXTURE, SCOPED])
def test_traced_reader_reads_nothing_from_a_program_without_stages(
        rec, name, path):
    """A parent commit's capture: no span of the contract
    (``small_trace.json``), or the contract's older spans alone
    (``scoped_trace.json``, with its twins in the ring)."""
    logs = []
    assert reader(name)(traced_ctx(path, logs, rec)) is None
    assert logs == []


def test_twins_need_one_constant_between_the_clocks(rec, monkeypatch):
    """A span read 2 ms apart on the two clocks (its thread lost its
    turn between the readings) still finds its twin; a capture with a
    span the ring lacks, one whose spans fit no constant, and a program
    without a ring have none."""
    from tensorflow_train_distributed_tpu.runtime import events

    def moved(by, rec=None, name="prefill/dispatch", start=1.92):
        ctx = traced_ctx(STAGED, [], rec)
        ctx["trace"].host[:] = [
            e._replace(start=e.start + by)
            if (e.name, e.start) == (name, start) else e
            for e in ctx["trace"].host]
        return ctx

    rows = [2048, 4096, 8192, 1024, 1024]
    for ctx in (moved(0.0, rec), moved(0.002)):
        twins = step_stages.ring_twins(ctx, "prefill/dispatch")
        assert [a["rows"] for _, a in twins] == rows
        assert [e.start for e, _ in twins] == sorted(
            e.start for e in ctx["trace"].host
            if e.name == "prefill/dispatch")
    # 30 ms: no twin of that start; the pairs in order lie too far apart
    assert step_stages.ring_twins(moved(0.03), "prefill/dispatch") is None
    assert reader("prefill_piece_ms_at_8k.serve")(moved(0.03)) is None
    # every second span of the capture somewhere else: no constant fits
    ctx = traced_ctx(STAGED, [])
    ctx["trace"].host[:] = [
        e._replace(start=e.start + 0.01 * (i % 2))
        for i, e in enumerate(ctx["trace"].host)]
    assert step_stages.ring_twins(ctx, "engine/step") is None
    monkeypatch.setattr(events, "get_recorder", lambda: (
        types.SimpleNamespace(events=lambda: [])))
    assert step_stages.ring_twins(moved(0.0), "prefill/dispatch") is None


def ev(name, start, dur):
    return trace.Event(name, start, dur)


@pytest.mark.parametrize("case, want", [
    # the first span takes the first execution after it, then in order
    ("in-order", [(1, 1.5), (2, 2.5)]),
    # from a level point: the execution of an older launch stays out
    ("level", [(1, 2.5)]),
    # an execution before its span: a launch is missing, no pairs
    ("refused", None),
    ("no-spans", []),
])
def test_launches_pair_with_executions_in_order(case, want):
    spans = [(ev("prefill/dispatch", 1.0, 0.1), {"rows": 1}),
             (ev("prefill/dispatch", 2.0, 0.1), {"rows": 2})]
    runs = [ev("p", 0.5, 0.3), ev("p", 1.5, 0.4), ev("p", 2.5, 0.4)]
    after = (None, None)
    if case == "level":
        spans, after = spans[:1], (1.0, 2.0)
    if case == "refused":
        spans[1] = (ev("prefill/dispatch", 2.7, 0.1), {"rows": 2})
    if case == "no-spans":
        spans = []
    got = step_stages.join_in_order(spans, runs, after)
    assert (got if got is None else [
        (a["rows"], ex.start) for a, ex in got]) == want


@pytest.mark.parametrize("case, want", [
    # a first-token read: nothing is queued behind the newest piece
    ("prefill-wait", (3.0, 3.0)),
    # a chunk's wait that blocked: level behind the chunk it waited
    # for, from that chunk's dispatch (a step earlier) on
    ("decode-wait", (0.2, 2.0)),
    # the wait did not block (its chunk had run long before): no point
    ("no-block", (None, None)),
    ("nothing", (None, None)),
])
def test_where_launches_and_executions_are_level(case, want):
    program = [ev("engine/step", 0.1, 0.9), ev("decode/dispatch", 0.2, 0.1),
               ev("engine/step", 1.1, 1.5), ev("decode/dispatch", 1.2, 0.1),
               ev("decode/wait", 1.5, 0.502)]
    chunks = [ev("jit__decode_chunk", 1.0, 1.0)]
    if case == "prefill-wait":
        program = [ev("prefill/wait", 2.9, 0.1)] + program[:4]
    if case == "no-block":
        chunks = [ev("jit__decode_chunk", 0.3, 1.0)]
    if case == "nothing":
        program = program[:4]
    assert step_stages.level_point(program, chunks) == want


@pytest.mark.parametrize("points, want, fit", [
    ([(1024, 30.0), (2048, 40.0), (4096, 60.0)], 100.0, "line"),
    ([(1024, 31.0), (2048, 39.0), (4096, 60.5), (4096, 59.5)], None,
     "line"),
    # fewer than three pairs, or one rows value: the mean, and it says so
    ([(1024, 30.0), (4096, 60.0)], 45.0, "mean"),
    ([(2048, 38.0), (2048, 40.0), (2048, 45.0)], 41.0, "mean"),
])
def test_the_line_over_rows_and_its_fall_back_to_the_mean(points, want,
                                                          fit):
    import numpy as np

    got = step_stages.line_at(points, 8192)
    assert got["fit"] == fit
    if want is None:            # scattered points: numpy's fit is the oracle
        slope, intercept = np.polyfit(*zip(*points), 1)
        want = intercept + slope * 8192
        rest = [y - (intercept + slope * x) for x, y in points]
        assert got["residual_spread"] == pytest.approx(
            np.percentile(rest, 75) - np.percentile(rest, 25))
    assert got["value"] == pytest.approx(want, rel=1e-6)
    if fit == "mean":
        assert got["slope"] is None and got["residual_spread"] is None
    else:
        assert got["slope"] == pytest.approx(10 / 1024, rel=0.05)


def test_new_metrics_are_appended_and_found_by_name():
    with open(os.path.join(cellkit.REPO, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    assert tuple(m["name"] for m in per_layer[-6:]) == NEW
    closed = [m["workloads"] for m in per_layer[-6:]
              if m["moves"] == "serve_tokens_per_s"]
    assert all(len(cells) in (3, 4) for cells in closed)
    assert per_layer[-5]["workloads"] == ["qwen25-7b-1chip.longprompt-rate"]
    assert per_layer[-5]["moves"] == "gap_p90_ms"
    assert per_layer[-4]["layer"] == "gateway / driver"
    for name in NEW:
        assert callable(reader(name))


def test_the_cells_read_as_before_the_stage_metrics_were_appended():
    """The two tests of earlier cells that assert an exact set of
    per-layer metrics (``tests/conftest.py`` marks them expected
    failures: their files are not this PR's to edit) hold every
    assertion on the manifest with this PR's six entries taken off:
    appending changed nothing that was there."""
    import copy

    import test_benchmark_glm as glm
    import test_benchmark_laguna as laguna

    man = manifest_lib.Manifest(cellkit.REPO)
    before = copy.copy(man)
    before.data = copy.deepcopy(man.data)
    assert tuple(m["name"] for m in before.data["per_layer"][-6:]) == NEW
    del before.data["per_layer"][-6:]
    glm.test_new_cells_traffic_and_metrics_are_found_by_name(before)
    laguna.test_the_earlier_share_cell_reads_as_before_a_later_cell_was_appended(
        before)
    # Nothing else of the manifest differs from what those tests saw.
    for section in ("configs", "workloads", "end_to_end"):
        assert before.data[section] == man.data[section]


def test_ring_readers_on_a_served_cell(cell_root, capsys):
    """End to end at test size: a cell served by the real engine behind
    its driver leaves steps in the ring whose stages and counters the
    readers take."""
    root = cell_root("tiny.closed", "tiny", "tiny-closed", 1,
                     ["serve_tokens_per_s"])
    t0 = time.monotonic()
    rc, result, _ = cellkit.run_cell(root, "tiny.closed", capsys=capsys)
    assert rc == 0 and result["correct"]
    logs = []
    ctx = ctx_for(logs, t_open=t0, seconds=time.monotonic() - t0)
    assert 0.0 <= reader("device_starved_pct.serve")(ctx) < 100.0
    assert reader("driver_away_ms.serve")(ctx) > 0.0
    unnamed = reader("step_unnamed_ms.serve")(ctx)
    starved, away, table = logs
    assert starved["steps"] == away["away_ms"]["n"] == table["n"] > 0
    assert set(step_stages.STAGES) <= set(table["by_span"])
    # what no span names is a small part of a step's own work
    named = sum(row["self_ms_mean"] for name, row in
                table["by_span"].items() if name != step_stages.UNNAMED)
    assert 0.0 <= unnamed and (
        table["by_span"][step_stages.UNNAMED]["self_ms_mean"] < named)


def test_idle_by_owner_is_one_sweep():
    """20,000 idle gaps against 4,000 spans (a 4 s capture of a fast
    cell holds 40,000 and 1,000) well inside a second: the spans'
    boundaries are swept once with the idle summed ahead, not every
    span cut out of every gap."""
    gaps = [(i * 1e-3, i * 1e-3 + 4e-4) for i in range(20000)]
    program = []
    for i in range(1000):
        t0 = i * 0.02
        program += [ev("engine/step", t0, 0.019),
                    ev("prefill/piece", t0 + 0.002, 0.01),
                    ev("prefill/dispatch", t0 + 0.003, 0.004),
                    ev("decode/wait", t0 + 0.013, 0.005)]
    t0 = time.monotonic()
    idle_s, by_span, unowned, places = step_stages.idle_by_owner(
        program, step_stages.Idle(gaps))
    assert time.monotonic() - t0 < 1.0
    assert idle_s == pytest.approx(8.0)
    assert by_span["prefill/dispatch"] == pytest.approx(
        1000 * 4 * 4e-4)
    assert unowned + sum(by_span.values()) == pytest.approx(idle_s)
    assert sum(places.values()) == pytest.approx(unowned)
