"""One span call, two sinks: ``runtime.events.span`` in the ring and in a
``jax.profiler`` capture, and the engine's span contract.

CPU only.  A capture here has no device plane; what is checked is the
host plane: the program's spans are events of it, under their names and
attrs, which on the chip puts them on the clock of the device's
operations (``benchmark/layer_metrics/idle_unowned_pct.longprompt.py``
reads them there).
"""

import glob
import os
import subprocess
import sys

import pytest

from tensorflow_train_distributed_tpu.runtime import events

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _trace_on(monkeypatch):
    monkeypatch.delenv("TTD_NO_TRACE", raising=False)


def _captured(tmp_path, body) -> dict:
    """Run ``body()`` under a profiler capture; ``{name: [stats]}`` of
    the capture's host events (stats as a dict per event)."""
    import jax

    logdir = str(tmp_path / "capture")
    jax.profiler.start_trace(logdir)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (dict(ev.stats), ev.start_ns, ev.duration_ns))
    return out


def test_span_lands_in_the_capture_with_its_attrs(tmp_path):
    rec = events.Recorder(16)

    def body():
        with rec.span("decode/dispatch", spec_k=3, fused=1):
            pass

    host = _captured(tmp_path, body)
    (stats, _, dur_ns), = host["decode/dispatch"]
    assert stats == {"spec_k": 3, "fused": 1}
    (name, ph, _, dur, _, attrs), = rec.events()
    assert (name, ph, attrs) == ("decode/dispatch", "X",
                                 {"spec_k": 3, "fused": 1})
    # Two clocks around one block: the same duration within a few us.
    assert abs(dur - dur_ns * 1e-9) < 1e-3


def test_set_attrs_reach_ring_and_capture(tmp_path):
    rec = events.Recorder(16)

    def body():
        with rec.span("engine/step") as step:
            step.set(lanes=2, committed=8)
        with rec.span("engine/step", queued=1) as step:
            step.set(lanes=0)

    host = _captured(tmp_path, body)
    assert [s for s, _, _ in host["engine/step"]] == [
        {"lanes": 2, "committed": 8}, {"queued": 1, "lanes": 0}]
    assert [e[5] for e in rec.events()] == [
        {"lanes": 2, "committed": 8}, {"queued": 1, "lanes": 0}]


def test_kill_switch_emits_to_neither_sink(tmp_path, monkeypatch):
    monkeypatch.setenv("TTD_NO_TRACE", "1")
    rec = events.Recorder(16)

    def body():
        with rec.span("decode/wait") as wait:
            wait.set(overlapped=True)
        rec.instant("slot/insert", rid=1)

    host = _captured(tmp_path, body)
    assert "decode/wait" not in host and "slot/insert" not in host
    assert len(rec) == 0


def test_instants_stay_in_the_ring(tmp_path):
    rec = events.Recorder(16)
    host = _captured(tmp_path, lambda: rec.instant("slot/insert", rid=1))
    assert "slot/insert" not in host
    assert [e[0] for e in rec.events()] == ["slot/insert"]


def test_recording_spans_initialises_no_backend():
    """The supervisor and the procpool parent record spans and must
    never hold the chip: a span may import jax's profiler, never start a
    backend.  A fresh process, so that nothing else has."""
    code = (
        "from tensorflow_train_distributed_tpu.runtime import events\n"
        "with events.span('engine/step', lanes=1) as s:\n"
        "    s.set(committed=2)\n"
        "events.instant('slot/insert', rid=0)\n"
        "from jax._src import xla_bridge\n"
        "print(len(events.get_recorder()),\n"
        "      xla_bridge.backends_are_initialized())\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=300,
                         env=dict(os.environ, TTD_NO_TRACE="0"))
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["2", "False"]


def test_annotation_class_is_resolved_lazily():
    """Not at import of ``events``: the module is imported by processes
    that record nothing."""
    code = (
        "import sys\n"
        "from tensorflow_train_distributed_tpu.runtime import events\n"
        "print(events._ANNOTATION)\n"
        "with events.span('x'): pass\n"
        "print(events._ANNOTATION.__name__)\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=300,
                         env=dict(os.environ, TTD_NO_TRACE="0"))
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["None", "TraceAnnotation"]


def test_spans_between_windows_and_reports_laps():
    rec = events.Recorder(4)
    for i in range(3):
        rec.record_at("engine/step", "X", 10.0 + i, 0.5, {"i": i})
    rec.record_at("slot/insert", "i", 11.2)
    spans, dropped = rec.spans_between(10.0, 12.0)
    assert [e[5]["i"] for e in spans] == [0, 1] and dropped == 0
    spans, _ = rec.spans_between(10.0, 13.0, name="decode/wait")
    assert spans == []
    # Two more events lap the ring: the window's start is gone, and the
    # reader is told how much was lost, not handed a short list in
    # silence.
    rec.record_at("engine/step", "X", 13.0, 0.5, {"i": 3})
    rec.record_at("engine/step", "X", 14.0, 0.5, {"i": 4})
    spans, dropped = rec.spans_between(10.0, 15.0, name="engine/step")
    assert [e[5]["i"] for e in spans] == [2, 3, 4] and dropped == 2
    # A window the ring still holds whole reports nothing lost.
    assert rec.spans_between(12.5, 15.0)[1] == 0
    fresh = events.Recorder(4)
    fresh.record_at("engine/step", "X", 1.0, 0.5)
    fresh.clear()                   # taken out on purpose: not a lap
    fresh.record_at("engine/step", "X", 3.0, 0.5)
    assert fresh.spans_between(2.0, 4.0) == (fresh.events(), 0)


@pytest.mark.parametrize("name, known", [
    ("engine/step", True), ("prefill/wait", True),
    ("compile/ServingEngine._decode_chunk", True),
    ("memory/kv_pool", True), ("engine/stepper", False),
    ("bench/window", False), ("$serving.py:2835 serve_step", False),
    # the stages of a step's host work are spans of their own; the
    # name nothing ever recorded is gone
    ("prefill/stage", True), ("prefill/cache", True),
    ("prefill/dispatch", True), ("prefill/insert", True),
    ("decode/stage", True), ("prefill/request", False)])
def test_contract_membership(name, known):
    assert events.in_contract(name) is known


def test_a_step_counts_what_the_device_was_left_without():
    assert {"starved_ms", "drains", "away_ms"} <= events.contract_attrs(
        "engine/step")


def test_a_step_counts_the_first_tokens_it_left_on_the_device():
    assert "first_deferred" in events.contract_attrs("engine/step")


class _Handle:
    """Stands for an output of the newest program in the engine's
    starved-device account: ``is_ready`` as the test sets it."""

    def __init__(self, ready=False):
        self.ready = ready
        self.polls = 0

    def is_ready(self):
        self.polls += 1
        return self.ready


# ── the engine keeps the contract ──────────────────────────────────────


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    from tensorflow_train_distributed_tpu.models.llama import (
        LLAMA_PRESETS,
        LlamaModel,
    )

    cfg = LLAMA_PRESETS["llama_tiny"]
    params = LlamaModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


#: Engine settings callers really pass: the default, a budget of two
#: pieces a step, the target as its own draft, int8 cache rows.
VARIANTS = {
    "default": dict(),
    "budget-8": dict(prefill_budget=8),
    "self-draft": dict(speculative_k=2),
    "kv-int8": dict(),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_engine_spans_keep_the_contract(tiny, variant):
    """Every name the engine records is in the table; every ``decode/*``
    and ``prefill/*`` span lies inside an ``engine/step`` of its thread;
    the steps' ``committed`` add up to the tokens handed back."""
    import dataclasses

    from tensorflow_train_distributed_tpu.serving import ServingEngine

    cfg, params = tiny
    kw = dict(VARIANTS[variant])
    if variant == "self-draft":
        kw.update(draft_config=cfg, draft_params=params)
    if variant == "kv-int8":
        cfg = dataclasses.replace(cfg, kv_cache_int8=True)
    reqs = [([1, 2, 3], 6), ([4, 5], 5), ([9, 8, 7, 6, 5, 4, 3, 2, 1], 4),
            ([7], 1)]
    eng = ServingEngine(cfg, params, slots=2, cache_len=32, chunk=2,
                        prefill_chunk=4, **kw)
    rec = events.get_recorder()
    seq0 = rec.events_after(0)[0]
    ids = [eng.submit(p, m) for p, m in reqs]
    out = eng.run()
    _, evs = rec.events_after(seq0)

    assert {e[0] for e in evs if not events.in_contract(e[0])} == set()
    for name, _, _, _, _, attrs in evs:
        assert set(attrs or ()) <= events.contract_attrs(name), (
            name, attrs)
    steps = [e for e in evs if e[0] == "engine/step"]
    for name, ph, t0, dur, tid, _ in evs:
        if ph == "X" and name.split("/")[0] in ("decode", "prefill"):
            assert any(s[4] == tid and s[2] <= t0
                       and t0 + dur <= s[2] + s[3] for s in steps), name
    returned = sum(len(out[i]) - len(p) for i, (p, _) in zip(ids, reqs))
    assert returned == sum(m for _, m in reqs)
    assert sum(s[5]["committed"] for s in steps) == returned
    # Counts a step carries: what its dispatch saw, what its pieces held.
    for s in steps:
        assert set(s[5]) == {"lanes", "positions", "kv_blocks",
                             "kv_table_blocks", "kv_window_blocks",
                             "kv_bytes", "state_bytes", "pieces",
                             "piece_calls", "prefill_tokens",
                             "first_deferred", "committed", "queued",
                             "starved_ms", "drains", "away_ms"}
        assert s[5]["starved_ms"] >= 0 and s[5]["away_ms"] >= 0
        assert s[5]["kv_window_blocks"] == 0     # no window layer here
        assert s[5]["state_bytes"] == 0          # no recurrent layer
        assert 0 <= s[5]["lanes"] <= 2
        # A dispatch reads a block or more of every slot, of the 2
        # slots x 2 blocks (cache 32, block 16) their tables have; a
        # step without one reads none.
        assert s[5]["kv_bytes"] == (
            s[5]["kv_blocks"] * eng._kv_pool.bytes_per_block)
        if s[5]["lanes"]:
            assert 2 <= s[5]["kv_blocks"] <= s[5]["kv_table_blocks"] == 4
        else:
            assert s[5]["kv_blocks"] == s[5]["kv_table_blocks"] == 0
    assert sum(s[5]["prefill_tokens"] for s in steps) == sum(
        len(p) for p, _ in reqs)
    # One dispatch a step that had lanes to run, and none otherwise; a
    # speculative round says its depth.
    dispatches = [e for e in evs if e[0] == "decode/dispatch"]
    assert len(dispatches) == len([s for s in steps if s[5]["lanes"]])
    assert {d[5]["spec_k"] for d in dispatches} == {
        kw.get("speculative_k", 0)}
    assert all(s[5]["positions"] >= s[5]["lanes"] for s in steps)
    # A call of the piece program a span, the draft's included (it runs
    # the target's grid again, for every request its first token did
    # not resolve); a call runs one piece, or under a budget of two the
    # two of one prompt that ride a step.
    pieces = [e for e in evs if e[0] == "prefill/piece"]
    assert len(pieces) == sum(s[5]["piece_calls"] for s in steps)
    assert sum(p[5]["pieces"] for p in pieces) == sum(
        s[5]["pieces"] for s in steps) == (
            11 if variant == "self-draft" else 6)
    assert {p[5]["pieces"] for p in pieces} == (
        {1, 2} if variant == "budget-8" else {1})
    assert all(1 <= p[5]["tokens"] <= 4 * p[5]["pieces"] for p in pieces)
    # The long prompt arrives at a step that began with a lane decoding
    # and is metered: one piece by default, two under a budget of 8.
    if "draft" not in variant:
        assert min(s[5]["pieces"] for s in steps if s[5]["pieces"]) == (
            2 if variant == "budget-8" else 1)
    # A first token stays on the device when its prompt's last piece
    # is enqueued, once per request, and a harvest reads it in its
    # ``decode/wait``.  No ``prefill/wait`` while a lane decodes; the
    # one there is reads the token of the request of ONE token, the
    # session's last, at the end of a step with nothing in flight.
    assert sum(s[5]["first_deferred"] for s in steps) == len(reqs)
    last_calls = [p for p in pieces         # of the target's pieces
                  if p[5]["piece"] + p[5]["pieces"] == p[5]["n_pieces"]]
    for s in steps:
        assert s[5]["first_deferred"] == len(
            [p for p in last_calls
             if s[4] == p[4] and s[2] <= p[2] <= s[2] + s[3]])
    waited = [e for e in evs if e[0] == "prefill/wait"]
    assert [e[5]["rid"] for e in waited] in ([], [ids[-1]])
    for e in waited:
        own = [s for s in steps if s[4] == e[4]
               and s[2] <= e[2] <= s[2] + s[3]]
        assert len(own) == 1 and not any(
            d[4] == e[4] and own[0][2] <= d[2] <= e[2]
            for d in evs if d[0] == "decode/dispatch")
    # Every harvest waited in a decode/wait span of its own; the last
    # of a session has no successor dispatched over it.
    waits = [e[5]["overlapped"] for e in evs if e[0] == "decode/wait"]
    assert len(waits) == len(
        [e for e in evs if e[0] == "decode/harvest"]) > 0
    assert True in waits and False in waits
    # The steps' starved milliseconds are the engine's running sum (the
    # gauge), and the first step was not away from anybody.
    assert sum(s[5]["starved_ms"] for s in steps) == pytest.approx(
        1e3 * eng.device_starved_s())
    # (a queue found empty in one step may be charged in the next)
    assert sum(s[5]["drains"] for s in steps) >= len(
        [s for s in steps if s[5]["starved_ms"]])
    assert steps[0][5]["away_ms"] == 0
    assert all(s[5]["away_ms"] > 0 for s in steps[1:])

    # The stages of a step's host work, each a span under its parent.
    def named(name):
        return [e for e in evs if e[0] == name]

    def inside(child, parents):
        return any(p[4] == child[4] and p[2] <= child[2]
                   and child[2] + child[3] <= p[2] + p[3]
                   for p in parents)

    staged = named("prefill/stage")
    assert [(e[5]["rid"], e[5]["tokens"], e[5]["matched"])
            for e in staged] == [(i, len(p), 0)
                                 for i, (p, _) in zip(ids, reqs)]
    allocs = named("kv/alloc")
    assert len(allocs) == len(reqs) and all(
        inside(a, staged) for a in allocs)
    # A batch-1 cache a request and model, a dispatch a piece, an
    # insert a request that reached a lane (the last resolves at its
    # first token): all under a piece.
    drafted = 3 if variant == "self-draft" else 0
    caches = named("prefill/cache")
    assert len(caches) == len(reqs) + drafted
    assert {c[5]["kind"] for c in caches} == {"fresh"}
    launched = named("prefill/dispatch")
    assert [(d[5]["rid"], d[5]["piece"], d[5]["tokens"], d[5]["rows"])
            for d in launched] == [
        (p[5]["rid"], p[5]["piece"], p[5]["tokens"], p[5]["rows"])
        for p in pieces]
    assert sum(d[5]["draft"] for d in launched) == (
        5 if variant == "self-draft" else 0)
    inserts = named("prefill/insert")
    assert sorted(e[5]["rid"] for e in inserts) == ids[:3]
    for child in caches + launched + inserts:
        assert inside(child, pieces), child
    # A chunk's host prelude lies in its dispatch span.
    stages = named("decode/stage")
    assert len(stages) == len(dispatches)
    assert all(inside(st, dispatches) for st in stages)
    assert all(set(st[5]) == {"stale", "refills"} for st in stages)
    assert sum(st[5]["refills"] for st in stages) == 3


@pytest.mark.parametrize("tile, int8, want", [
    (None, False, [32, 32, 32]),    # the cache of 32 is one tile
    (4, False, [4, 8, 12]),         # a tile a piece: 1x, 2x, 3x the piece
    (4, True, [4, 8, 12]),          # int8 rows, dequantized a tile
    (8, False, [8, 8, 16]),         # two pieces a tile: whole tiles
    (10, False, [10, 10, 20])])     # tiles the cache is no multiple of
def test_a_piece_records_the_rows_its_attention_walks(
        tiny, walk_in_tiles, tile, int8, want):
    """``prefill/piece`` ``rows``: the cache rows the piece's attention
    walks by ``ops.attention.prefix_tiles_walked`` (whole tiles from
    row 0 through the piece's last row), beside ``cache_rows``; the
    tokens do not depend on the tile."""
    import dataclasses

    from tensorflow_train_distributed_tpu.serving import ServingEngine

    cfg, params = tiny
    cfg = dataclasses.replace(cfg, kv_cache_int8=int8)
    prompt = [9, 8, 7, 6, 5, 4, 3, 2, 1]            # three pieces of 4

    def serve():
        eng = ServingEngine(cfg, params, slots=2, cache_len=32, chunk=2,
                            prefill_chunk=4)
        rec = events.get_recorder()
        seq0 = rec.events_after(0)[0]
        rid = eng.submit(prompt, 4)
        out = eng.run()[rid]
        return out, [e[5] for e in rec.events_after(seq0)[1]
                     if e[0] == "prefill/piece"]

    whole, _ = serve()
    walk_in_tiles(tile)
    out, pieces = serve()
    assert out == whole
    assert [p["rows"] for p in pieces] == want
    assert {p["cache_rows"] for p in pieces} == {32}
    assert {p["select_rows"] for p in pieces} == {0}    # nothing chooses


@pytest.mark.parametrize("killed", [False, True])
def test_harvest_seconds_split_by_the_wait_span_and_outlive_the_kill_switch(
        tiny, killed, monkeypatch):
    """A harvest-first step (every active lane certainly retires in the
    chunk in flight, so no successor is dispatched over it) waits in a
    ``decode/wait`` that says ``overlapped=False``; ``overlap_stats``
    counts its host pass in ``harvest_s`` only, so ``overlap_ratio()``
    is below 1; the operator's gauge keeps counting under
    ``TTD_NO_TRACE=1``.  An admission in such a step still runs behind
    the chunk in flight: while every poll finds a successor in flight
    (a handle that is never ready), the device is never counted
    starved."""
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    if killed:
        monkeypatch.setenv("TTD_NO_TRACE", "1")
    cfg, params = tiny
    eng = ServingEngine(cfg, params, slots=2, cache_len=32, chunk=2,
                        prompt_buckets=(8,))
    busy = _Handle(ready=False)
    eng._handle_of = lambda out: busy
    rec = events.get_recorder()
    seq0 = rec.events_after(0)[0]
    eng.submit([1, 2, 3], 7)              # first token + three chunks
    eng.serve_step()                      # lane 0 decodes from here on
    eng.serve_step()                      # 4 tokens left: two chunks
    eng.serve_step()                      # 2 left: retires in flight
    assert eng._skip_eager_dispatch()
    eng.submit([4, 5, 6], 4)              # admitted harvest-first
    skipped = eng.overlap_stats["chunks"]
    eng.serve_step()
    # No chunk over the harvest; one after it, for the new lane.
    assert eng.overlap_stats["chunks"] == skipped + 1
    assert eng.active_slots() == 1
    eng.run()
    stats = eng.overlap_stats
    assert 0 < stats["overlapped_harvests"] < stats["chunks"]
    assert 0.0 < stats["overlapped_harvest_s"] < stats["harvest_s"]
    assert 0.0 < eng.overlap_ratio() < 1.0
    assert busy.polls > 0 and eng.device_starved_s() == 0.0
    assert not any(e[5]["drains"] for e in rec.events_after(seq0)[1]
                   if e[0] == "engine/step")
    waits = [e[5]["overlapped"] for e in rec.events_after(seq0)[1]
             if e[0] == "decode/wait"]
    if killed:
        assert not waits
    else:
        assert waits.count(True) == stats["overlapped_harvests"]
        assert False in waits


@pytest.mark.parametrize("killed", [False, True])
def test_a_wait_that_drains_the_queue_is_charged_to_the_next_enqueue(
        tiny, killed, monkeypatch):
    """A harvest-first step: the chunk in flight is the newest program,
    so its ``decode/wait`` returns on an empty queue while a request
    still waits for a lane.  From that return to the next enqueue (the
    stale lane's reset, ahead of the new lane's chunk) the device is
    starved: on a stubbed clock exactly the interval, in
    ``device_starved_s()`` (under ``TTD_NO_TRACE=1`` too) and in the
    step's ``starved_ms`` / ``drains``."""
    import numpy as np

    from tensorflow_train_distributed_tpu.serving import ServingEngine

    if killed:
        monkeypatch.setenv("TTD_NO_TRACE", "1")
    cfg, params = tiny
    eng = ServingEngine(cfg, params, slots=2, cache_len=32, chunk=2,
                        prompt_buckets=(8,))
    now = [0.0]
    eng._clock = lambda: now[0]
    handle = _Handle(ready=False)
    eng._handle_of = lambda out: handle
    rec = events.get_recorder()
    eng.submit([1, 2, 3], 7)
    for _ in range(3):
        eng.serve_step()
    assert eng._skip_eager_dispatch()     # the lane retires in flight
    eng.submit([4, 5, 6], 4)
    assert eng.device_starved_s() == 0.0

    toks = eng._inflight["toks"]

    class Read:                 # the wait's read empties the queue
        def __array__(self, dtype=None, copy=None):
            handle.ready, now[0] = True, 10.0
            return np.asarray(toks)

    eng._inflight["toks"] = Read()
    harvest = eng._harvest

    def later(toks, rids):      # host work between wait and enqueue
        handle.ready, now[0] = False, 10.25
        return harvest(toks, rids=rids)

    eng._harvest = later
    seq0 = rec.events_after(0)[0]
    eng.serve_step()
    assert eng.device_starved_s() == pytest.approx(0.25)
    step = [e[5] for e in rec.events_after(seq0)[1]
            if e[0] == "engine/step"]
    if killed:
        assert not step
    else:
        assert step[0]["starved_ms"] == pytest.approx(250.0)
        assert step[0]["drains"] == 1
    eng.run()
    assert eng.device_starved_s() == pytest.approx(0.25)


@pytest.mark.parametrize("variant", ["default", "self-draft"])
def test_a_donated_handle_is_never_polled(tiny, variant):
    """Every cache leaf is donated to the next program, and a deleted
    array cannot be asked whether it is ready: the engine polls the
    newest program's output only before the enqueue that may consume
    it.  A session that stages, prefills, inserts, decodes, retires
    and resets lanes, with every poll checked."""
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    cfg, params = tiny
    kw = dict(VARIANTS[variant])
    if variant == "self-draft":
        kw.update(draft_config=cfg, draft_params=params)
    eng = ServingEngine(cfg, params, slots=2, cache_len=32, chunk=2,
                        prefill_chunk=4, **kw)
    polls = []

    class Probe:
        def __init__(self, leaf):
            self.leaf = leaf

        def is_ready(self):
            polls.append(self.leaf.is_deleted())
            return self.leaf.is_ready()

    leaf_of = ServingEngine._handle_of
    eng._handle_of = lambda out: Probe(leaf_of(out))
    for prompt, new in [([1, 2, 3], 6), ([4, 5], 5),
                        ([9, 8, 7, 6, 5, 4, 3, 2, 1], 4), ([7], 1)]:
        eng.submit(prompt, new)
    eng.run()
    assert polls and not any(polls)
    assert eng.device_starved_s() >= 0.0
