"""Flight recorder tests (runtime.events and its faces).

Tier-1 pins the tentpole's contracts: the ring buffer is bounded and
lock-safe, ``TTD_NO_TRACE=1`` kills recording cleanly, the Chrome
trace-event export validates against the schema Perfetto needs
(required keys per event, balanced spans), serving outputs are
BITWISE-IDENTICAL with the recorder on vs killed (the always-on
claim), the request-timeline join survives gateway-id reuse, and
``tools/trace_report.py`` renders a dump.  The slow tier adds the
trainer's per-step span anatomy over a real ``fit``.
"""

import json
import threading

import pytest

from tensorflow_train_distributed_tpu.runtime import events
from tensorflow_train_distributed_tpu.runtime.events import Recorder

REQUIRED_KEYS = {"name", "ph", "ts", "pid", "tid"}


@pytest.fixture(autouse=True)
def _trace_on(monkeypatch):
    """These tests A/B the kill switch themselves — an ambient
    TTD_NO_TRACE from the shell would fail the ON legs' asserts."""
    monkeypatch.delenv("TTD_NO_TRACE", raising=False)


def _validate_chrome(trace: dict) -> None:
    """The schema check Perfetto/chrome://tracing loading relies on."""
    assert isinstance(trace["traceEvents"], list)
    json.dumps(trace)                      # exportable as-is
    begins = ends = 0
    for ev in trace["traceEvents"]:
        assert REQUIRED_KEYS <= set(ev), ev
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ph"] in ("X", "i", "B", "E"), ev
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        begins += ev["ph"] == "B"
        ends += ev["ph"] == "E"
    assert begins == ends              # spans balanced (X needs no pair)


# ── ring buffer unit tests ─────────────────────────────────────────────


def test_ring_is_bounded_and_evicts_oldest():
    rec = Recorder(capacity=8)
    for i in range(20):
        rec.instant("tick", i=i)
    assert len(rec) == 8
    kept = [e[5]["i"] for e in rec.events()]
    assert kept == list(range(12, 20))     # oldest fell off the back


def test_span_records_duration_and_attrs():
    rec = Recorder(capacity=16)
    with rec.span("work/unit", k="v"):
        pass
    rec.instant("mark", n=3)
    (name, ph, t0, dur, tid, attrs), (n2, ph2, *_rest) = rec.events()
    assert (name, ph, attrs) == ("work/unit", "X", {"k": "v"})
    assert dur >= 0 and tid == threading.get_ident()
    assert (n2, ph2) == ("mark", "i")


def test_kill_switch_records_nothing(monkeypatch):
    rec = Recorder(capacity=16)
    monkeypatch.setenv("TTD_NO_TRACE", "1")
    assert not rec.enabled
    with rec.span("dead"):
        rec.instant("dead/too")
    assert len(rec) == 0
    assert rec.export_chrome_trace()["otherData"]["killed"] is True
    monkeypatch.delenv("TTD_NO_TRACE")
    with rec.span("live"):
        pass
    assert [e[0] for e in rec.events()] == ["live"]   # flips back live


def test_last_s_window_filters_old_events():
    rec = Recorder(capacity=16)
    old = ("old", "i", -1e9, 0.0, 1, None)   # monotonic long past
    rec._buf.append(old)
    rec.instant("new")
    assert [e[0] for e in rec.events()] == ["old", "new"]
    assert [e[0] for e in rec.events(last_s=60.0)] == ["new"]


def test_export_schema_synthetic():
    rec = Recorder(capacity=16)
    with rec.span("a/b", x=1):
        rec.instant("c/d")
    trace = rec.export_chrome_trace()
    _validate_chrome(trace)
    by_name = {e["name"]: e for e in trace["traceEvents"]}
    assert by_name["a/b"]["args"] == {"x": 1}
    assert by_name["a/b"]["cat"] == "a"
    assert by_name["c/d"]["s"] == "t"


def test_request_timeline_joins_latest_life_only():
    """Gateway request ids restart per driver: the timeline must follow
    the LATEST admission of an id, join engine events through the rid
    its engine-submit recorded, and not leak a previous life's rid."""
    rec = Recorder(capacity=64)
    # First life of request 0: engine rid 7, expired.
    rec.instant("request/admitted", request_id=0)
    rec.instant("request/engine_submit", request_id=0, rid=7)
    rec.instant("prefill/old", rid=7)
    rec.instant("request/retire", request_id=0, status="expired")
    # Unrelated request in between.
    rec.instant("request/admitted", request_id=1)
    # Second life of request 0: engine rid 12, served.
    rec.instant("request/admitted", request_id=0)
    rec.instant("request/engine_submit", request_id=0, rid=12)
    rec.instant("slot/insert", rid=12, slot=0)
    rec.instant("request/commit", request_id=0, tokens=2)
    rec.instant("request/retire", request_id=0, status="ok")
    rec.instant("decode/later", rid=12)    # after retire: out of scope
    names = [e[0] for e in rec.request_timeline(0)]
    assert names == ["request/admitted", "request/engine_submit",
                     "slot/insert", "request/commit", "request/retire"]


def test_request_timeline_stale_pool_anchor_never_captures_solo_life():
    """Ids collide across serving sessions in one process (driver ids
    restart; the recorder is global): a NEWER standalone-driver
    request must anchor on its own admission, not join a stale pool
    request's events — and a pool request's own per-life re-admissions
    (tagged with their replica) must never displace the pool anchor."""
    rec = Recorder(capacity=64)
    # Old pool request id 3 (a finished replica-pool session).
    rec.instant("request/pool_admitted", request_id=3)
    rec.instant("request/admitted", request_id=3, replica=0)
    rec.instant("request/engine_submit", request_id=3, rid=0, replica=0)
    rec.instant("request/commit", request_id=3, tokens=2, replica=0)
    rec.instant("request/pool_retire", request_id=3, status="ok")
    # Newer SINGLE-DRIVER session reuses id 3.
    rec.instant("request/admitted", request_id=3)
    rec.instant("request/engine_submit", request_id=3, rid=9)
    rec.instant("request/commit", request_id=3, tokens=1)
    rec.instant("request/retire", request_id=3, status="ok")
    names = [e[0] for e in rec.request_timeline(3)]
    assert names == ["request/admitted", "request/engine_submit",
                     "request/commit", "request/retire"]
    # The converse: a pool life whose per-life (replica-tagged)
    # admissions come after pool_admitted keeps the POOL anchor.
    rec2 = Recorder(capacity=64)
    rec2.instant("request/pool_admitted", request_id=5)
    rec2.instant("request/admitted", request_id=5, replica=1)
    rec2.instant("request/failover", request_id=5, from_replica=1,
                 resumed_at=2, reason="dead")
    rec2.instant("request/admitted", request_id=5, replica=0)
    rec2.instant("request/pool_retire", request_id=5, status="ok")
    names = [e[0] for e in rec2.request_timeline(5)]
    assert names[0] == "request/pool_admitted"
    assert names.count("request/admitted") == 2


def test_concurrent_appends_and_reads_are_safe():
    rec = Recorder(capacity=1024)
    stop = threading.Event()
    errs = []

    def writer():
        try:
            while not stop.is_set():
                with rec.span("w"):
                    rec.instant("i")
        except BaseException as e:          # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=writer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            _validate_chrome(rec.export_chrome_trace())
            rec.events(last_s=1.0)
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errs
    assert len(rec) == 1024


# ── serving integration: parity + real-trace schema (tier-1) ───────────


@pytest.fixture(scope="module")
def llama_tiny_setup():
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


def _engine_outputs(cfg, params, reqs, **kw):
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    eng = ServingEngine(cfg, params, **kw)
    ids = [eng.submit(p, m) for p, m in reqs]
    out = eng.run()
    return [out[i] for i in ids]


@pytest.mark.parametrize("sampling", [False, True],
                         ids=["greedy", "seeded-sampling"])
def test_serving_parity_recorder_on_vs_killed(llama_tiny_setup,
                                              monkeypatch, sampling):
    """The always-on claim: recording changes NOTHING about served
    tokens — recorder on vs TTD_NO_TRACE=1 are bitwise-identical (the
    recorder only observes host scheduling; device programs and their
    inputs are untouched)."""
    cfg, params = llama_tiny_setup
    reqs = [([1, 2, 3], 6), ([4, 5], 5), ([9, 8, 7, 6], 4)]
    kw = dict(slots=2, cache_len=32, chunk=2, prompt_buckets=(8,))
    if sampling:
        kw.update(temperature=0.8, top_k=20)

    rec = events.get_recorder()
    n0 = len(rec)
    traced = _engine_outputs(cfg, params, reqs, **kw)
    recorded = [e[0] for e in rec.events()][n0:]
    assert any(n.startswith("prefill/") for n in recorded)
    assert any(n.startswith("decode/") for n in recorded)  # engaged

    monkeypatch.setenv("TTD_NO_TRACE", "1")
    n1 = len(rec)
    killed = _engine_outputs(cfg, params, reqs, **kw)
    assert len(rec) == n1                  # kill switch: zero events
    assert killed == traced


def test_real_serving_trace_validates_chrome_schema(llama_tiny_setup):
    """Acceptance: the export of a REAL serving run's events validates
    against the Chrome trace-event schema (required keys, balanced
    spans) and carries the request lifecycle."""
    cfg, params = llama_tiny_setup
    _engine_outputs(cfg, params, [([1, 2, 3], 5)], slots=2,
                    cache_len=32, chunk=2, prompt_buckets=(8,))
    trace = events.get_recorder().export_chrome_trace()
    _validate_chrome(trace)
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"engine/queued", "decode/dispatch", "slot/retire"} <= names


# ── tools/trace_report.py ──────────────────────────────────────────────


def test_trace_report_renders_tables_and_waterfall(tmp_path, capsys):
    import importlib.util
    import os

    rec = Recorder(capacity=64)
    rec.instant("request/admitted", request_id=3)
    rec.instant("request/engine_submit", request_id=3, rid=5)
    with rec.span("prefill/piece", rid=5):
        pass
    rec.instant("request/commit", request_id=3, tokens=2)
    rec.instant("request/retire", request_id=3, status="ok")
    path = tmp_path / "trace.json"
    rec.save(str(path))

    journal = tmp_path / "supervisor.jsonl"
    journal.write_text(json.dumps(
        {"event": "exit", "attempt": 0, "rc": -9, "class": "crash"})
        + "\n")

    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(os.path.dirname(__file__),
                                     "..", "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main([str(path), "--request", "3", "--requests",
                   "--journal", str(journal)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "prefill/piece" in out          # stage table
    assert "request/retire" in out         # waterfall
    assert "status=ok" in out or "ok" in out
    assert "class=crash" in out            # journal overlay


def test_trace_report_prints_a_step_by_stage_and_what_starved(
        tmp_path, capsys):
    """From a ring export: a step's time by the innermost span open
    (self time), what no span covers, and the starved / away seconds a
    step and the first tokens left on the device from ``engine/step``'s
    own counters."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(os.path.dirname(__file__),
                                     "..", "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    rec = Recorder(capacity=64)
    # 100 ms: a dispatch of 30 with its prelude of 20 inside; a piece
    # of 20 holding a dispatch of 10, which reads nothing (the first
    # token stays on the device); the harvest's wait of 30 behind it;
    # 20 under no span.
    for name, off, dur in (("decode/stage", 0.0, 0.02),
                           ("decode/dispatch", 0.0, 0.03),
                           ("prefill/dispatch", 0.035, 0.01),
                           ("prefill/piece", 0.03, 0.02),
                           ("decode/wait", 0.05, 0.03)):
        rec.record_at(name, "X", 10.0 + off, dur)
    rec.record_at("engine/step", "X", 10.0, 0.1,
                  dict(starved_ms=4.0, drains=1, away_ms=0.0,
                       first_deferred=1))
    rec.record_at("engine/step", "X", 10.15, 0.05,
                  dict(starved_ms=8.0, drains=2, away_ms=50.0,
                       first_deferred=2))
    path = tmp_path / "trace.json"
    rec.save(str(path))
    got = mod.step_stages(mod.load_events(str(path)))
    rows = {name: (mean, seen) for name, mean, _, _, seen in got["rows"]}
    assert rows == pytest.approx({
        "decode/stage": (10.0, 1), "decode/dispatch": (5.0, 1),
        "prefill/piece": (5.0, 1), "prefill/dispatch": (5.0, 1),
        "decode/wait": (15.0, 1), "(no span)": (35.0, 2)})
    assert (got["starved_ms"], got["drains"], got["away_ms"]) == (
        12.0, 3, 50.0)
    assert got["first_deferred"] == 3
    assert got["span_ms"] == pytest.approx(200.0)
    assert mod.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "== engine step by stage (2 steps" in out
    assert "device starved     0.012 s of 0.200 s" in out
    assert "25.000 ms between two steps" in out
    assert "first tokens left on the device for a harvest 3 (1.50" in out
    # A trace from before the counters prints the table alone.
    old = Recorder(capacity=8)
    old.record_at("engine/step", "X", 1.0, 0.1, dict(lanes=1))
    old.save(str(path))
    assert mod.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "engine step by stage" in out and "device starved" not in out
    assert "first tokens left" not in out


def test_trace_report_counts_fused_dispatches(tmp_path):
    """The paged-KV summary reports how many decode dispatches ran the
    fused paged-attention kernel (the ``decode/dispatch`` span's
    ``fused`` tag the engine stamps per chunk) — and a gather-leg
    window (fused=0) truthfully reports zero."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(os.path.dirname(__file__),
                                     "..", "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    rec = Recorder(capacity=64)
    with rec.span("decode/dispatch", active=2, fused=1):
        pass
    with rec.span("decode/dispatch", active=2, fused=1):
        pass
    with rec.span("decode/dispatch", active=1, fused=0):
        pass
    rec.instant("kv/prefix_hit", rid=1, tokens=8)
    path = tmp_path / "trace.json"
    rec.save(str(path))
    kv = mod.kv_cache_summary(mod.load_events(str(path)))
    assert kv["fused_attn_dispatches"] == 2
    assert kv["prefix_hit_tokens"] == 8


def test_trace_report_prints_the_kv_walk_live_share(tmp_path, capsys):
    """``engine/step``'s ``kv_blocks`` (what the attention kernel's walk
    read at the step's dispatch) beside ``kv_table_blocks`` (the slots x
    blocks-a-lane table it spans): the report sums both over the window
    and prints the share; a step with no dispatch (both 0) adds
    nothing.  Where some layers see a sliding window,
    ``kv_window_blocks`` (one such layer's walk of its rings) is
    printed as a share of ``kv_blocks``; a model without one prints no
    such line."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(os.path.dirname(__file__),
                                     "..", "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    rec = Recorder(capacity=64)
    for blocks in (1024, 968, 0):
        with rec.span("engine/step") as step:
            step.set(lanes=31, positions=15_445, kv_blocks=blocks,
                     kv_table_blocks=8192 if blocks else 0)
    path = tmp_path / "trace.json"
    rec.save(str(path))
    kv = mod.kv_cache_summary(mod.load_events(str(path)))
    assert (kv["kv_blocks"], kv["kv_table_blocks"]) == (1992, 16384)
    assert mod.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "kv blocks walked   1992 of 16384" in out
    assert "live share 12.2%" in out
    assert "window share" not in out

    windowed = Recorder(capacity=8)
    for blocks, ring in ((9000, 1056), (11000, 1088)):
        with windowed.span("engine/step") as step:
            step.set(lanes=30, positions=160_000, kv_blocks=blocks,
                     kv_table_blocks=34816, kv_window_blocks=ring)
    windowed.save(str(path))
    assert mod.kv_cache_summary(
        mod.load_events(str(path)))["kv_window_blocks"] == 2144
    assert mod.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "a window layer read 2144 of the 20000 blocks" in out
    assert "window share 10.7%" in out

    # Layers that keep a recurrent state: its bytes beside the rows'.
    hybrid = Recorder(capacity=8)
    for lanes, blocks in ((60, 9000), (64, 11000)):
        with hybrid.span("engine/step") as step:
            step.set(lanes=lanes, positions=160_000, kv_blocks=blocks,
                     kv_table_blocks=81920, kv_bytes=blocks * 20480,
                     state_bytes=lanes * 1_000_000)
    hybrid.save(str(path))
    assert mod.kv_cache_summary(
        mod.load_events(str(path)))["state_bytes"] == 124_000_000
    assert mod.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "recurrent state    124000000 bytes" in out
    assert "beside 409600000 bytes of rows walked: state share 23.2%" in out

    linear = Recorder(capacity=8)
    with linear.span("engine/step") as step:
        step.set(lanes=2, positions=9, kv_blocks=0, kv_table_blocks=0)
    linear.save(str(path))
    assert mod.kv_cache_summary(mod.load_events(str(path))) == {}


def test_trace_report_prints_the_share_of_the_cache_prefill_walked(
        tmp_path, capsys):
    """``prefill/piece``'s ``rows`` (what the piece's attention walked)
    beside ``cache_rows`` (a whole cache a piece, what it read before
    the walk): the stage table's footnote sums both over the window's
    pieces and prints walked / (cache_len x pieces); a trace from
    before the attribute prints no such line."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(os.path.dirname(__file__),
                                     "..", "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    rec = Recorder(capacity=64)
    for i, rows in enumerate((1024, 2048, 3072)):
        with rec.span("prefill/piece", rid=7, piece=i, n_pieces=3,
                      tokens=1024, rows=rows, select_rows=0,
                      cache_rows=8192):
            pass
    with rec.span("prefill/piece", rid=8):      # an older program's
        pass
    path = tmp_path / "trace.json"
    rec.save(str(path))
    assert mod.prefill_walk(mod.load_events(str(path))) == (
        3, 6144, 24576, 0)
    assert mod.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert ("walked 6144 of 24576 cache rows in the last pieces of 3 "
            "calls: share walked 0.250\n") in out
    assert "share selected" not in out      # no learned selection here

    # Beside it, where attention chooses its rows: what the choice of
    # a piece counted over (nothing in a piece that keeps all it sees;
    # a span from before the attribute adds nothing) and what the
    # decode steps attended of what they scored.
    with rec.span("prefill/piece", rid=9, piece=2, n_pieces=3, tokens=4,
                  rows=3072, select_rows=3072, cache_rows=8192):
        pass
    with rec.span("prefill/piece", rid=9, piece=3, n_pieces=3, tokens=4,
                  rows=1024, cache_rows=8192):
        pass
    with rec.span("engine/step", lanes=2, rows_scored=7700.0,
                  rows_selected=4096.0):
        pass
    with rec.span("engine/step", lanes=3, rows_scored=12300.0,
                  rows_selected=8192.0):
        pass
    with rec.span("engine/step", lanes=0):
        pass
    rec.save(str(path))
    assert mod.rows_selected(mod.load_events(str(path))) == (
        2, 12288.0, 20000.0)
    assert mod.prefill_walk(mod.load_events(str(path))) == (
        5, 10240, 40960, 3072)
    assert mod.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert ("walked 10240 of 40960 cache rows in the last pieces of 5 "
            "calls: share walked 0.250, share the selection counted over "
            "0.075\n") in out
    assert ("learned selection attended 12288 of 20000 rows scored in 2 "
            "decode steps: share selected 0.614") in out

    old = Recorder(capacity=8)
    with old.span("prefill/piece", rid=8):
        pass
    old.save(str(path))
    assert mod.main([str(path)]) == 0
    assert "share walked" not in capsys.readouterr().out


# ── supervisor instants ────────────────────────────────────────────────


def test_supervisor_journal_doubles_as_instants(tmp_path):
    import sys

    from tensorflow_train_distributed_tpu.runtime.supervisor import (
        TrainSupervisor,
    )

    rec = events.get_recorder()
    n0 = len(rec)
    sup = TrainSupervisor(
        [sys.executable, "-c", "pass"],
        journal_path=str(tmp_path / "j.jsonl"), handle_signals=False)
    res = sup.run()
    assert res.returncode == 0
    names = [e[0] for e in rec.events()[n0:]]
    assert "supervisor/exit" in names
    assert "supervisor/done" in names
    ex = next(e for e in rec.events()[n0:] if e[0] == "supervisor/exit")
    assert ex[5]["class"] == "clean" and ex[5]["rc"] == 0


# ── trainer step anatomy (slow tier: a real fit) ───────────────────────


@pytest.mark.slow
def test_trainer_emits_step_spans(mesh8):
    import optax

    from tensorflow_train_distributed_tpu.data import (
        DataConfig,
        HostDataLoader,
    )
    from tensorflow_train_distributed_tpu.data.datasets import (
        SyntheticBlobs,
    )
    from tensorflow_train_distributed_tpu.training import (
        Trainer,
        TrainerConfig,
    )
    from tests.test_trainer import _BlobsTask

    rec = events.get_recorder()
    n0 = len(rec)
    loader = HostDataLoader(
        SyntheticBlobs(num_examples=64),
        DataConfig(global_batch_size=16, seed=0))
    trainer = Trainer(_BlobsTask(), optax.adam(1e-2), mesh8,
                      config=TrainerConfig(log_every=2))
    trainer.fit(loader, steps=4)
    tail = rec.events()[n0:]
    spans = [e[0] for e in tail if e[1] == "X"]
    assert spans.count("train/data_wait") >= 4
    assert spans.count("train/step_dispatch") >= 4
    assert "train/host_callbacks" in spans
    steps = [e[5]["step"] for e in tail
             if e[0] == "train/step_dispatch"]
    assert steps == [1, 2, 3, 4]
