"""Fleet observability plane (clock sync, trace spool, roofline).

Fast tier, four seams:

- **ClockSync math** (pure, no I/O): the NTP-style midpoint estimate
  stays within its ``rtt/2`` bound under injected ±50 ms skew and
  asymmetric transport legs; the min-RTT filter and the drift window
  behave; the one-way HELLO estimate's transport-latency bias — the
  bug this PR fixes — is demonstrated against the corrected path, and
  cross-worker hop latencies stay POSITIVE once both ends are
  offset-corrected.
- **Crash-durable spool** (``TTD_TRACE_SPOOL``): segment headers carry
  the wall/mono anchors, ring-lap drops become honesty markers,
  rotation enforces the byte cap by unlinking the process's own
  oldest segments, and the env var auto-arms a fresh Recorder.
- **Live roofline** (``compilecheck``): a dispatched compile site
  accumulates flops/bytes from XLA cost analysis, the mfu/mbu gauges
  render against env-pinned peaks, and with NO peak known they render
  NOTHING (never a made-up percentage).
- **Transport integration**: a raw-socket TCP peer's STATS frame
  lands its ``hbm`` and ``programs`` dicts in the pool's
  ``hbm_by_pool``/``programs_by_site`` (the netpool satellite), and a
  live subprocess fleet converges to a synced clock whose relayed
  events carry ``clock_conf_s`` — unless ``TTD_NO_CLOCK_SYNC=1``.

The SIGKILL-mid-decode post-mortem chaos leg lives in
``tools/chaos_check.py --serving --disagg`` (sampled in
tests/test_disagg.py's chaos smoke).
"""

import glob
import importlib.util
import json
import os
import socket
import time

import pytest

from tensorflow_train_distributed_tpu.runtime import events
from tensorflow_train_distributed_tpu.runtime.events import Recorder
from tensorflow_train_distributed_tpu.runtime.lint import compilecheck
from tensorflow_train_distributed_tpu.server import proto
from tensorflow_train_distributed_tpu.server.netpool import NetPool
from tensorflow_train_distributed_tpu.server.procpool import (
    ClockSync,
    ProcPool,
    WorkerSpec,
    clock_sync_killed,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(REPO_ROOT, "tools",
                                     "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ── ClockSync math (pure) ──────────────────────────────────────────────


def _exchange(cs, *, t0, d_up, d_down, skew):
    """One PING/PONG over a simulated transport: the worker's
    monotonic clock reads ``parent_mono + skew``, the legs take
    ``d_up``/``d_down``.  Returns (accepted, true_offset) where
    true_offset maps worker mono → parent mono (= ``-skew``)."""
    t1 = t0 + d_up + skew           # worker's stamp at the echo
    t3 = t0 + d_up + d_down         # parent receives the PONG
    body = dict(cs.ping(t0), mono=t1)
    return cs.pong(body, t3), -skew


@pytest.mark.parametrize("skew", [0.05, -0.05, 0.0])
def test_offset_within_rtt_bound_under_skew(skew):
    """±50 ms of clock skew: the midpoint estimate's error is bounded
    by rtt/2 REGARDLESS of skew (symmetric legs make it exact)."""
    cs = ClockSync()
    ok, true_offset = _exchange(cs, t0=100.0, d_up=0.002,
                                d_down=0.002, skew=skew)
    assert ok
    assert cs.offset == pytest.approx(true_offset, abs=1e-12)
    assert cs.confidence_s() == pytest.approx(0.002)


def test_asymmetric_legs_stay_inside_the_bound():
    """A 4 ms up / 1 ms down transport shifts the estimate by
    |d_up - d_down|/2 = 1.5 ms — still inside the rtt/2 = 2.5 ms
    bound, under 50 ms of skew."""
    cs = ClockSync()
    ok, true_offset = _exchange(cs, t0=7.0, d_up=0.004,
                                d_down=0.001, skew=0.05)
    assert ok
    err = abs(cs.offset - true_offset)
    assert err == pytest.approx(0.0015)
    assert err <= cs.confidence_s()


def test_one_way_hello_bias_regression():
    """The bug this PR fixes: the HELLO path set
    ``_mono_offset = parent_now - worker_mono`` from ONE stamp,
    silently absorbing the full transport latency (40 ms here) into
    every relayed timestamp.  The two-stamp exchange over the SAME
    delayed transport pins the error to rtt/2 — and symmetric legs
    recover the true offset exactly."""
    d = 0.040                               # a slow TCP hop
    skew = 0.05
    t_send = 200.0
    worker_mono_at_send = t_send + skew
    # Old estimator: the parent stamps at RECEIPT of the worker's one
    # HELLO stamp — the pipe latency lands inside the offset.
    old_offset = (t_send + d) - worker_mono_at_send
    true_offset = -skew
    assert abs(old_offset - true_offset) == pytest.approx(d)

    cs = ClockSync()
    ok, true_offset = _exchange(cs, t0=t_send, d_up=d, d_down=d,
                                skew=skew)
    assert ok
    assert abs(cs.offset - true_offset) <= cs.confidence_s()
    assert abs(cs.offset - true_offset) < abs(old_offset - true_offset)


def test_hop_latency_positive_under_bidirectional_skew():
    """The fleet-waterfall acceptance: prefill worker at +50 ms skew,
    decode worker at −50 ms, a true 5 ms handoff hop between them.
    Offset-corrected timestamps keep the hop positive and within the
    summed confidence of the two estimates; the uncorrected stamps
    render it as −95 ms."""
    cs_a, cs_b = ClockSync(), ClockSync()
    ok_a, off_a = _exchange(cs_a, t0=10.0, d_up=0.002, d_down=0.001,
                            skew=0.05)
    ok_b, off_b = _exchange(cs_b, t0=10.0, d_up=0.001, d_down=0.002,
                            skew=-0.05)
    assert ok_a and ok_b
    # Prefill ends at parent-true time 20.000, decode starts 20.005.
    prefill_end_worker = 20.000 + 0.05      # worker A's own stamp
    decode_start_worker = 20.005 - 0.05     # worker B's own stamp
    raw_hop = decode_start_worker - prefill_end_worker
    assert raw_hop < 0                      # the pre-sync symptom
    corrected = ((decode_start_worker + cs_b.offset)
                 - (prefill_end_worker + cs_a.offset))
    assert corrected > 0
    bound = cs_a.confidence_s() + cs_b.confidence_s()
    assert abs(corrected - 0.005) <= bound


def test_min_rtt_filter_and_drift_window():
    cs = ClockSync()
    assert _exchange(cs, t0=0.0, d_up=0.001, d_down=0.001,
                     skew=0.01)[0]
    crisp = cs.offset
    # A congested sample (20 ms rtt) inside the drift window never
    # replaces the crisp one...
    ok, _ = _exchange(cs, t0=1.0, d_up=0.015, d_down=0.005, skew=0.01)
    assert not ok
    assert cs.offset == crisp
    # ...but after DRIFT_WINDOW_S the next in-bound sample wins even
    # at a worse rtt (crystals drift; a stale perfect sample lies).
    later = ClockSync.DRIFT_WINDOW_S + 2.0
    ok, _ = _exchange(cs, t0=later, d_up=0.003, d_down=0.003,
                      skew=0.011)
    assert ok
    assert cs.offset == pytest.approx(-0.011)


def test_garbage_pongs_never_fold():
    cs = ClockSync()
    assert not cs.pong({}, 1.0)
    assert not cs.pong({"t": "nope", "mono": 0.0}, 1.0)
    assert not cs.pong({"t": 5.0, "mono": 0.0}, 4.0)    # rtt < 0
    assert not cs.pong({"t": 0.0, "mono": 0.0},
                       ClockSync.MAX_RTT_S + 1.0)       # congestion
    assert cs.offset is None and cs.confidence_s() is None


def test_kill_switch_reader(monkeypatch):
    monkeypatch.delenv("TTD_NO_CLOCK_SYNC", raising=False)
    assert not clock_sync_killed()
    monkeypatch.setenv("TTD_NO_CLOCK_SYNC", "0")
    assert not clock_sync_killed()
    monkeypatch.setenv("TTD_NO_CLOCK_SYNC", "1")
    assert clock_sync_killed()


# ── crash-durable trace spool ──────────────────────────────────────────


def _read_spool(directory):
    headers, rows, drops = [], [], []
    for path in sorted(glob.glob(os.path.join(directory,
                                              "spool-*.jsonl"))):
        with open(path) as f:
            for line in f:
                rec = json.loads(line)
                if isinstance(rec, dict) and rec.get("spool"):
                    headers.append(rec)
                elif isinstance(rec, dict) and "dropped" in rec:
                    drops.append(rec)
                elif isinstance(rec, dict):
                    rows.extend(rec.get("b") or [])
                else:
                    rows.append(rec)
    return headers, rows, drops


def test_spool_header_anchors_and_final_flush(tmp_path):
    rec = Recorder(capacity=256)
    assert rec.start_spool(str(tmp_path)) == str(tmp_path)
    with rec.span("decode/dispatch", rid=1, step=0):
        pass
    rec.instant("request/commit", request_id=1, tokens=2)
    n = rec.flush_spool()
    assert n == 2
    rec.stop_spool()
    headers, rows, drops = _read_spool(str(tmp_path))
    assert headers and headers[0]["pid"] == os.getpid()
    # The anchors reconstruct wall time offline: both clocks sampled
    # at Recorder construction, within this test's lifetime.
    assert abs(headers[0]["wall_anchor_s"] - time.time()) < 300
    assert not drops
    names = [r[0] for r in rows]
    assert names == ["decode/dispatch", "request/commit"]
    assert rows[1][5]["tokens"] == 2
    # Disarmed: further flushes are no-ops, info is None.
    assert rec.flush_spool() == 0
    assert rec.spool_info() is None


def test_spool_ring_lap_writes_drop_marker(tmp_path):
    """The flusher lagging behind a hot ring must say so on disk: a
    ``{"dropped": n}`` line, not silently contiguous events."""
    rec = Recorder(capacity=64)
    rec.start_spool(str(tmp_path))
    for i in range(600):
        rec.instant("hot/event", i=i)
    rec.flush_spool()
    rec.stop_spool()
    _, rows, drops = _read_spool(str(tmp_path))
    assert len(rows) == 64                  # what the ring still held
    assert drops and drops[0]["dropped"] == 600 - 64
    assert rows[-1][5]["i"] == 599          # newest survived


def test_spool_rotation_enforces_byte_cap(tmp_path, monkeypatch):
    """Segments rotate at cap/4 and the process unlinks its own
    oldest segments to stay under TTD_TRACE_SPOOL_BYTES."""
    monkeypatch.setenv("TTD_TRACE_SPOOL_BYTES", str(2 << 20))
    rec = Recorder(capacity=8192)
    rec.start_spool(str(tmp_path))
    payload = "x" * 160
    for _ in range(8):                      # ~0.8 MiB per batch
        for i in range(4096):
            rec.instant("bulk/event", i=i, payload=payload)
        rec.flush_spool()
    info = rec.spool_info()
    rec.stop_spool()
    assert info["segment"] >= 3, info       # rotation happened
    files = glob.glob(os.path.join(str(tmp_path), "spool-*.jsonl"))
    assert len(files) < info["segment"], "no old segment was unlinked"
    total = sum(os.path.getsize(f) for f in files)
    # Cap plus one segment of slack (the open segment rotates only at
    # the NEXT flush after crossing seg_cap).
    assert total <= (2 << 20) + (1 << 20) + 65536, total


def test_spool_env_auto_arms_new_recorders(tmp_path, monkeypatch):
    monkeypatch.setenv("TTD_TRACE_SPOOL", str(tmp_path))
    rec = Recorder(capacity=64)
    try:
        info = rec.spool_info()
        assert info is not None and info["active"]
        rec.instant("auto/armed")
        assert rec.flush_spool() == 1
    finally:
        rec.stop_spool()
    monkeypatch.delenv("TTD_TRACE_SPOOL")
    rec2 = Recorder(capacity=64)
    assert rec2.spool_info() is None        # off by default


# ── live roofline (compilecheck cost capture) ──────────────────────────


def test_roofline_counts_dispatches_and_renders_gauges(monkeypatch):
    import jax
    import jax.numpy as jnp

    from tensorflow_train_distributed_tpu.runtime.lint.registry import (
        compile_site,
    )

    if not compilecheck.armed():
        pytest.skip("TTD_COMPILECHECK not armed")
    site = "test.obs_roofline"
    compilecheck.reset(site)

    @compile_site(site=site, statics=(), donates=(), max_compiles=2)
    @jax.jit
    def _mm(x):
        return x @ x

    x = jnp.ones((64, 64), jnp.float32)
    for _ in range(4):
        _mm(x).block_until_ready()

    stats = compilecheck.program_stats()
    assert site in stats, stats
    s = stats[site]
    assert s["dispatches"] == 4
    # XLA's cost model on CPU reports a 64x64x64 matmul's flops; the
    # per-dispatch number must be positive and scale with dispatches.
    assert s["flops_total"] > 0
    assert s["flops_per_s"] > 0
    assert s["flops_total"] == pytest.approx(
        4 * s["flops_total"] / s["dispatches"])

    # Env-pinned peaks (the CPU-test seam): percentages become exact
    # arithmetic on the captured rates.
    monkeypatch.setenv("TTD_PEAK_FLOPS", "1e9")
    monkeypatch.setenv("TTD_PEAK_HBM_BYTES", "1e9")
    mfu = compilecheck.mfu_by_program()
    mbu = compilecheck.mbu_by_program()
    assert mfu[site] == pytest.approx(
        100.0 * s["flops_per_s"] / 1e9, rel=0.25)
    assert site in mbu
    from tensorflow_train_distributed_tpu.server.metrics import (
        GatewayMetrics,
    )

    m = GatewayMetrics(queue_depth_fn=lambda: 0,
                       slots_in_use_fn=lambda: 0, slots_total=1)
    text = m.render()
    assert f'ttd_engine_mfu_pct{{program="{site}"}}' in text
    assert f'ttd_engine_mbu_pct{{program="{site}"}}' in text
    compilecheck.reset(site)


def test_roofline_renders_nothing_without_a_known_peak(monkeypatch):
    """Off-TPU with no TTD_PEAK_* pinned there is NO denominator —
    the gauges must render no series, not a fabricated number."""
    monkeypatch.delenv("TTD_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("TTD_PEAK_HBM_BYTES", raising=False)
    if compilecheck.peak_flops_per_s() is not None:
        pytest.skip("host reports a real device peak")
    assert compilecheck.mfu_by_program() == {}
    assert compilecheck.mbu_by_program() == {}
    from tensorflow_train_distributed_tpu.server.metrics import (
        GatewayMetrics,
    )

    m = GatewayMetrics(queue_depth_fn=lambda: 0,
                       slots_in_use_fn=lambda: 0, slots_total=1)
    text = m.render()
    assert "ttd_engine_mfu_pct{" not in text
    assert "ttd_engine_mbu_pct{" not in text


# ── transport integration ──────────────────────────────────────────────


def test_tcp_stats_frame_lands_hbm_and_programs_in_pool(monkeypatch):
    """The netpool satellite: a dial-in worker's STATS frame carries
    ``hbm`` and ``programs`` dicts, and the pool surfaces them keyed
    ``<replica>/<pool>`` — so ``ttd_engine_hbm_bytes{pool=...}`` and
    the mfu/mbu gauges cover TCP workers, not just subprocesses."""
    pool = NetPool(host="127.0.0.1", port=0, scale_min=1,
                   max_workers=2, watchdog_timeout_s=10.0,
                   monitor_poll_s=0.02).start()
    sock = None
    try:
        hello = proto.encode_frame(proto.HELLO, {
            "proto": proto.PROTO_VERSION, "pid": 4242,
            "replica": None, "role": "decode", "mono": 0.0,
            "engine": {"slots": 1, "kv_block_size": 16,
                       "cache_len": 64,
                       "pool_blocks": None, "buckets": None}})
        sock = socket.create_connection(("127.0.0.1", pool.port),
                                        timeout=10)
        sock.sendall(hello)
        assert pool.wait_ready(10)
        sock.sendall(proto.encode_frame(proto.STATS, {
            "queue_depth": 0, "active_slots": 0, "steps": 1,
            "hbm": {"kv_cache": 12345.0, "weights": 99.0},
            "programs": {"serving.decode": {
                "dispatches": 4, "flops_total": 8.0,
                "bytes_total": 16.0, "flops_per_s": 2.0,
                "bytes_per_s": 4.0}}}))
        deadline = time.monotonic() + 10
        hbm = {}
        while time.monotonic() < deadline:
            hbm = pool.hbm_by_pool()
            if any(k.endswith("/kv_cache") for k in hbm):
                break
            time.sleep(0.02)
        kv = [v for k, v in hbm.items() if k.endswith("/kv_cache")]
        assert kv == [12345.0], hbm
        progs = pool.programs_by_site()
        decode = [v for k, v in progs.items()
                  if k.endswith("/serving.decode")]
        assert decode and decode[0]["dispatches"] == 4, progs
        # The parent-side peak pins turn the relayed rates into fleet
        # mfu/mbu series.
        monkeypatch.setenv("TTD_PEAK_FLOPS", "1e2")
        monkeypatch.setenv("TTD_PEAK_HBM_BYTES", "1e2")
        mfu = pool.mfu_by_program()
        key = [k for k in mfu if k.endswith("/serving.decode")]
        assert key and mfu[key[0]] == pytest.approx(2.0)
        # And the labeled gauge family renders the TCP worker's pools.
        from tensorflow_train_distributed_tpu.server.metrics import (
            Registry,
        )

        r = Registry()
        r.labeled_gauge("ttd_engine_hbm_bytes", "live bytes", "pool",
                        fn=pool.hbm_by_pool)
        text = r.render()
        assert 'pool="' in text and "/kv_cache" in text
    finally:
        if sock is not None:
            sock.close()
        pool.join(timeout=30)


def _stub_pool(n=1, **kw):
    kw.setdefault("watchdog_timeout_s", 10.0)
    kw.setdefault("monitor_poll_s", 0.02)
    kw.setdefault("restart_backoff_s", 0.05)
    spec = WorkerSpec(factory="stub", factory_json={"slots": 2},
                      stats_interval_s=0.05)
    return ProcPool(spec, replicas=n, **kw).start()


def test_subprocess_fleet_converges_to_synced_clock():
    """A live stub fleet: within a few heartbeats every replica's
    /healthz clock block reports a PONG-backed offset with a bounded
    confidence, and relayed worker events carry ``clock_conf_s`` and
    their replica id."""
    cursor = events.get_recorder().events_after(0)[0]
    pool = _stub_pool(1)
    try:
        assert pool.wait_ready(30)
        h = pool.submit([3, 4], 4)
        assert h.result(timeout=30)
        clock = {}
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            clock = pool.replica_states()[0].get("clock") or {}
            if clock.get("synced"):
                break
            time.sleep(0.05)
        assert clock.get("synced"), clock
        assert clock["rtt_s"] > 0.0
        assert clock["conf_s"] == pytest.approx(clock["rtt_s"] / 2.0,
                                                abs=1e-6)
        assert abs(clock["offset_s"]) < 60.0     # same host, sane
        deadline = time.monotonic() + 10
        relayed = []
        while time.monotonic() < deadline and not relayed:
            _, evs = events.get_recorder().events_after(cursor)
            relayed = [e for e in evs
                       if (e[5] or {}).get("clock_conf_s") is not None]
            time.sleep(0.05)
        assert relayed, "no relayed event carried clock_conf_s"
        attrs = relayed[0][5]
        assert attrs["replica"] == 0
        assert 0.0 < attrs["clock_conf_s"] < 5.0
    finally:
        assert pool.join(timeout=30)


def test_kill_switch_restores_one_way_offset_path(monkeypatch):
    """TTD_NO_CLOCK_SYNC=1: no PINGs leave the parent, so the clock
    block stays on the HELLO's one-way estimate (synced=False) while
    relay itself keeps working."""
    monkeypatch.setenv("TTD_NO_CLOCK_SYNC", "1")
    pool = _stub_pool(1)
    try:
        assert pool.wait_ready(30)
        h = pool.submit([5, 6], 3)
        assert h.result(timeout=30)
        time.sleep(0.5)                     # several heartbeats
        clock = pool.replica_states()[0].get("clock") or {}
        assert clock.get("synced") is False, clock
        assert clock.get("offset_s") is not None    # HELLO guess
        assert "rtt_s" not in clock
    finally:
        assert pool.join(timeout=30)


# ── trace_report: fleet + post-mortem faces ────────────────────────────


def test_trace_report_fleet_view(tmp_path, capsys):
    evs = []

    def ev(name, ph, ts, dur=None, **args):
        e = {"name": name, "ph": ph, "ts": ts, "pid": 1, "tid": 1,
             "args": args}
        if dur is not None:
            e["dur"] = dur
        evs.append(e)

    ev("request/admitted", "i", 100.0, request_id=7)
    ev("engine/prefill", "X", 120.0, dur=5000.0, request_id=7,
       replica=0, clock_conf_s=0.0002)
    ev("handoff/export", "X", 5200.0, dur=300.0, request_id=7,
       prefill_replica=0)
    ev("handoff/install", "X", 5900.0, dur=150.0, request_id=7,
       decode_replica=1, bytes=4096)
    ev("decode/dispatch", "X", 6200.0, dur=900.0, request_id=7,
       replica=1, clock_conf_s=0.0005)
    ev("request/migrate", "i", 9000.0, request_id=7, from_replica=1,
       to_replica=2, ms=3.25, bytes=2048, resumed_at=40)
    ev("request/done", "i", 9500.0, request_id=7)
    doc = {"traceEvents": evs, "displayTimeUnit": "ms", "otherData": {
        "fleet": [{"replica": 0, "state": "ready",
                   "clock": {"synced": True, "offset_s": -2.5e-5,
                             "rtt_s": 4e-4, "conf_s": 2e-4}}],
        "roofline": {"0/decode_step": {
            "dispatches": 120, "flops_per_s": 2.0e11,
            "bytes_per_s": 3.0e10, "mfu_pct": 12.5, "mbu_pct": 44.2}},
    }}
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(doc))
    mod = _trace_report()
    rc = mod.main([str(path), "--fleet", "--request", "7"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "fleet view" in out
    # The measured handoff hop: export END (5500 us) → install START
    # (5900 us) = 0.400 ms, positive.
    assert "kv_handoff" in out and "0.400" in out
    assert "migrate" in out and "3.250" in out
    assert "±0.20ms" in out                 # lane clock confidence
    assert "decode_step" in out and "12.50" in out   # roofline table


def test_trace_report_post_mortem_reconstructs_death(tmp_path,
                                                     capsys):
    """The chaos acceptance in miniature: a worker's spooled ring plus
    the parent's corpse snapshot must surface the final decode
    dispatch of the request it died serving."""
    rec = Recorder(capacity=128)
    rec.start_spool(str(tmp_path))
    for i in range(5):
        with rec.span("decode/dispatch", request_id=7, replica=1,
                      step=i):
            pass
    rec.flush_spool()
    # No stop_spool(): SIGKILL never runs atexit — the fsynced
    # segments ARE the durable record.
    corpse = {"corpse": 1, "replica": 1, "pid": os.getpid(),
              "returncode": -9, "reason": "killed", "drained": False,
              "clock": {"synced": True, "offset_s": -2.5e-5,
                        "rtt_s": 4e-4, "conf_s": 2e-4},
              "events_relayed": 5,
              "last_events": [["decode/dispatch", "X", 1.0, 0.001,
                               {"request_id": 7, "step": 4}]],
              "wall_s": time.time(), "mono_s": time.monotonic()}
    (tmp_path / f"corpse-1-{os.getpid()}-123.json").write_text(
        json.dumps(corpse))
    mod = _trace_report()
    rc = mod.main(["--post-mortem", str(tmp_path)])
    out = capsys.readouterr().out
    rec.stop_spool()
    assert rc == 0
    assert "reason=killed" in out and "rc=-9" in out
    assert "decode/dispatch" in out and "step=4" in out
    assert "offset=-0.025ms" in out         # clock state at death
