"""What a traced run does with its capture once the window has closed:
``trace.attribute_gaps`` as one sweep, held to the function it replaced
(``gaps_oracle.py``, G x H) on the fixtures and on generated traces, at
a size only the sweep can reduce; and the ``reduce`` line that
``run.py`` logs before the result."""

import glob
import json
import os
import random
import time

import pytest

import cellkit
import gaps_oracle

from benchmark.harness import trace

FIXTURES = sorted(glob.glob(os.path.join(cellkit.REPO, "benchmark",
                                         "fixtures", "*.json")))
NAMES = ("$serving.py:2592 _harvest", "$serving.py:2828 _dispatch_chunk",
         "$array.py:631 _value", "bench/harvest", "bench/submit",
         "engine/step", "decode/dispatch", "PjitFunction(_reset_lanes)",
         # in which a thread only waits: passed over
         "$threading.py:637 wait", "$queue.py:154 get", "decode/wait",
         "$loadgen.py:160 iter_tokens")


def same(mine, theirs):
    assert [label for label, _ in mine] == [label for label, _ in theirs]
    assert [s for _, s in mine] == pytest.approx([s for _, s in theirs],
                                                 rel=1e-12, abs=0.0)


def generated(seed: int) -> trace.Trace:
    """A capture on a grid of quarter units, so that starts, ends,
    durations and covers coincide often: a device whose operations
    leave gaps of 0 to 6 units, some back to back; 1 to 6 threads of
    nested spans (a child inside its parent, sometimes to the parent's
    very end), zero-length events, names that wait and names of the
    benchmark's own, spans cut at a gap's edges on purpose.  One seed
    in eight has an empty host plane, one in five only thin cover, so
    that gaps fall to the span that covers most or to none."""
    rng = random.Random(seed)
    grid = 0.25
    t, ops = 0.0, []
    for i in range(rng.randint(3, 60)):
        dur = grid * rng.randint(1, 8)
        ops.append(trace.Event(f"%fusion.{i % 7}", t, dur))
        t += dur + grid * rng.choice((0, 0, 1, 1, 2, 3, 6))
    edges = sorted({ev.start for ev in ops}
                   | {ev.start + ev.dur for ev in ops})
    host = []
    thin = seed % 5 == 0

    def spans(lo, hi, depth):
        at = lo
        while at < hi:
            dur = grid * rng.randint(0, 2 if thin else 24)
            if rng.random() < 0.3:          # end on an operation's edge
                dur = max(min(e for e in edges + [hi] if e >= at) - at,
                          0.0)
            dur = min(dur, hi - at)
            host.append(trace.Event(rng.choice(NAMES), at, dur))
            if dur > grid and depth < 4 and rng.random() < 0.7:
                spans(at + grid * rng.randint(0, 1), at + dur, depth + 1)
            at += dur + grid * rng.randint(0, 6 if thin else 2)

    if seed % 8 != 7:
        for _ in range(rng.randint(1, 6)):
            spans(grid * rng.randint(-4, 8), t + grid * rng.randint(-8, 4),
                  0)
        rng.shuffle(host)                   # the capture lists by thread
    return trace.Trace([trace.DevicePlane("/device:TPU:0", ops, [])], host)


@pytest.mark.parametrize("seed", range(48))
def test_the_sweep_gives_the_old_scans_list(seed):
    tr = generated(seed)
    lo, hi = trace.window(tr)
    for lo, hi in ((lo, hi), (lo + 0.125, hi - 1.3), (lo - 1.0, hi + 1.0)):
        for prefer in ("bench/", "engine/"):
            same(trace.attribute_gaps(tr, lo, hi, n=10 ** 6, prefer=prefer),
                 gaps_oracle.attribute_gaps(tr, lo, hi, n=10 ** 6,
                                            prefer=prefer))
    assert trace.attribute_gaps(tr, lo, hi, n=3) == \
        gaps_oracle.attribute_gaps(tr, lo, hi, n=3)


def test_generated_traces_hold_what_they_are_there_for():
    """Ties, waits, the benchmark's spans, gaps no span half covers and
    gaps no span touches all occur among the seeds above."""
    labels, ties, uncovered = set(), 0, 0
    for seed in range(48):
        tr = generated(seed)
        rows = trace.attribute_gaps(tr, *trace.window(tr), n=10 ** 6)
        labels.update(label for label, _ in rows)
        host = [ev for ev in tr.host
                if not any(w in ev.name for w in trace.WAITING)]
        for glo, ghi in trace.idle_gaps(tr.devices[0], *trace.window(tr)):
            covers = [min(ghi, ev.start + ev.dur) - max(glo, ev.start)
                      for ev in host]
            full = [ev.dur for ev, c in zip(host, covers) if c == ghi - glo]
            ties += len(full) != len(set(full))
            uncovered += bool(covers) and 0 < max(covers) < 0.5 * (ghi - glo)
    assert "(no host span)" in labels and "bench/harvest" in labels
    assert not any(w in label for label in labels for w in trace.WAITING)
    assert ties > 20 and uncovered > 5


@pytest.mark.parametrize("path", FIXTURES, ids=os.path.basename)
def test_the_sweep_on_the_fixtures(path):
    tr = trace.load_json(path)
    for lo, hi in (trace.window(tr), (0.5, 3.5)):
        same(trace.attribute_gaps(tr, lo, hi),
             gaps_oracle.attribute_gaps(tr, lo, hi))
    bare = trace.Trace(tr.devices, [])
    same(trace.attribute_gaps(bare, 0.0, 4.0),
         gaps_oracle.attribute_gaps(bare, 0.0, 4.0))
    assert trace.attribute_gaps(trace.Trace([], tr.host), 0.0, 4.0) == []


def test_a_capture_the_old_scan_could_not_reduce():
    """20,000 idle gaps against 300,000 host events (6e9 pairs for the
    old scan: hours): 64 callers' frames alive from end to end, an
    engine thread whose calls nest four deep and start and end inside
    gaps, in well under the 20 s allowed here."""
    rng = random.Random(28)
    step = 50e-6
    ops = [trace.Event("%fusion.1", i * step, 40e-6) for i in range(20_001)]
    end = ops[-1].start + ops[-1].dur
    host = []
    for _ in range(64):
        host.append(trace.Event("$loadgen.py:158 _follow", -1.0, end + 2.0))
        host.append(trace.Event("$threading.py:1012 run", -1.0, end + 2.0))
    at = 0.0
    while len(host) < 300_000:
        dur = rng.choice((1e-6, 3e-6, 12e-6, 70e-6))
        names = ("engine/step", "$serving.py:2828 _dispatch_chunk",
                 "$array.py:631 _value", "bench/submit")
        for depth, name in enumerate(names):
            host.append(trace.Event(name, at + depth * 0.1e-6,
                                    dur - depth * 0.2e-6))
        at += dur + rng.choice((0.0, 0.5e-6))
    assert at > 0.5 * end           # the calls run on through the gaps
    tr = trace.Trace([trace.DevicePlane("/device:TPU:0", ops, [])], host)
    gaps = trace.idle_gaps(tr.devices[0], 0.0, end)
    assert len(gaps) == 20_000 and len(tr.host) >= 300_000
    t0 = time.monotonic()
    rows = trace.attribute_gaps(tr, 0.0, end, n=10 ** 6)
    seconds = time.monotonic() - t0
    assert seconds < 20.0
    assert sum(s for _, s in rows) == pytest.approx(trace.total(gaps))
    # The oracle on the first hundred gaps alone (a 200th of its work).
    cut = gaps[100][0]
    head = trace.Trace(tr.devices, [ev for ev in host if ev.start < cut])
    same(trace.attribute_gaps(head, 0.0, cut, n=10 ** 6),
         gaps_oracle.attribute_gaps(head, 0.0, cut, n=10 ** 6))


XSPACE = """
planes {
  name: "/host:CPU"
  lines { name: "engine" timestamp_ns: 5000
          events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
          events { metadata_id: 2 offset_ps: 4100000 duration_ps: 800000 } }
  lines { name: "caller" timestamp_ns: 5000
          events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "engine/step" } }
  event_metadata { key: 2 value { id: 2 name: "bench/harvest" } }
  event_metadata { key: 3 value { id: 3 name: "$queue.py:154 get" } }
}
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 5000
          events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
          events { metadata_id: 1 offset_ps: 5000000 duration_ps: 4000000 } }
  lines { name: "XLA Ops" timestamp_ns: 5000
          events { metadata_id: 2 offset_ps: 0 duration_ps: 4000000 }
          events { metadata_id: 2 offset_ps: 5000000 duration_ps: 3000000 }
          events { metadata_id: 3 offset_ps: 8500000 duration_ps: 500000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__decode_chunk(7)" } }
  event_metadata { key: 2 value {
    id: 2 name: "%fusion.1 = bf16[32,18944] fusion(%p)" } }
  event_metadata { key: 3 value {
    id: 3 name: "%copy.67 = bf16[12,8193,16,4,128] copy(%r)" } }
}
"""
PER_LAYER = ("compile_s", "decode_step_ms.decode", "decode_gap_ms.decode",
             "device_idle_pct.decode", "decode_hbm_pct.decode")


def test_a_traced_run_logs_what_its_capture_cost(cell_root, capsys,
                                                 monkeypatch):
    """``run.main`` with ``--trace 1`` over a runner that only opens the
    window and captures, the profiler's two calls replaced by ones that
    lay a small capture where the tracer writes: the line before the
    result is the ``reduce`` line, with every key; the result is as it
    was (metrics, device, breakdown), and a reader with nothing to read
    is timed and left out."""
    import jax
    from jax.profiler import ProfileData

    from benchmark import run

    root = cell_root("tiny.traced", "tiny", "tiny-closed", 1,
                     ["serve_tokens_per_s"], per_layer=PER_LAYER[1:])
    where = []
    monkeypatch.setattr(jax.profiler, "start_trace", where.append)

    def stop_trace():
        into = os.path.join(where[-1], "plugins", "profile", "t")
        os.makedirs(into)
        with open(os.path.join(into, "h.xplane.pb"), "wb") as f:
            f.write(ProfileData.text_proto_to_serialized_xspace(XSPACE))

    monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)

    def runner(ctx):
        ctx["window_opened"](time.monotonic())
        ctx["tracer"].start()
        ctx["tracer"].stop()
        return {"correct": True, "attempted": 3, "failed": 0,
                "memory_peak_bytes": 1, "end_to_end": {},
                "counters": {"chunk": 8, "records": []}}

    rc = run.main(["--workload", "tiny.traced", "--seed", "3000000019",
                   "--seconds", "1", "--trace", "1"], root=root,
                  require_platform=None, runners={"serve": runner})
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert rc == 0 and where == [os.path.join(root, ".bench_trace",
                                              "tiny.traced")]
    reduce, result = lines[-2], lines[-1]
    assert reduce.pop("phase") == "reduce"
    assert sorted(reduce) == ["attribute_gaps_s", "device_ops",
                              "host_events", "idle_gaps", "load_s",
                              "readers_s", "stop_export_s", "top_ops_s"]
    assert sorted(reduce.pop("readers_s")) == sorted(PER_LAYER)
    assert (reduce.pop("idle_gaps"), reduce.pop("host_events"),
            reduce.pop("device_ops")) == (2, 3, 3)
    assert all(0.0 <= seconds < 5.0 for seconds in reduce.values())
    assert [ln["phase"] for ln in lines[:-2]] == ["start", "setup"]
    # The result, as every traced run printed it before.
    assert list(result) == ["correct", "attempted", "failed", "breakdown",
                            "metrics", "device"]
    assert sorted(result["metrics"]) == sorted(PER_LAYER[:4])
    assert result["metrics"]["decode_gap_ms.decode"] == {
        "value": pytest.approx(1e-3), "unit": "ms"}
    assert result["metrics"]["device_idle_pct.decode"]["value"] == \
        pytest.approx(100 * 1.5 / 9)
    assert result["device"]["busy_s"] == pytest.approx(7.5e-6)
    assert result["device"]["window_s"] == pytest.approx(9e-6)
    assert result["breakdown"]["device_ops"][0] == [
        "%fusion.1 fusion bf16[32,18944]", pytest.approx(7e-6)]
    assert result["breakdown"]["idle_gaps"] == [
        ["bench/harvest", pytest.approx(1e-6)],
        ["engine/step", pytest.approx(5e-7)]]
