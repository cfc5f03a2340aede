"""The six per-layer readers that read the program's own spans, counts
and device scopes: each on a hand-built ring or on
``benchmark/fixtures/scoped_trace.json`` (``small_trace.json`` with what
the scopes and spans add), and each where there is nothing to read."""

import os
import threading
import types

import pytest

import cellkit

from benchmark.harness import manifest as manifest_lib
from benchmark.harness import scopes, spans, trace

SCOPED = os.path.join(cellkit.REPO, "benchmark", "fixtures",
                      "scoped_trace.json")
RING = ("host_self_ms.decode", "host_self_p95_ms.longprompt",
        "decode_lanes_mean.decode", "step_prefill_tokens_p95.longprompt")
TRACED = ("decode_plumbing_ms.decode", "idle_unowned_pct.longprompt")


def reader(name):
    return manifest_lib.Manifest(cellkit.REPO).layer_reader(name)


def step(rec, t0, dur, tid_wait=(), **attrs):
    """One ``engine/step`` of the recording thread with ``*/wait``
    children at (offset, duration)."""
    for off, wait in tid_wait:
        rec.record_at("decode/wait", "X", t0 + off, wait)
    rec.record_at("engine/step", "X", t0, dur, attrs)


@pytest.fixture
def ring(monkeypatch):
    """A recorder in the program's place, holding a window [100, 110)
    of five steps: four that dispatched, one that only prefilled, with
    a step before and one after the window."""
    from tensorflow_train_distributed_tpu.runtime import events

    rec = events.Recorder(256)
    monkeypatch.setattr(events, "get_recorder", lambda: rec)
    step(rec, 99.0, 0.5, lanes=9, prefill_tokens=9, committed=1)
    step(rec, 100.0, 1.0, [(0.1, 0.7)], lanes=4, positions=400,
         prefill_tokens=0, pieces=0, committed=8)
    step(rec, 101.0, 1.0, [(0.1, 0.4), (0.6, 0.38)], lanes=2,
         positions=100, prefill_tokens=1024, pieces=1, committed=4)
    step(rec, 102.0, 2.0, [(0.5, 1.4)], lanes=4, positions=500,
         prefill_tokens=4000, pieces=4, committed=8)
    step(rec, 104.0, 0.1, lanes=0, positions=0, prefill_tokens=700,
         pieces=1, committed=1)
    step(rec, 105.0, 1.0, [(0.0, 0.99)], lanes=3, positions=300,
         prefill_tokens=0, pieces=0, committed=6)
    step(rec, 110.0, 1.0, lanes=9, prefill_tokens=9, committed=1)
    # Another thread's wait inside a step's interval is not its child.
    other = threading.Thread(
        target=lambda: rec.record_at("decode/wait", "X", 100.2, 0.5))
    other.start()
    other.join()
    return rec


def ctx_for(logs, **more):
    ctx = {"result": {"counters": {"t_open": 100.0, "seconds": 10.0,
                                   "slots": 4, "chunk": 2}},
           "traffic": {"engine": {"prefill_budget": 4096}},
           "log": lambda **rec: logs.append(rec)}
    ctx.update(more)
    return ctx


# self times of the five steps of the window, ms
SELF_MS = [300.0, 220.0, 600.0, 100.0, 10.0]


@pytest.mark.parametrize("name, want", [
    ("host_self_ms.decode", 220.0),
    ("host_self_p95_ms.longprompt", 600.0 - 0.2 * 300.0),
    # lanes of the four that dispatched, weighted by 1, 1, 2 and 1 s
    ("decode_lanes_mean.decode", (4 + 2 + 2 * 4 + 3) / 5.0),
    # 0, 0, 1024, 4000 -> p95 by interpolation
    ("step_prefill_tokens_p95.longprompt", 1024 + 0.85 * (4000 - 1024)),
])
def test_ring_reader_on_a_hand_built_ring(ring, name, want):
    logs = []
    assert reader(name)(ctx_for(logs)) == pytest.approx(want)
    (table,) = logs                    # its table, for people
    assert table["steps"] == 5 and table["ring_dropped"] == 0


def test_self_time_table_says_where_a_step_went(ring):
    logs = []
    reader("host_self_ms.decode")(ctx_for(logs))
    (table,) = logs
    assert table["inside_ms_mean"] == pytest.approx(
        {"decode/wait": (700 + 780 + 1400 + 990) / 5.0})
    assert table["slowest"]["self_ms"] == pytest.approx(600.0)
    assert table["slowest"]["attrs"]["prefill_tokens"] == 4000


def test_self_time_takes_only_the_threads_own_waits(ring):
    steps, dropped = spans.window_steps(ctx_for([]))
    assert [round(1e3 * s.self_s, 6) for s in steps] == SELF_MS
    assert dropped == 0


def test_self_time_leaves_out_the_steps_a_capture_overlapped(ring):
    """The profiler's Python tracer slows the host's own work: steps
    that overlap the capture (here 101.5-103: the second and third) are
    logged apart and the number is read from the others."""
    logs = []
    ctx = ctx_for(logs, tracer=types.SimpleNamespace(t0=101.5, t1=103.0))
    assert reader("host_self_p95_ms.longprompt")(ctx) == pytest.approx(
        100.0 + 0.9 * 200.0)     # of 10, 100 and 300
    (table,) = logs
    assert (table["steps"], table["n"], table["traced"]["n"]) == (5, 3, 2)
    assert table["traced"]["slowest"]["self_ms"] == pytest.approx(600.0)
    assert table["slowest"]["self_ms"] == pytest.approx(300.0)
    assert [t[2] for t in table["timeline"]] == [0, 1, 1, 0, 0]
    # The counts are the engine's own and are read from every step.
    assert reader("step_prefill_tokens_p95.longprompt")(ctx) == (
        pytest.approx(1024 + 0.85 * (4000 - 1024)))
    assert logs[1]["queued"]["n"] == 5


def test_a_wait_that_outlasts_the_window_is_still_its_steps(monkeypatch):
    """The window's last step began inside it; the read it blocks on
    begins after the window has closed, and is no self time."""
    from tensorflow_train_distributed_tpu.runtime import events

    rec = events.Recorder(16)
    monkeypatch.setattr(events, "get_recorder", lambda: rec)
    step(rec, 109.5, 1.0, [(0.6, 0.3)], lanes=1)
    (last,), _ = spans.window_steps(ctx_for([]))
    assert last.self_s == pytest.approx(0.7)


def test_ring_reader_says_what_the_ring_lapped(ring, monkeypatch):
    from tensorflow_train_distributed_tpu.runtime import events

    small = events.Recorder(2)
    monkeypatch.setattr(events, "get_recorder", lambda: small)
    for i in range(4):
        step(small, 100.0 + i, 0.5, lanes=1)
    logs = []
    assert reader("decode_lanes_mean.decode")(ctx_for(logs)) == 1.0
    assert logs[0]["ring_dropped"] == 2 and logs[0]["steps"] == 2


@pytest.mark.parametrize("name", RING)
@pytest.mark.parametrize("why", ["no-steps", "older-program"])
def test_ring_reader_without_spans_reads_nothing(monkeypatch, name, why):
    """A window without steps, and a program whose recorder predates
    ``spans_between`` (the parent commit), both give None, no error."""
    from tensorflow_train_distributed_tpu.runtime import events

    rec = (events.Recorder(8) if why == "no-steps"
           else types.SimpleNamespace(events=lambda: []))
    monkeypatch.setattr(events, "get_recorder", lambda: rec)
    logs = []
    assert reader(name)(ctx_for(logs)) is None and logs == []


@pytest.mark.parametrize("op_name, want", [
    ("jit(_decode_chunk)/while/body/closed_call/LlamaModel/layers/while/"
     "body/closed_call/stack/block/mlp/mlp/wo/dot_general", "mlp"),
    ("jit(f)/LlamaModel/layers/while/body/closed_call/stack/block/"
     "attn_norm/norm/rms_norm_fwd/pallas_call", "norm"),
    ("jit(f)/stack/block/attention/attention._paged_decode_step/"
     "attn/qkv/attention._qkv/attention._proj/query/dot_general",
     "attn/qkv"),
    ("jit(f)/stack/block/attention/attention._paged_decode_step/"
     "attention._attn_epilogue/attn/out/attention._out_proj/out/"
     "dot_general", "attn/out"),
    ("jit(f)/LlamaModel/token_embed/embed/jit(_take)/gather", "embed"),
    ("jit(f)/LlamaModel/layers/while/body/dynamic_update_slice", None),
    ("jit(f)/LlamaModel/lm_head_norm/dot_general", None),
    ("", None),
])
def test_scope_of_an_op_name(op_name, want):
    assert scopes.scope_of(op_name) == want


XSPACE = """
planes { name: "/host:CPU" }
planes {
  name: "/device:TPU:0"
  lines { name: "Steps" timestamp_ns: 5000
          events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 } }
  lines { name: "XLA Modules" timestamp_ns: 5000
          events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 } }
  lines { name: "XLA Ops" timestamp_ns: 5000
          events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
          events { metadata_id: 3 offset_ps: 3000000 duration_ps: 500000 }
          events { metadata_id: 4 offset_ps: 3500000 duration_ps: 250000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__decode_chunk(7)" } }
  event_metadata { key: 2 value {
    id: 2 name: "%fusion.1 = bf16[32,18944] fusion(%p)"
    stats { metadata_id: 9 uint64_value: 4345298944 }
    stats { metadata_id: 7 str_value: "jit(_decode_chunk)/x/mlp/mlp/wo/dot_general:" } } }
  event_metadata { key: 3 value {
    id: 3 name: "%fusion.2 = bf16[131088,4,128] fusion(%q)"
    stats { metadata_id: 7 ref_value: 8 } } }
  event_metadata { key: 4 value { id: 4 name: "%copy.67 = bf16[12,8193,16,4,128] copy(%r)" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "jit(_decode_chunk)/y/kv_pool/write/scatter:Scatter" } }
  stat_metadata { key: 9 value { id: 9 name: "flops" } }
}
"""


def test_a_capture_is_read_with_its_operations_op_names(tmp_path):
    """What ``ProfileData`` leaves out: ``tf_op`` among the stats of an
    operation's metadata, as a string or as a reference to one; times
    as ``ProfileData`` gives them."""
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    ops, programs = scopes.load(str(tmp_path))
    assert [(op.name.split(" ")[0], op.op_name) for op in ops] == [
        ("%fusion.1", "jit(_decode_chunk)/x/mlp/mlp/wo/dot_general"),
        ("%fusion.2", "jit(_decode_chunk)/y/kv_pool/write/scatter"),
        ("%copy.67", "")]
    theirs = trace.load_xplane(str(path)).devices[0]
    for mine, other in ((ops, theirs.ops), (programs, theirs.modules)):
        assert [ev.name for ev in mine] == [ev.name for ev in other]
        assert [ev.start for ev in mine] == pytest.approx(
            [ev.start for ev in other])
        assert [ev.dur for ev in mine] == pytest.approx(
            [ev.dur for ev in other])
    assert scopes.by_scope(ops, programs) == pytest.approx(
        {"mlp": 2e-6, "kv_pool/write": 5e-7, scopes.PLUMBING: 2.5e-7})


def traced_ctx(path, logs):
    tr = trace.load_json(path)
    return ctx_for(logs, trace=tr, trace_window=trace.window(tr),
                   tracer=types.SimpleNamespace(directory=path))


def test_plumbing_is_what_no_scope_and_no_kernel_owns():
    """Two chunks of two steps.  Inside them: mlp 1.0 s, the pool write
    0.5 s, the kernel 0.3 s, two operations under no scope 2.0 s; the
    loop's own event and the prefill program's operation stay out."""
    logs = []
    got = reader("decode_plumbing_ms.decode")(traced_ctx(SCOPED, logs))
    assert got == pytest.approx(2.0 / 4 * 1e3)
    (table,) = logs
    assert table["by_scope_ms"] == pytest.approx({
        scopes.PLUMBING: 500.0, "mlp": 250.0, "kv_pool/write": 125.0,
        scopes.KERNEL: 75.0})
    assert table["program_ms"] == pytest.approx(2.7 / 4 * 1e3)


def test_idle_is_owned_by_the_innermost_span_below_the_step():
    """The device idles for 0.9 s of the capture's 4: 1.7-2.0 and
    2.4-3.0.  ``prefill/piece`` owns 0.15 of the first; the steps own
    nothing themselves.  Unowned: 0.4 under a step alone, 0.05 under
    the wait the piece does not cover, 0.1 between the steps and 0.2
    after the last span."""
    logs = []
    got = reader("idle_unowned_pct.longprompt")(traced_ctx(SCOPED, logs))
    assert got == pytest.approx(100.0 * 0.75 / 0.9)
    (table,) = logs
    assert table["by_span_s"] == pytest.approx({"prefill/piece": 0.15})
    assert table["idle_s"] == pytest.approx(0.9)
    assert table["window_s"] == pytest.approx(4.0)
    assert table["unowned_by_place_s"] == pytest.approx({
        "engine/step alone": 0.4, "*/wait": 0.05, "between steps": 0.1,
        "capture edges": 0.2})


@pytest.mark.parametrize("name", TRACED)
def test_traced_reader_reads_nothing_from_a_program_without_names(name):
    """``small_trace.json`` is what a parent commit's capture holds: no
    scope on any operation, no span of the contract among the host's."""
    logs = []
    assert reader(name)(traced_ctx(cellkit.FIXTURE, logs)) is None
    assert logs == []


def test_scoped_fixture_extends_the_small_one():
    """The reduction's own numbers do not move between the two."""
    small, scoped = trace.load_json(cellkit.FIXTURE), trace.load_json(SCOPED)
    assert trace.window(small) == trace.window(scoped)
    assert trace.idle_share_pct(small, 0.0, 4.0) == pytest.approx(
        trace.idle_share_pct(scoped, 0.0, 4.0))
    assert set(small.host) <= set(scoped.host)
    assert {tuple(op) for op in small.devices[0].ops} <= {
        tuple(op) for op in scoped.devices[0].ops}


def test_readers_on_a_served_cell(cell_root, capsys):
    """End to end at test size: a cell served by the real engine leaves
    steps in the ring whose counts add up."""
    import time

    from tensorflow_train_distributed_tpu.runtime import events

    root = cell_root("tiny.closed", "tiny", "tiny-closed", 1,
                     ["serve_tokens_per_s"])
    t0 = time.monotonic()
    seq0 = events.get_recorder().events_after(0)[0]
    rc, result, _ = cellkit.run_cell(root, "tiny.closed", capsys=capsys)
    assert rc == 0 and result["correct"]
    # Engine and driver together keep to the contract's names and attrs.
    recorded = events.get_recorder().events_after(seq0)[1]
    assert {"request/admitted", "request/commit", "engine/step"} <= {
        e[0] for e in recorded}
    for name, _, _, _, _, attrs in recorded:
        assert events.in_contract(name), name
        assert set(attrs or ()) <= events.contract_attrs(name), (
            name, attrs)
    logs = []
    ctx = ctx_for(logs)
    ctx["result"]["counters"].update(t_open=t0,
                                     seconds=time.monotonic() - t0)
    lanes = reader("decode_lanes_mean.decode")(ctx)
    assert 1.0 <= lanes <= 4.0
    assert reader("host_self_ms.decode")(ctx) > 0.0
    assert logs[0]["committed"] > 0
