"""The yardstick's arithmetic: operation and byte counts and the peaks
table against values worked by hand, and the trace reduction on the
small hand-built trace kept in ``benchmark/fixtures`` (interval
arithmetic, busy union and idle share, per-program time, gaps attributed
to host spans, the per-layer metric readers)."""

import json
import os

import pytest

from benchmark.harness import costs, peaks, trace
from benchmark.harness.manifest import Manifest
from cellkit import FIXTURE, REPO

with open(os.path.join(REPO, "benchmark", "configs",
                       "qwen25-7b-1chip.json")) as f:
    QWEN = json.load(f)


@pytest.fixture(scope="module")
def tr():
    return trace.load_json(FIXTURE)


def test_qwen_layer_and_model_parameters_by_hand():
    # q 3584*3584, k and v 3584*512 each, o 3584*3584, three 3584*18944.
    per_layer = 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944
    assert per_layer == 233_046_016
    assert costs.layer_matmul_params(QWEN) == per_layer
    n = QWEN["num_hidden_layers"]
    assert costs.matmul_params(QWEN) == n * per_layer + 3584 * 152064
    # The issue's check, at the depth it planned: 2 x 3.8 B forward.
    fourteen = dict(QWEN, num_hidden_layers=14)
    assert costs.matmul_params(fourteen) / 1e9 == pytest.approx(3.807, abs=1e-3)


def test_forward_flops_count_attention_over_the_keys_attended():
    seq = 1024
    want = (2 * costs.matmul_params(QWEN)
            + QWEN["num_hidden_layers"] * 4 * 28 * 128 * (seq + 1) / 2)
    assert costs.forward_flops_per_token(QWEN, seq) == pytest.approx(want)


def test_decode_step_bytes_weights_once_and_kv_at_real_lengths():
    n = QWEN["num_hidden_layers"]
    small = n * 2 * 3584 + 3584 + n * (3584 + 2 * 512)
    assert costs.weight_bytes(QWEN) == 2 * (costs.matmul_params(QWEN) + small)
    assert costs.kv_bytes_per_token(QWEN) == 2 * n * 4 * 128 * 2
    lanes = [100, 250, 4096]
    assert costs.decode_step_bytes(QWEN, lanes) == (
        costs.weight_bytes(QWEN) + costs.kv_bytes_per_token(QWEN) * 4446)


def test_a_share_above_105_percent_is_a_bug_not_a_result():
    assert costs.share_pct(50.0, 100.0, "x") == 50.0
    assert costs.share_pct(104.0, 100.0, "x") == 104.0
    with pytest.raises(ValueError, match="count or the time"):
        costs.share_pct(106.0, 100.0, "x")


def test_peaks_table_is_keyed_by_device_kind_and_refuses_unknown_kinds():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "cloud.google.com" in v5e["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_interval_arithmetic():
    assert trace.union([(0, 1), (0.5, 1.5), (2, 3), (3, 3)]) == \
        [(0, 1.5), (2, 3)]
    assert trace.subtract([(0, 4)], [(1, 2), (3, 5)]) == [(0, 1), (2, 3)]
    assert trace.subtract([(0, 1), (2, 3)], [(0.5, 2.5)]) == \
        [(0, 0.5), (2.5, 3)]
    assert trace.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]
    assert trace.total([(0, 1.5), (2, 3)]) == 2.5


def test_busy_union_counts_overlapping_ops_once(tr):
    lo, hi = trace.window(tr)
    assert (lo, hi) == (0.0, 4.0)
    # [0, 1.7) + [2.0, 2.4) + [3.0, 4.0): the 0.5-1.0 overlap counts once
    assert trace.busy_seconds(tr.devices[0]) == pytest.approx(3.1)
    assert trace.mean_busy_seconds(tr, lo, hi) == pytest.approx(3.1)
    idle = 100 * (1 - trace.mean_busy_seconds(tr, lo, hi) / (hi - lo))
    assert idle == pytest.approx(22.5)
    gaps = trace.idle_gaps(tr.devices[0], lo, hi)
    assert [(round(a, 6), round(b, 6)) for a, b in gaps] == \
        [(1.7, 2.0), (2.4, 3.0)]


def test_program_time_and_the_idle_gap_between_its_executions(tr):
    plane = tr.devices[0]
    assert len(trace.program_events(plane, "_decode_chunk")) == 2
    assert trace.mean_execution_seconds(plane, "_decode_chunk") == \
        pytest.approx(1.35)
    assert trace.mean_execution_seconds(plane, "_no_such") is None
    # from 1.7 to 3.0, less the 0.4 s the prefill program ran
    assert trace.gaps_between(plane, "_decode_chunk") == \
        [pytest.approx(0.9)]
    top = trace.top_ops(tr, 2)
    assert [name for name, _ in top] == ["%fusion.1", "%fusion.2"] or \
        top[0][1] == pytest.approx(1.0)


def test_gaps_go_to_the_innermost_working_span(tr):
    rows = dict(trace.attribute_gaps(tr, 0.0, 4.0))
    # the benchmark's own span covers the first gap, the engine's
    # harvest the second; the thread that only waits gets nothing, and
    # the loop span that covers everything loses to the inner spans.
    assert rows == {"bench/harvest": pytest.approx(0.3),
                    "$serving.py:2592 _harvest": pytest.approx(0.6)}
    bare = trace.Trace(tr.devices, [])
    assert dict(trace.attribute_gaps(bare, 0.0, 4.0)) == \
        {"(no host span)": pytest.approx(0.9)}


def test_layer_metric_readers_on_the_fixture(tr):
    man = Manifest(REPO)
    ctx = {"trace": tr, "trace_window": (0.0, 4.0), "tracer": None,
           "peaks": None, "config": man.config("qwen25-7b-1chip"),
           "setup": {"compile_s": 1.25},
           "result": {"counters": {"chunk": 8, "records": [],
                                   "committed_tokens_per_s": 250.0}}}
    read = man.layer_reader
    assert read("device_idle_pct.decode")(ctx) == pytest.approx(22.5)
    assert read("decode_step_ms.decode")(ctx) == pytest.approx(1350 / 8)
    assert read("decode_gap_ms.decode")(ctx) == pytest.approx(900.0)
    assert read("compile_s")(ctx) == 1.25
    assert read("commit_tokens_per_s.decode")(ctx) == 250.0
    # nothing to read -> nothing returned, and the harness leaves it out
    assert read("decode_hbm_pct.decode")(ctx) is None
    assert read("prefill_tokens_per_s.longprompt")(ctx) is None
    # 3 pieces carried 2,400 prompt tokens; a piece took 0.4 s of device
    ctx["result"]["counters"].update(prefill_pieces=3,
                                     prefill_prompt_tokens=2400)
    assert read("prefill_tokens_per_s.longprompt")(ctx) == \
        pytest.approx(2000.0)
