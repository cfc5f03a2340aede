"""The latent-attention, routed-expert configuration's part of the
benchmark: a ``family: "moe"`` cell added by files and manifest entries
alone runs end to end through ``harness/serve_family.py`` at test size
on the CPU (and its ``fp8w`` control comes out not correct), every
configuration file is what its family's builder runs, the published
file equals its catalog row, ``costs_moe`` by hand, and the new readers
on a hand-built capture and where there is nothing to read."""

import json
import os
import types

import pytest

import cellkit
from cellkit import REPO, run_cell

from benchmark.harness import costs_moe, manifest as manifest_lib
from benchmark.harness import scope_table, scopes, serve_family, trace
from test_benchmark_spans import ctx_for, step, traced_ctx

SCOPED_MOE = os.path.join(REPO, "benchmark", "fixtures",
                          "scoped_trace_moe.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CTX = ("decode_step_ms.ctx", "moe_experts_ms.ctx", "moe_gmm_roofline.ctx",
       "latent_attn_ms.ctx", "latent_attn_roofline.ctx",
       "decode_plumbing_ms.ctx", "experts_hit_mean.ctx",
       "device_idle_pct.ctx", "prefill_piece_ms.ctx")
# Accepted metrics the cell is appended to, or that list no cells.
SHARED = ("compile_s", "host_self_ms.decode", "decode_lanes_mean.decode")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def man():
    return manifest_lib.Manifest(REPO)


def reader(name):
    return manifest_lib.Manifest(REPO).layer_reader(name)


def _compared(earlier):
    checked = next(r for r in earlier if r.get("phase") == "check")
    return {row["number"]: row for row in checked["compared"]}


def test_a_moe_family_cell_added_by_files_alone_runs_and_its_control_fails(
        cell_root, capsys, monkeypatch):
    from tensorflow_train_distributed_tpu.runtime import events

    results = []
    real_run = serve_family.run
    monkeypatch.setattr(serve_family, "run", lambda ctx: (
        results.append(real_run(ctx)) or results[-1]))
    root = cell_root("glm-tiny.closed", "glm-tiny", "glm-tiny-closed", 1,
                     ["serve_tokens_per_s", "gap_p95_ms"])
    seq0 = events.get_recorder().events_after(0)[0]
    rc, sound, earlier = run_cell(root, "glm-tiny.closed", seed=2 ** 31 + 5,
                                  capsys=capsys)
    assert rc == 0 and sound["correct"] is True, (sound, earlier[-1])
    # The routed engine keeps to the contract's names and attrs, and its
    # steps carry the experts' counts.
    recorded = events.get_recorder().events_after(seq0)[1]
    for name, _, _, _, _, attrs in recorded:
        assert events.in_contract(name), name
        assert set(attrs or ()) <= events.contract_attrs(name), (name, attrs)
    hits = [e[5]["experts_hit"] for e in recorded
            if e[0] == "engine/step" and "experts_hit" in (e[5] or {})]
    assert hits and all(2.0 <= h <= 8.0 for h in hits)
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert set(sound["metrics"]) == {"serve_tokens_per_s", "gap_p95_ms",
                                     "setup_s"}
    window = next(r for r in earlier if r.get("phase") == "window")
    assert window["compiles_in_window"] == 0
    assert window["engine_stats"]["prefill"]["installments"] > 0
    checked = next(r for r in earlier if r.get("phase") == "check")
    assert checked["reference"].endswith("glm_moe_lite")
    sound_rows = _compared(earlier)
    # The accepted ring readers the real cell is appended to read this
    # runner's counters as they read ``serve``'s.
    logs = []
    ctx = {"result": results[0], "tracer": None,
           "log": lambda **rec: logs.append(rec)}
    assert 0.0 < reader("decode_lanes_mean.decode")(ctx) <= 4.0
    assert reader("host_self_ms.decode")(ctx) > 0.0
    assert {r["phase"] for r in logs} == {"decode_lanes_mean",
                                          "host_self_ms"}
    rc, control, earlier = run_cell(
        root, "glm-tiny.closed", seed=2 ** 31 + 5,
        extra=["--control", "fp8w"], capsys=capsys)
    assert rc == 0 and control["correct"] is False
    rows = _compared(earlier)
    assert not rows["served_gap_mean"]["within"]
    assert rows["served_gap_mean"]["value"] > \
        10 * max(sound_rows["served_gap_mean"]["value"], 1e-6)


def test_fp8_control_rounds_to_the_formats_own_values():
    """``to_fp8_and_back`` is the ``float8_e4m3fn`` round trip, value
    for value, over normals, subnormals, ties and zero (the plain pair
    of conversions is exact here on the CPU; the TPU's compiler folds
    it away, which is why the control spells it out)."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(3)
    x = np.concatenate([
        rng.normal(size=4000) * 0.022, rng.normal(size=4000),
        rng.uniform(-447, 447, 2000), np.arange(-32, 33) * 2.0 ** -10,
        [0.0, 2.0 ** -6, -(2.0 ** -6), 2.0 ** -9, 1.0625, 1.1875, 448.0]])
    for dtype in (jnp.bfloat16, jnp.float32):
        v = jnp.asarray(x, dtype)
        want = v.astype(jnp.float8_e4m3fn).astype(dtype)
        got = serve_family.to_fp8_and_back(v)
        assert got.dtype == v.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    assert float(jnp.mean(serve_family.to_fp8_and_back(v) != v)) > 0.9


def test_every_configuration_file_is_what_its_family_runs(man):
    """``test_configuration_file_is_what_the_program_runs`` for every
    family: the builder ``serve_family`` picks by ``program.family``
    accepts the file as it stands and refuses a width that differs."""
    for c in man.data["configs"]:
        cfg_file = man.config(c["name"])
        build, shapes_of = serve_family.FAMILIES[
            cfg_file["program"]["family"]]
        cfg = build(cfg_file)
        assert cfg.num_layers == cfg_file["num_hidden_layers"]
        with pytest.raises(ValueError, match="would run"):
            build(dict(cfg_file, hidden_size=1234))
    glm = man.config("glm47-flash-1chip")
    with pytest.raises(ValueError, match="the program's block has"):
        serve_family.moe_config(dict(glm, topk_method="greedy"))
    with pytest.raises(ValueError, match="would run"):
        serve_family.moe_config(dict(glm, routed_scaling_factor=2.5))
    leaves = serve_family.moe_param_shapes(serve_family.moe_config(glm))
    assert leaves["layer_0"]["mlp"]["wi_gate"]["kernel"].shape == (
        2048, 10240)
    assert leaves["layer_1"]["moe"]["experts"]["wo"]["kernel"].shape == (
        64, 1536, 2048)
    assert leaves["layer_1"]["moe"]["bias"].shape == (64,)
    assert leaves["layer_1"]["attention"]["kv_a"]["kernel"].shape == (
        2048, 576)


def test_published_file_equals_its_catalog_row(man):
    """Every number of the catalog row's ``config`` is in the file
    under the same key; only the keys in ``reduced`` differ, each with
    its source value and reason under ``changed``."""
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GLM-4.7-Flash")
    entry = next(c for c in man.data["configs"]
                 if c["name"] == "glm47-flash-1chip")
    cfg = man.config("glm47-flash-1chip")
    assert entry["source"] == row["source_url"] == cfg["source"]
    differs = {k for k, v in row["config"].items() if cfg.get(k, KeyError) != v}
    assert differs == set(cfg["reduced"]) == set(entry["reduced"])
    for key in differs:
        assert cfg["changed"][key]["source"] == row["config"][key]
        assert cfg["changed"][key]["here"] == cfg[key]
        assert cfg["changed"][key]["why"]


def test_costs_of_the_block_by_hand(man):
    cfg = man.config("glm47-flash-1chip")
    assert costs_moe.latent_row_bytes(cfg) == 1152
    # 55 experts of 3 x 2048 x 1536 bf16 weights, 128 rows through them
    flops, nbytes = costs_moe.gmm_layer_call(cfg, 55.0, 128)
    assert flops == 2 * 128 * 3 * 2048 * 1536
    assert nbytes == 55 * 3 * 2048 * 1536 * 2 + 128 * (
        2 * 2048 * 2 + 2 * 1536 * 4 + 1536 * 2 + 2048 * 4)
    # 7,000 blocks of 16 rows: 20 heads x (512 + 64 + 512) a row
    flops, nbytes = costs_moe.latent_attention_call(cfg, 7000, 16, 32)
    assert flops == 2 * 20 * 1088 * 7000 * 16
    assert nbytes == 7000 * 16 * 1152 + 2 * 32 * 20 * 1088
    assert costs_moe.experts_hit_expected(64, 128) == pytest.approx(
        55.48, abs=0.01)


@pytest.mark.parametrize("op_name, want", [
    ("jit(_decode_chunk)/x/layer_1/moe/moe/shared/shared_mlp/mlp/wo/dot",
     "moe/shared"),
    ("jit(_decode_chunk)/x/layer_0/mlp/mlp/wo/dot", "mlp"),
    ("jit(_decode_chunk)/x/attention/attn/q_latent/q_norm/norm/mul",
     "attn/q_latent"),
    ("jit(_decode_chunk)/x/final_norm/norm/mul", "norm"),
    ("jit(_decode_chunk)/x/attention/kv_pool/write/scatter",
     "kv_pool/write"),
    ("jit(_decode_chunk)/x/dynamic_slice", None),
])
def test_scope_of_the_blocks_regions(op_name, want):
    assert scope_table.scope_of(op_name) == want


def _moe_ctx(logs, rec_steps=True, monkeypatch=None):
    from tensorflow_train_distributed_tpu.runtime import events

    ctx = traced_ctx(SCOPED_MOE, logs)
    # The profiler ran for the ring's seconds 100 to 102.
    ctx["tracer"].t0, ctx["tracer"].t1 = 100.0, 102.0
    ctx["peaks"] = PEAKS
    ctx["config"] = manifest_lib.Manifest(REPO).config("glm47-flash-1chip")
    ctx["result"]["counters"].update(slots=32, kv_block_size=16)
    rec = events.Recorder(64)
    monkeypatch.setattr(events, "get_recorder", lambda: rec)
    if rec_steps:
        step(rec, 100.0, 1.0, lanes=32, kv_blocks=7000, experts_hit=56.0,
             expert_load_cv=1.2)
        step(rec, 101.0, 1.0, lanes=32, kv_blocks=7400, experts_hit=54.0,
             expert_load_cv=1.0)
        step(rec, 102.0, 1.0, lanes=0, kv_blocks=0)   # no chunk harvested
        # after the capture, the lanes fuller: the window's, not its
        step(rec, 103.0, 1.0, lanes=32, kv_blocks=9000, experts_hit=58.0,
             expert_load_cv=0.8)
    return ctx


def test_readers_on_a_hand_built_capture(monkeypatch):
    """Two whole chunks of two steps (a third, cut by the capture's
    end, is left out).  Inside them: the grouped matmuls 1.0 s
    and the gating product 0.2 s (``moe/experts`` 1.2), the latent
    kernel 0.6 s in two calls, the shared expert 0.3 s, the query's
    norm 0.1 + 0.1 s, one operation under no scope 0.4 s; the loop's own
    event and the prefill piece's operation stay out."""
    logs = []
    ctx = _moe_ctx(logs, monkeypatch=monkeypatch)
    assert reader("moe_experts_ms.ctx")(ctx) == pytest.approx(1.2 / 4 * 1e3)
    assert reader("latent_attn_ms.ctx")(ctx) == pytest.approx(0.6 / 4 * 1e3)
    assert reader("decode_plumbing_ms.ctx")(ctx) == pytest.approx(
        0.4 / 4 * 1e3)
    assert reader("decode_step_ms.ctx")(ctx) == pytest.approx(1.5 / 2 * 1e3)
    assert reader("device_idle_pct.ctx")(ctx) == pytest.approx(
        100.0 * (0.1 + 0.5 + 0.1 + 0.3) / 4.4)
    assert reader("prefill_piece_ms.ctx")(ctx) == pytest.approx(600.0)
    # a counter of the whole window ...
    assert reader("experts_hit_mean.ctx")(ctx) == pytest.approx(56.0)
    table = next(r for r in logs
                 if r["phase"] == "decode_ms_per_step_by_scope.ctx")
    assert table["by_scope_ms"] == pytest.approx({
        "moe/experts": 300.0, scope_table.LATENT_KERNEL: 150.0,
        "moe/shared": 75.0, "attn/q_latent": 50.0, scopes.PLUMBING: 100.0})
    assert table["kernel_calls"] == {"gmm": 0.5,
                                     scope_table.LATENT_KERNEL: 0.5}
    assert len([r for r in logs if r["phase"] == table["phase"]]) == 1
    # ... but a roofline sets its count beside device times of the
    # capture, so it takes the steps the capture overlapped (55 experts,
    # 7,200 blocks; the window's means are 56 and 7,800).
    cfg = ctx["config"]
    _, nbytes = costs_moe.gmm_layer_call(cfg, 55.0, 128)
    assert reader("moe_gmm_roofline.ctx")(ctx) == pytest.approx(
        100.0 * 7 * nbytes / 819e9 / 0.25)
    _, nbytes = costs_moe.latent_attention_call(cfg, 7200.0, 16, 32)
    assert reader("latent_attn_roofline.ctx")(ctx) == pytest.approx(
        100.0 * nbytes / 819e9 / 0.3)


@pytest.mark.parametrize("name", [n for n in CTX if n not in (
    "decode_step_ms.ctx", "device_idle_pct.ctx", "prefill_piece_ms.ctx")])
def test_new_reader_reads_nothing_from_a_program_without_its_names(
        name, monkeypatch):
    """A parent commit's capture (``small_trace.json``: no scope on any
    operation) and its ring (no ``experts_hit``): nothing, no error."""
    from tensorflow_train_distributed_tpu.runtime import events

    logs = []
    ctx = traced_ctx(cellkit.FIXTURE, logs)
    ctx["peaks"] = PEAKS
    rec = events.Recorder(16)
    monkeypatch.setattr(events, "get_recorder", lambda: rec)
    step(rec, 100.0, 1.0, lanes=4, kv_blocks=12)
    assert reader(name)(ctx) is None
    assert logs == []


def test_a_scoped_capture_without_the_experts_ring_gives_no_roofline(
        monkeypatch):
    logs = []
    ctx = _moe_ctx(logs, rec_steps=False, monkeypatch=monkeypatch)
    assert reader("moe_gmm_roofline.ctx")(ctx) is None
    assert reader("latent_attn_roofline.ctx")(ctx) is None
    assert reader("experts_hit_mean.ctx")(ctx) is None
    assert reader("moe_experts_ms.ctx")(ctx) == pytest.approx(300.0)


def test_new_cells_traffic_and_metrics_are_found_by_name(man):
    cell = man.workload("glm47-flash-1chip.ctx-decode")
    traffic = man.traffic(cell["traffic"])
    assert traffic["kind"] == "serve_family" and traffic["loop"] == "closed"
    assert (traffic["callers"], traffic["pool"]) == (64, 64)
    assert traffic["engine"] == {"prefill_chunk": 1024,
                                 "prefill_budget": 2048}
    assert traffic["ramp_s"] == 12.0
    # ``gap_p95_ms`` spreads 3.5% here against the 2.5% its bound
    # admits (PERF.md section 2), so the cell does not report it.
    assert {m["name"] for m in man.end_to_end_for(cell["name"])} == {
        "serve_tokens_per_s", "setup_s"}
    assert {m["name"] for m in man.per_layer_for(cell["name"])} == set(
        CTX + SHARED)
