"""A decoder whose attention layers differ by a pattern (``laguna``) in
the benchmark: a ``family: "moe_pattern"`` cell added by files and
manifest entries alone runs end to end through
``harness/serve_pattern.py`` at test size on the CPU (and its ``fp8w``
control comes out not correct), the published file equals its catalog
row but for ``reduced``, the builder refuses a wrong head count, window
or rotary rule and a program without the fields, the traffic file holds
the issue's parameters, ``costs_pattern`` by hand, the new scopes, the
new readers on a hand-built capture and where there is nothing to
read."""

import dataclasses
import json
import os

import pytest

import cellkit
from cellkit import CELLS, REPO, run_cell

from benchmark.harness import costs_moe, costs_pattern
from benchmark.harness import manifest as manifest_lib
from benchmark.harness import scope_pattern, scopes, serve, serve_family
from benchmark.harness import serve_pattern
from test_benchmark_spans import step, traced_ctx

SCOPED = os.path.join(REPO, "benchmark", "fixtures",
                      "scoped_trace_pattern.json")
SCOPED_MOE = os.path.join(REPO, "benchmark", "fixtures",
                          "scoped_trace_moe.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "laguna-s21-1chip.mixed-queue"
CONFIG = "laguna-s21-1chip"
MIXED = ("prefill_piece_ms.mixed",
         "attn_full_ms.mixed", "attn_window_ms.mixed",
         "paged_attn_roofline.mixed", "window_rows_share.mixed",
         "moe_experts_ms.mixed", "moe_gmm_roofline.mixed",
         "experts_hit_mean.mixed", "decode_plumbing_ms.mixed")
# Accepted metrics whose reader reads this cell as it stands (a whole
# program's executions, the device's idle share, the engine's ring): the
# cell's name is appended to their lists, no second reader is added.
SHARED = ("host_self_ms.decode", "decode_lanes_mean.decode",
          "decode_step_ms.ctx", "device_idle_pct.ctx")
# A reader of whole programs: it reads a parent's capture too (what it
# reads is in every program).
EVERY_PROGRAM = ("prefill_piece_ms.mixed",)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def man():
    return manifest_lib.Manifest(REPO)


def reader(name):
    return manifest_lib.Manifest(REPO).layer_reader(name)


def _compared(earlier):
    checked = next(r for r in earlier if r.get("phase") == "check")
    return {row["number"]: row for row in checked["compared"]}


def test_a_pattern_cell_added_by_files_alone_runs_and_its_control_fails(
        cell_root, capsys):
    """Experts [2, 4) of 8, half the vocabulary, a window of 8 under
    prompts of 12-88 and outputs of 8-24: every lane decodes past its
    window and all past a ring turn (3 blocks of 4 rows).  float32 on
    both sides, so the limits (``laguna-tiny-closed.traffic.json``) are
    rounding's, and the fp8 weights of the control pass them a
    hundredfold."""
    from tensorflow_train_distributed_tpu.runtime import events

    root = cell_root("laguna-tiny.closed", "laguna-tiny",
                     "laguna-tiny-closed", 1, ["serve_tokens_per_s"])
    seq0 = events.get_recorder().events_after(0)[0]
    rc, sound, earlier = run_cell(root, "laguna-tiny.closed",
                                  seed=2 ** 31 + 5, capsys=capsys)
    assert rc == 0 and sound["correct"] is True, (sound, earlier[-1])
    assert sound["failed"] == 0 and sound["attempted"] > 0
    recorded = events.get_recorder().events_after(seq0)[1]
    for name, _, _, _, _, attrs in recorded:
        assert events.in_contract(name), name
        assert set(attrs or ()) <= events.contract_attrs(name), (name, attrs)
    steps = [e[5] for e in recorded
             if e[0] == "engine/step" and e[5].get("kv_window_blocks")]
    # a window of 8 in blocks of 4 reaches 3 blocks; 4 slots
    assert steps and all(s["kv_window_blocks"] <= 3 * 4 for s in steps)
    assert all(s["experts_held"] == 2 for s in steps
               if "experts_held" in s)
    # 3 window layers' rings of 3 blocks a slot and the scratch block,
    # 2 full layers' 4 x 32 blocks and theirs: x keys and values x 4
    # rows x 32 float32 values
    warm = next(r for r in earlier if r.get("phase") == "warm")
    assert warm["kv_pool_bytes"] == (
        3 * (1 + 4 * 3) + 2 * (1 + 4 * 32)) * 2 * 4 * 32 * 4
    window = next(r for r in earlier if r.get("phase") == "window")
    assert window["compiles_in_window"] == 0
    assert window["engine_stats"]["kv"]["prefix_hits"] == 0
    checked = next(r for r in earlier if r.get("phase") == "check")
    assert checked["reference"].endswith("laguna")
    sound_rows = _compared(earlier)
    rc, control, earlier = run_cell(
        root, "laguna-tiny.closed", seed=2 ** 31 + 5,
        extra=["--control", "fp8w"], capsys=capsys)
    assert rc == 0 and control["correct"] is False
    rows = _compared(earlier)
    assert not rows["served_gap_mean"]["within"]
    assert rows["served_gap_mean"]["value"] > \
        100 * max(sound_rows["served_gap_mean"]["value"], 1e-7)


def test_published_file_equals_its_catalog_row_but_for_the_share(man):
    """Every key of the catalog row's ``config`` is in the file under
    the same key at the published value (the per-layer lists whole, 48
    long); only the three keys in ``reduced`` differ, each with its
    source value, its value here and a reason; no width among them."""
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-S-2.1")
    entry = next(c for c in man.data["configs"] if c["name"] == CONFIG)
    cfg = man.config(CONFIG)
    assert entry["source"] == row["source_url"] == cfg["source"]
    differs = {k for k, v in row["config"].items()
               if cfg.get(k, KeyError) != v}
    assert differs == set(cfg["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for key in differs:
        assert cfg["changed"][key]["source"] == row["config"][key]
        assert cfg["changed"][key]["here"] == cfg[key]
        assert len(cfg["changed"][key]["why"]) > 40
    assert "rank 0 of 4 chips" in cfg["deployment"]
    assert cfg["engine"] == {
        "slots": 32, "chunk": 8, "cache_len": 17408, "kv_block_size": 16,
        "kv_pool_blocks": None, "max_queue": 64}
    assert set(cfg["assumed"]) >= {"router", "gate", "rope_layout",
                                   "residuals"}
    assert (cfg["reference"], cfg["dtype"], cfg["experts_offset"]) == (
        "laguna", "bfloat16", 0)


def test_the_builder_runs_the_file_and_refuses_what_it_would_not_run(man):
    """``pattern_config`` takes the file as it stands: a router of the
    published 256, 64 experts held from 0, [full, window x 3] with 48
    and 72 query heads, half a head under YaRN x 1.485 against a whole
    head at 10,000; and raises "would run" for a published key or a
    layer of the pattern the program would not run as written."""
    cfg_file = man.config(CONFIG)
    cfg = serve_pattern.pattern_config(cfg_file)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_offset,
            cfg.top_k) == (256, 64, 0, 10)
    assert (cfg.num_layers, cfg.dense_layers, cfg.vocab_size) == (
        5, 1, 25088)
    assert [dataclasses.astuple(cfg.attn_kind(i))[:2]
            for i in range(5)] == [(48, None), (72, 512), (72, 512),
                                   (72, 512), (48, None)]
    assert cfg.attn_kind(0).rope_scaling == (
        "yarn", 128.0, 32.0, 1.0, 8192, 1.4852030263919618)
    assert (cfg.attn_kind(0).rotary_share, cfg.attn_kind(1).rotary_share,
            cfg.attn_kind(1).rope_base) == (0.5, 1.0, 10_000.0)
    assert cfg.attn_window == 512 and cfg.attn_gate
    assert serve_family.FAMILIES["moe_pattern"][0] is \
        serve_pattern.pattern_config

    def with_list(key, i, value):
        out = list(cfg_file[key])
        out[i] = value
        return {key: out}

    rope = cfg_file["rope_parameters"]
    for change in (
            {"num_experts": 32}, {"num_key_value_heads": 4},
            {"head_dim": 64}, {"experts_offset": 64},
            {"moe_routed_scaling_factor": 1.0},
            # a wrong head count, a wrong window, a wrong rotary rule
            with_list("num_attention_heads_per_layer", 1, 48),
            with_list("num_attention_heads_per_layer", 44, 72),
            {"sliding_window": 511},
            with_list("layer_types", 2, "full_attention"),
            {"rope_parameters": dict(rope, full_attention=dict(
                rope["full_attention"], partial_rotary_factor=1))},
            {"rope_parameters": dict(rope, full_attention=dict(
                rope["full_attention"], attention_factor=1.0))},
            {"rope_parameters": dict(rope, sliding_attention=dict(
                rope["sliding_attention"], rope_theta=500000))},
            with_list("gating_types", 3, None),
            with_list("mlp_layer_types", 1, "dense"),
            {"changed": dict(cfg_file["changed"], num_experts=dict(
                cfg_file["changed"]["num_experts"], source=128))}):
        with pytest.raises(ValueError, match="would run"):
            serve_pattern.pattern_config(dict(cfg_file, **change))
    with pytest.raises(ValueError, match="the program's block has"):
        serve_pattern.pattern_config(dict(cfg_file, gating="per-token"))
    leaves = serve_family.moe_param_shapes(cfg)
    full, window = (leaves[f"layer_{i}"]["attention"] for i in (4, 1))
    assert full["query"]["kernel"].shape == (3072, 48 * 128)
    assert window["query"]["kernel"].shape == (3072, 72 * 128)
    assert window["key"]["kernel"].shape == (3072, 8 * 128)
    assert (full["gate"]["kernel"].shape, window["gate"]["kernel"].shape) \
        == ((3072, 48), (3072, 72))
    moe = leaves["layer_1"]["moe"]
    assert moe["experts"]["wo"]["kernel"].shape == (64, 1024, 3072)
    assert moe["router"]["kernel"].shape == (3072, 256)
    assert leaves["lm_head"]["kernel"].shape == (3072, 25088)
    assert "moe" not in leaves["layer_0"] and "moe" in leaves["layer_4"]


def test_a_program_without_the_fields_stops_before_any_weight(
        man, monkeypatch):
    """The parent commit's ``MoeConfig`` has no ``attn_period`` and no
    preset of this name: the builder says so with a ``ValueError``
    before a weight is made, so the parent fails the cell at once."""
    from tensorflow_train_distributed_tpu.models import moe

    cfg_file = man.config(CONFIG)

    @dataclasses.dataclass(frozen=True)
    class ParentConfig:
        vocab_size: int = 0
        num_layers: int = 0
        experts_held: int = 0
        experts_offset: int = 0

    monkeypatch.setattr(moe, "MoeConfig", ParentConfig)
    made = []
    monkeypatch.setattr(serve_family.weights, "make_params",
                        lambda *a, **k: made.append(a))
    with pytest.raises(ValueError, match="no MoeConfig field attn_gate, "
                                         "attn_period, head_dim"):
        serve_pattern.pattern_config(cfg_file)
    monkeypatch.setattr(moe, "MOE_PRESETS", {})
    with pytest.raises(ValueError, match="no preset 'laguna_s21'"):
        serve_family.FAMILIES["moe_pattern"][0](cfg_file)
    assert made == []


def test_the_cells_engine_holds_its_window_layers_in_bounded_rings(man):
    """The engine of the cell as the configuration and traffic files
    give it, on parameters that are shapes alone: a ring of ``ceil(512
    / 16) + 1`` = 33 blocks a slot in each of the three window layers,
    0.21 GB where the lanes held whole would take 6.84 GB, beside the
    full layers' 4.56 GB; and the run is the family runner's, with no
    name lent."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from tensorflow_train_distributed_tpu.models import moe
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    assert serve_pattern.run is serve_family.run
    cfg_file = man.config(CONFIG)
    cfg = serve_pattern.pattern_config(cfg_file)
    shapes = nn.meta.unbox(jax.eval_shape(
        lambda: moe.MoeLmModel(cfg).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))))["params"]
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), shapes)
    sized = {k: v for k, v in cfg_file["engine"].items()
             if k in ("slots", "chunk", "cache_len", "kv_block_size")}
    eng = ServingEngine(cfg, params, cast_params=False, **sized,
                        **man.traffic("mixed-queue")["engine"])
    assert (eng._window, eng._ring_blocks) == (512, 33)
    row = 2 * 8 * 128 * 2                   # keys and values, bf16
    assert eng._kv_ring_bytes == 3 * (1 + 32 * 33) * 16 * row
    assert eng._kv_ring_bytes < 0.75e9
    whole = 2 * (1 + 32 * (17408 // 16)) * 16 * row
    assert eng.kv_pool_bytes() == whole + eng._kv_ring_bytes
    assert 3 * 32 * 17408 * row > 30 * eng._kv_ring_bytes


def test_costs_of_two_kinds_of_cache_by_hand(man):
    cfg = man.config(CONFIG)
    assert costs_pattern.kv_row_bytes(cfg) == 4096
    assert costs_pattern.layers_by_kind(cfg) == {
        "full": [48, 48], "window": [72, 72, 72]}
    # 32 lanes whose full layers' walks reach 9,000 blocks between them
    # and whose windows reach 1,100: two layers read 9,000 blocks of 16
    # rows of 4,096 B, three read 1,100; a row meets 48 or 72 heads of
    # 128 (QK^T and PV); queries in and outputs out once a layer.
    flops, nbytes = costs_pattern.paged_attention_step(cfg, 9000, 1100,
                                                       16, 32)
    assert flops == 4 * 128 * 16 * (2 * 48 * 9000 + 3 * 72 * 1100)
    assert nbytes == 16 * 4096 * (2 * 9000 + 3 * 1100) + 2 * 2 * 32 * 128 * (
        2 * 48 + 3 * 72)
    # 40 of the 64 held experts hit by 75 of a step's 320 pairs: their
    # kernels of 3 x 3072 x 1024 bf16 once, 75 rows in and out.
    flops, nbytes = costs_moe.gmm_layer_call(cfg, 40.0, 75)
    assert flops == 2 * 75 * 3 * 3072 * 1024
    assert nbytes == 40 * 3 * 3072 * 1024 * 2 + 75 * (
        2 * 3072 * 2 + 2 * 1024 * 4 + 1024 * 2 + 3072 * 4)


_D = "jit(_decode_chunk)/w/layer_1/layer_1._mha/"


@pytest.mark.parametrize("op_name, want", [
    (_D + "attn/window/attention/attention._paged_decode_step/pallas_call",
     "attn/window"),
    (_D + "attn/full/attention/attention._paged_decode_step/pallas_call",
     "attn/full"),
    # a layer's projections and its out product are its kind's
    (_D + "attn/window/attention/attn/qkv/query/dot_general", "attn/window"),
    (_D + "attn/full/attention/attn/out/out/dot_general", "attn/full"),
    # the two kinds of write and the gate are rows of their own
    (_D + "attn/window/attention/kv_pool/write/window/scatter",
     "kv_pool/write/window"),
    (_D + "attn/full/attention/kv_pool/write/scatter", "kv_pool/write"),
    (_D + "attn/window/attention/attn/gate/mul", "attn/gate"),
    ("jit(_decode_chunk)/w/layer_2/moe/moe/experts/experts/pallas_call",
     "moe/experts"),
    ("jit(_decode_chunk)/w/layer_0/mlp/wo/dot_general", "mlp"),
    ("jit(_decode_chunk)/w/dynamic_slice", None),
])
def test_scope_of_the_patterns_regions(op_name, want):
    assert scope_pattern.scope_of(op_name) == want


def _pattern_ctx(logs, monkeypatch, rec_steps=True, path=SCOPED):
    from tensorflow_train_distributed_tpu.runtime import events

    ctx = traced_ctx(path, logs)
    # The profiler ran for the ring's seconds 100 to 102.
    ctx["tracer"].t0, ctx["tracer"].t1 = 100.0, 102.0
    ctx["peaks"] = PEAKS
    ctx["config"] = manifest_lib.Manifest(REPO).config(CONFIG)
    ctx["result"]["counters"].update(slots=32, kv_block_size=16)
    rec = events.Recorder(64)
    monkeypatch.setattr(events, "get_recorder", lambda: rec)
    if rec_steps:
        held = dict(experts_held=64, expert_load_cv=1.1)
        step(rec, 100.0, 1.0, lanes=26, experts_hit=40.0, routed_here=0.25,
             kv_blocks=8000, kv_window_blocks=1000, **held)
        step(rec, 101.0, 1.0, lanes=28, experts_hit=44.0, routed_here=0.23,
             kv_blocks=10000, kv_window_blocks=1100, **held)
        step(rec, 102.0, 1.0, lanes=0)              # no chunk harvested
        # after the capture, the lanes fuller: the window's, not its
        step(rec, 103.0, 1.0, lanes=30, experts_hit=48.0, routed_here=0.27,
             kv_blocks=12000, kv_window_blocks=1150, **held)
    return ctx


def test_readers_on_a_hand_built_capture(monkeypatch):
    """Two whole chunks of two steps (a third, cut by the capture's
    end, is left out) and one whole piece.  In the chunks: the kernel
    0.6 s in the full layers and 0.3 s in the window layers, whose
    projections take 0.2 s more; the ring's write 0.1 s, the blocks'
    write 0.2 s, the gate 0.1 s, the grouped matmuls 1.0 s and the
    gating product 0.2 s, one operation under no scope 0.2 s.  In the
    piece: a full layer's walk 0.3 s, a window layer's 0.2 s, the gate
    0.1 s, experts 0.3 s, the head 0.1 s."""
    logs = []
    ctx = _pattern_ctx(logs, monkeypatch)
    assert reader("decode_step_ms.ctx")(ctx) == pytest.approx(
        1.5 / 2 * 1e3)
    assert reader("prefill_piece_ms.mixed")(ctx) == pytest.approx(1000.0)
    assert reader("attn_full_ms.mixed")(ctx) == pytest.approx(150.0)
    assert reader("attn_window_ms.mixed")(ctx) == pytest.approx(125.0)
    assert reader("moe_experts_ms.mixed")(ctx) == pytest.approx(300.0)
    assert reader("decode_plumbing_ms.mixed")(ctx) == pytest.approx(50.0)
    # busy: -0.2..-0.1, 0..2, 2.4..3.4, 3.6..4.5, 4.9..5 of -0.2..5
    assert reader("device_idle_pct.ctx")(ctx) == pytest.approx(
        100.0 * 1.1 / 5.2)
    # a counter of the whole window ...
    assert reader("experts_hit_mean.mixed")(ctx) == pytest.approx(44.0)
    # ... the captured steps' walks, by the kernel's own rule
    assert reader("window_rows_share.mixed")(ctx) == pytest.approx(
        100.0 * 2100 / 18000)
    tables = {r["program"]: r for r in logs
              if r["phase"] == "ms_by_scope.mixed"}
    assert tables["_decode_chunk"]["ms"] == pytest.approx({
        "moe/experts": 300.0, "attn/full": 150.0, "attn/window": 125.0,
        "kv_pool/write": 50.0, scopes.PLUMBING: 50.0,
        "kv_pool/write/window": 25.0, "attn/gate": 25.0})
    assert tables["_decode_chunk"]["kernel_ms"] == pytest.approx({
        "paged_attn/full": 150.0, "paged_attn/window": 75.0, "gmm": 250.0})
    assert tables["_decode_chunk"]["kernel_calls"] == {
        "paged_attn/full": 0.5, "paged_attn/window": 0.5, "gmm": 0.5}
    assert tables["_prefill_piece"]["ms"] == pytest.approx({
        "attn/full": 300.0, "moe/experts": 300.0, "attn/window": 200.0,
        "attn/gate": 100.0, "head": 100.0})
    assert len(tables) == len(
        [r for r in logs if r["phase"] == "ms_by_scope.mixed"]) == 2
    # A roofline sets its count beside device times of the capture, so
    # it takes the steps the capture overlapped: 9,000 blocks in the
    # full layers and 1,050 in the window layers against 0.225 s of the
    # kernel a step; 42 experts hit by 320 pairs x 0.24, four expert
    # layers in 0.25 s of ``gmm`` a step.
    cfg = ctx["config"]
    _, nbytes = costs_pattern.paged_attention_step(cfg, 9000.0, 1050.0,
                                                   16, 32)
    assert reader("paged_attn_roofline.mixed")(ctx) == pytest.approx(
        100.0 * nbytes / 819e9 / 0.225)
    _, nbytes = costs_moe.gmm_layer_call(cfg, 42.0, 320 * 0.24)
    assert reader("moe_gmm_roofline.mixed")(ctx) == pytest.approx(
        100.0 * 4 * nbytes / 819e9 / 0.25)


@pytest.mark.parametrize("name", [n for n in MIXED
                                  if n not in EVERY_PROGRAM])
def test_new_reader_reads_nothing_from_a_program_without_its_names(
        name, monkeypatch):
    """A parent commit's captures (``small_trace.json``: no scope on
    any operation; ``scoped_trace_moe.json``: a block of one kind of
    layer) and its ring (no ``kv_window_blocks``): nothing, no error,
    no line in the log."""
    from tensorflow_train_distributed_tpu.runtime import events

    for path in (cellkit.FIXTURE, SCOPED_MOE):
        logs = []
        ctx = traced_ctx(path, logs)
        ctx["tracer"].t0, ctx["tracer"].t1 = 100.0, 102.0
        ctx["peaks"] = PEAKS
        ctx["config"] = manifest_lib.Manifest(REPO).config(CONFIG)
        ctx["result"]["counters"].update(slots=32, kv_block_size=16)
        rec = events.Recorder(16)
        monkeypatch.setattr(events, "get_recorder", lambda rec=rec: rec)
        step(rec, 100.0, 1.0, lanes=4, kv_blocks=12, experts_hit=50.0,
             expert_load_cv=1.0)
        assert reader(name)(ctx) is None
        assert logs == []


def test_a_capture_of_the_pattern_without_its_ring_gives_no_share(
        monkeypatch):
    logs = []
    ctx = _pattern_ctx(logs, monkeypatch, rec_steps=False)
    for name in ("paged_attn_roofline.mixed", "moe_gmm_roofline.mixed",
                 "experts_hit_mean.mixed", "window_rows_share.mixed"):
        assert reader(name)(ctx) is None
    assert reader("attn_window_ms.mixed")(ctx) == pytest.approx(125.0)


def test_the_earlier_share_cell_reads_as_before_a_later_cell_was_appended(
        man):
    """``test_benchmark_deepseek_v32.py``'s look at its own cell holds
    every assertion on the manifest with this cell's name taken off the
    lists it was appended to: appending changed nothing that was there
    (that test asserts its cell is the LAST of two lists, so it cannot
    pass as written beside a later cell: ``tests/conftest.py``)."""
    import copy

    import test_benchmark_deepseek_v32 as earlier

    before = copy.copy(man)
    before.data = copy.deepcopy(man.data)
    taken = 0
    for section in ("end_to_end", "per_layer"):
        for m in before.data[section]:
            if CELL in m.get("workloads", []) and m["workloads"] != [CELL]:
                m["workloads"].remove(CELL)
                taken += 1
    assert taken == 1 + len(SHARED)
    earlier.test_new_cells_traffic_and_metrics_are_found_by_name(before)


def test_new_cells_traffic_and_metrics_are_found_by_name(man):
    cell = man.workload(CELL)
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert "1/4" in cell["why"] and "512-16384" in cell["why"]
    traffic = man.traffic(cell["traffic"])
    # The issue's parameters, to the letter.
    assert traffic["kind"] == "serve_pattern" and traffic["loop"] == "closed"
    assert "order" not in traffic
    assert (traffic["callers"], traffic["pool"], traffic["mix_seed"]) == (
        64, 64, 20260929)
    assert traffic["prompt_len"] == {
        "dist": "lognormal", "median": 4096, "sigma": 0.9, "min": 512,
        "max": 16384}
    assert traffic["output_len"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.6, "min": 32,
        "max": 1024}
    assert (traffic["ramp_s"], traffic["drain_s"], traffic["trace_s"]) == (
        15.0, 0.0, 2.0)
    assert traffic["engine"] == {"prefill_chunk": 1024,
                                 "prefill_budget": 4096}
    assert traffic["check"]["sample"] in (4, 8)
    limits = traffic["check"]["limits"]
    assert 0.0 < limits["served_gap_mean"] < limits["served_gap_max"]
    # the longest request the mix can draw fills the engine's cache
    assert 16384 + 1024 == man.config(CONFIG)["engine"]["cache_len"]
    assert {m["name"] for m in man.end_to_end_for(cell["name"])} == {
        "serve_tokens_per_s", "setup_s"}
    ours = {m["name"] for m in man.per_layer_for(cell["name"])}
    assert ours >= set(MIXED) | set(SHARED) | {"compile_s"}
    for m in man.data["per_layer"]:
        if m["name"] in MIXED:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
        if m["name"] in SHARED:
            assert CELL in m["workloads"]
    with open(os.path.join(CELLS, "laguna-tiny.json")) as f:
        tiny = json.load(f)
    assert serve_pattern.pattern_config(tiny).attn_window == 8
