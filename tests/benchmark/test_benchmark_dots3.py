"""A decoder whose latent attention layers are of two kinds
(``dots3_note``) in the benchmark: a ``family: "moe_latents"`` cell added
by files and manifest entries alone runs end to end through
``harness/serve_latents.py`` at test size on the CPU (and its ``fp8w``
control comes out not correct), the published file equals its catalog
row but for ``reduced``, the builder refuses a wrong kind of layer, a
wrong width and a program without the fields, the traffic file holds
the issue's parameters, ``costs_latents`` by hand, the new readers on a
hand-built capture and where there is nothing to read, the manifest's
new entries found BY NAME, and the two tests that pin the manifest's
tail run whole on the manifest as it was."""

import dataclasses
import json
import os

import pytest

from cellkit import REPO, run_cell

from benchmark.harness import (costs_flash_latent, costs_latents, costs_moe,
                               costs_share)
from benchmark.harness import manifest as manifest_lib
from benchmark.harness import scope_latents, scopes, serve, serve_family
from benchmark.harness import serve_latents, weights

import test_benchmark_mimo as mimo
from test_benchmark_mimo import rec  # noqa: F401  (the ring's fixture)

SCOPED = os.path.join(REPO, "benchmark", "fixtures",
                      "scoped_trace_latents.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "dots3-note-1chip.transcript-notes"
CONFIG = "dots3-note-1chip"
NOTES = ("prefill_piece_ms.notes", "attn_latent_ms.notes",
         "attn_latent_window_ms.notes", "latent_window_roofline.notes",
         "latent_attn_roofline.notes", "prefix_flash_roofline.notes")
# Accepted metrics whose reader is cell-agnostic: the cell's name is
# appended to their lists, no second reader is added.  ``READ_HERE``
# are the eight whose reading depends on what THIS program records (its
# scopes, kernels and counters), each held to a value below on this
# family's own capture.
READ_HERE = ("decode_step_ms.ctx", "device_idle_pct.ctx",
             "window_rows_share.mixed", "rows_selected_share.longctx",
             "experts_hit_mean.longctx", "sparse_select_step_ms.longctx",
             "moe_gmm_roofline.longctx",
             "paged_index_scores_roofline.longctx")
SHARED = ("host_self_ms.decode", "decode_lanes_mean.decode",
          "device_starved_pct.serve", "driver_away_ms.serve",
          "step_unnamed_ms.serve", "idle_unowned_pct.serve",
          "prefill_pieces_per_call.serve") + READ_HERE
TRAFFIC = {"engine": {"prefill_chunk": 1024}}
ATTRS = {
    ("engine/step", 0.0): dict(
        lanes=30, kv_blocks=12000, kv_window_blocks=1000,
        kv_bytes=12000 * 73728, experts_hit=9.0, routed_here=0.0625,
        experts_held=16, rows_scored=180000.0, rows_selected=60000.0),
    ("engine/step", 2.3): dict(
        lanes=32, kv_blocks=14000, kv_window_blocks=1088,
        kv_bytes=14000 * 73728, experts_hit=11.0, routed_here=0.0625,
        experts_held=16, rows_scored=220000.0, rows_selected=65536.0,
        pieces=1, piece_calls=1),
    ("prefill/dispatch", 2.31): dict(rid=7, piece=3, pieces=1, tokens=1024,
                                     rows=4096, draft=0),
}


@pytest.fixture(scope="module")
def man():
    return manifest_lib.Manifest(REPO)


def notes_ctx(logs, rec, man, path=SCOPED, attrs=ATTRS):
    """``mimo.sink_ctx`` over this family's capture and configuration."""
    return dict(mimo.sink_ctx(logs, rec, path=path, attrs=attrs),
                config=man.config(CONFIG), traffic=TRAFFIC)


def _compared(earlier):
    checked = next(r for r in earlier if r.get("phase") == "check")
    return {row["number"]: row for row in checked["compared"]}


def test_a_latents_cell_added_by_files_alone_runs_and_its_control_fails(
        cell_root, capsys):
    """Experts [2, 4) of 8, half the vocabulary, two full layers that
    choose 16 rows and three window layers (9) with rows of their own
    under prompts of 12-88 and outputs of 8-24 on four slots (every
    slot reused, every ring turned over).  float32 on both sides, so
    the limits (``dots3-tiny-closed.traffic.json``) are rounding's, and
    the fp8 weights of the control pass them a hundredfold."""
    from tensorflow_train_distributed_tpu.runtime import events

    root = cell_root("dots3-tiny.closed", "dots3-tiny", "dots3-tiny-closed",
                     1, ["serve_tokens_per_s"])
    seq0 = events.get_recorder().events_after(0)[0]
    rc, sound, earlier = run_cell(root, "dots3-tiny.closed",
                                  seed=2 ** 31 + 5, capsys=capsys)
    assert rc == 0 and sound["correct"] is True, (sound, earlier[-1])
    assert sound["failed"] == 0 and sound["attempted"] > 0
    recorded = events.get_recorder().events_after(seq0)[1]
    for name, _, _, _, _, attrs in recorded:
        assert events.in_contract(name), name
        assert set(attrs or ()) <= events.contract_attrs(name), (name, attrs)
    steps = [e[5] for e in recorded
             if e[0] == "engine/step" and e[5].get("lanes")]
    # a full layer's block: 4 rows of a latent row stored 128 wide and
    # an index key of 16, float32, in two layers; a window of 9 reaches
    # 4 blocks of 4 at most in each of the four slots (an idle slot
    # counts one)
    assert steps and all(
        s["kv_bytes"] == s["kv_blocks"] * 2 * 4 * (128 + 16) * 4
        for s in steps)
    assert all(0 < s["kv_window_blocks"] <= 4 * 4 for s in steps)
    assert any(s["kv_window_blocks"] < s["kv_blocks"] for s in steps)
    assert any(s.get("rows_selected", 0) < s.get("rows_scored", 0)
               for s in steps)
    assert all(s["experts_held"] == 2 for s in steps
               if "experts_held" in s)
    assert {e[5]["pool"] for e in recorded
            if e[0] == "kv/alloc"} == {"full", "window"}
    # pool bytes by kind, beside the warm line's total: two full layers'
    # 4 x 32 blocks and the scratch block at 128 + 16 values a row;
    # three window layers' 4 rings of 4 blocks and the scratch block at
    # 256
    parts = {"latent_pool_bytes": 2 * (1 + 4 * 32) * 4 * 128 * 4,
             "index_pool_bytes": 2 * (1 + 4 * 32) * 4 * 16 * 4,
             "latent_ring_bytes": 3 * (1 + 4 * 4) * 4 * 256 * 4}
    pools = next(r for r in earlier if r.get("phase") == "pools")
    warm = next(r for r in earlier if r.get("phase") == "warm")
    assert pools == dict(parts, phase="pools",
                         kv_pool_bytes=sum(parts.values()))
    assert warm["kv_pool_bytes"] == sum(parts.values())
    window = next(r for r in earlier if r.get("phase") == "window")
    assert window["compiles_in_window"] == 0
    assert window["engine_stats"]["kv"]["prefix_hits"] == 0
    checked = next(r for r in earlier if r.get("phase") == "check")
    assert checked["reference"].endswith("dots3_note")
    sound_rows = _compared(earlier)
    rc, control, earlier = run_cell(
        root, "dots3-tiny.closed", seed=2 ** 31 + 5,
        extra=["--control", "fp8w"], capsys=capsys)
    assert rc == 0 and control["correct"] is False
    rows = _compared(earlier)
    assert not rows["served_gap_mean"]["within"]
    assert rows["served_gap_mean"]["value"] > \
        100 * max(sound_rows["served_gap_mean"]["value"], 1e-7)
    # the run is the share runner's (the pool in one order), and the
    # names lent for the run are given back
    from benchmark.harness import loadgen

    assert loadgen.Schedule.__module__ == "benchmark.harness.loadgen"
    assert serve.build.__module__ == "benchmark.harness.serve"
    assert weights.make_params.__module__ == "benchmark.harness.weights"


def test_the_kernels_that_read_a_rescaled_latent_are_filled_for_it():
    """``seeded_latents`` refills ``q_b``, ``index_q`` and ``kv_b`` of
    every attention layer at std ``1 / sqrt(d_model)`` (their input's
    variance is ``d_model / rows``, not 1) and hands every other leaf on
    as it is; queries and keys made from the rescaled latents then have
    the spread the same kernels give an unscaled latent under
    ``weights._fill``'s own rule."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflow_train_distributed_tpu.models import moe

    cfg = moe.MOE_PRESETS["dots3_note_tiny"]
    plain = weights.make_params(serve_family.moe_param_shapes(cfg), 7,
                                jnp.float32)
    seeded = serve_latents.seeded_latents(plain)
    changed = set()
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(plain)[0],
            jax.tree_util.tree_flatten_with_path(seeded)[0]):
        names = tuple(getattr(k, "key", "") for k in path)
        if names[-2] in serve_latents.RESCALED_INPUT:
            assert names[-3] == "attention" and names[-1] == "kernel"
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a) * (a.shape[0] / 64) ** 0.5,
                rtol=1e-6)
            changed.add(names[-2])
        else:
            assert b is a
    assert changed == set(serve_latents.RESCALED_INPUT)
    # layer 2 is a window layer: kv rank 136 of a hidden size of 64
    kv_b = np.asarray(seeded["layer_2"]["attention"]["kv_b"]["kernel"])
    assert kv_b.shape[0] == 136 and abs(kv_b.std() * 8 - 1) < 0.05


def test_the_published_file_is_its_catalog_row_but_for_reduced(man):
    """Every key of the catalog row's ``config`` is in the file under
    the same name with the same value, but for the three in ``reduced``
    (counts, no width), each with source, here and why."""
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "dots3-note-prev")
    cfg = man.config(CONFIG)
    entry = man._by_name("configs", CONFIG)
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["changed"][key]["source"] == value
            assert cfg["changed"][key]["here"] == cfg[key] != value
            assert cfg["changed"][key]["why"]
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (9, 16, 152064 // 8)
    # every published width, unchanged
    assert [cfg[k] for k in (
        "hidden_size", "num_attention_heads", "swa_num_attention_heads",
        "q_lora_rank", "kv_lora_rank", "swa_q_lora_rank",
        "swa_kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "v_head_dim",
        "swa_v_head_dim", "sliding_window_size", "moe_intermediate_size",
        "num_experts_per_tok", "index_n_heads", "index_head_dim",
        "index_topk")] == [5120, 128, 64, 1024, 512, 1024, 1024, 128, 64,
                           192, 64, 128, 128, 513, 1536, 8, 64, 128, 2048]
    assert len(cfg["layer_types"]) == 46
    assert cfg["layer_types"][:9] == (
        ["full_attention"] + ["full_attention"] + ["sliding_attention"] * 3
        + ["full_attention"] + ["sliding_attention"] * 3)
    assert set(cfg["assumed"]) >= {
        "apply_mla_qkv_lora_rescale", "sliding_window_size",
        "attention_gate_type", "indexer", "rope_layout", "weights",
        "not_run"}
    assert "16 chips" in cfg["deployment"]


def test_the_configuration_file_is_what_the_program_runs(man):
    """Every published key and every layer's kind: the file through the
    family's builder is the preset cut to the chip's share, and all 46
    entries of ``layer_types`` are the kinds the program would run."""
    cfg_file = man.config(CONFIG)
    cfg = serve_latents.latents_config(cfg_file)
    assert (cfg.num_layers, cfg.experts_held, cfg.num_experts,
            cfg.vocab_size, cfg.d_model) == (9, 16, 256, 19008, 5120)
    for i, name in enumerate(cfg_file["layer_types"]):
        sizes = cfg.latent_sizes(i)
        assert (sizes.window == 513) == (name == "sliding_attention")
        assert dataclasses.asdict(sizes) == serve_latents.kind_of(
            cfg_file, i)
    assert cfg_file["engine"] == {
        "slots": 32, "chunk": 8, "cache_len": 16384, "kv_block_size": 16,
        "kv_pool_blocks": None, "max_queue": 64}


def test_the_builder_refuses_what_the_program_would_not_run(man):
    good = man.config(CONFIG)

    def bent(**over):
        return dict(good, **over)

    kinds = list(good["layer_types"])
    kinds[2] = "full_attention"
    with pytest.raises(ValueError, match="layer 2"):
        serve_latents.latents_config(bent(layer_types=kinds))
    kinds[2] = "linear_attention"
    with pytest.raises(ValueError, match="layer_types"):
        serve_latents.latents_config(bent(layer_types=kinds))
    with pytest.raises(ValueError, match="layer 2"):
        serve_latents.latents_config(bent(swa_kv_lora_rank=512))
    with pytest.raises(ValueError, match="layer 2"):
        serve_latents.latents_config(bent(sliding_window_size=512))
    with pytest.raises(ValueError, match="kv_lora_rank"):
        serve_latents.latents_config(bent(kv_lora_rank=1024))
    with pytest.raises(ValueError, match="apply_mla_qkv_lora_rescale"):
        serve_latents.latents_config(bent(apply_mla_qkv_lora_rescale=False))
    with pytest.raises(ValueError, match="swa_attention_gate_type"):
        serve_latents.latents_config(bent(swa_attention_gate_type=None))
    with pytest.raises(ValueError, match="rope_scaling"):
        serve_latents.latents_config(bent(rope_scaling={"type": "yarn"}))
    # a program from before this family says so and stops
    with pytest.raises(ValueError, match="cannot run the configuration"):
        serve_latents.latents_config(bent(program=dict(
            good["program"], preset="dots3_note_from_before")))
    with pytest.raises(ValueError, match="no MoeConfig field a_field"):
        serve_latents.latents_config(bent(program=dict(
            good["program"], replace=dict(good["program"]["replace"],
                                          a_field=1))))


def test_the_traffic_file_holds_the_issues_parameters(man):
    cell = man.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "transcript-notes", 1)
    t = man.traffic("transcript-notes")
    assert (t["kind"], t["loop"], t["callers"], t["pool"], t["order"]) == (
        "serve_latents", "closed", 64, 96, "pool")
    assert t["prompt_len"] == {"dist": "lognormal", "median": 6144,
                               "sigma": 0.6, "min": 1024, "max": 14336}
    assert t["output_len"] == {"dist": "lognormal", "median": 512,
                               "sigma": 0.5, "min": 128, "max": 1536}
    assert t["engine"] == {"prefill_chunk": 1024, "prefill_budget": 2048}
    assert (t["ramp_s"], t["trace_s"], t["check"]["sample"]) == (15.0, 2.0,
                                                                 4)
    # the longest pair fits the cache
    assert 14336 + 1536 <= man.config(CONFIG)["engine"]["cache_len"]
    assert set(t["check"]["limits"]) == {"served_gap_max",
                                         "served_gap_mean"}


def test_new_entries_are_found_by_name(man):
    """The cell, its configuration and its six readers, each BY NAME;
    the fifteen accepted cell-agnostic metrics list the cell."""
    by_name = {m["name"]: m for m in man.data["per_layer"]}
    for name in NOTES:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == \
            "serve_tokens_per_s"
        assert callable(man.layer_reader(name))
    for name in SHARED:
        assert CELL in by_name[name]["workloads"]
    read = {m["name"] for m in man.per_layer_for(CELL)}
    assert read == set(NOTES) | set(SHARED) | {"compile_s"}
    assert {m["name"] for m in man.end_to_end_for(CELL)} == {
        "serve_tokens_per_s", "setup_s"}
    layers = {m["layer"] for m in man.data["per_layer"]
              if m["name"] not in NOTES}
    assert {by_name[n]["layer"] for n in NOTES} <= layers
    # no accepted cell reads a reader of this cell
    for w in man.data["workloads"]:
        if w["name"] != CELL:
            assert not set(NOTES) & {
                m["name"] for m in man.per_layer_for(w["name"])}


def test_costs_of_the_rings_walk_by_hand(man):
    cfg = man.config(CONFIG)
    assert costs_latents.window_layers(cfg) == 6
    assert costs_latents.window_row_bytes(cfg) == 2176
    flops, nbytes = costs_latents.latent_window_step(cfg, 1088.0, 16, 32)
    rows = 1088 * 16
    assert flops == 6 * 2.0 * 64 * (2 * 1024 + 64) * rows
    assert nbytes == 6 * (rows * 2176 + 2.0 * 32 * 64 * (2 * 1024 + 64))
    # memory-bound on the chip: the bytes' time is the floor
    assert nbytes / mimo.PEAKS["hbm_bytes_per_s"] > \
        flops / mimo.PEAKS["bf16_flops_per_s"]


def test_a_decode_step_by_kind_of_layer_and_by_kernel(rec, man):
    """Two whole chunks of two steps: the window kind's operations (the
    kernel over the ring, the ring's write, a projection, the out
    projection) 0.8 s, the full kind's (index scores, the choice, the
    kernel over the chosen rows, the pool's write) 0.8, the grouped
    matmuls 0.8; the dense layer and two copies under no scope are rows
    of their own; the loop's event, the cut chunk's copy and the
    insert's stay out."""
    logs = []
    ctx = notes_ctx(logs, rec, man)
    assert mimo.reader("attn_latent_ms.notes")(ctx) == pytest.approx(200.0)
    assert mimo.reader("attn_latent_window_ms.notes")(ctx) == \
        pytest.approx(200.0)
    assert mimo.reader("sparse_select_step_ms.longctx")(ctx) == \
        pytest.approx(100.0)
    assert mimo.reader("decode_step_ms.ctx")(ctx) == pytest.approx(750.0)
    assert mimo.reader("prefill_piece_ms.notes")(ctx) == \
        pytest.approx(1000.0)
    table = next(r for r in logs if r.get("phase") == "ms_by_scope.notes"
                 and r["program"] == "_decode_chunk")
    assert (table["executions"], table["n"]) == (2, 4)
    assert table["ms"] == pytest.approx({
        "moe/experts": 200.0, scope_latents.WINDOW_KERNEL: 100.0,
        "attn/select": 100.0, scopes.PLUMBING: 100.0,
        "kv_pool/write/window": 50.0, "attn/sparse": 50.0, "mlp": 50.0,
        "kv_pool/write": 25.0, "attn/index_score": 25.0,
        "attn/q_latent": 25.0, "attn/out": 25.0})
    assert table["kernel_ms"] == pytest.approx({
        scope_latents.WINDOW_KERNEL: 100.0, "paged_latent_attention": 50.0,
        "paged_index_scores": 25.0, "gmm": 200.0})
    piece = next(r for r in logs if r.get("phase") == "ms_by_scope.notes"
                 and r["program"] == "_prefill_piece")
    assert piece["kind_ms"] == pytest.approx(
        {"attn/latent": 400.0, "attn/latent_window": 300.0})


def test_the_kernels_rooflines_and_the_shares_by_hand(rec, man):
    """The kernel over the rings: the captured steps' 1,044 window
    blocks a layer at 2,176 B a row in six layers, of 100 ms a step.
    The kernel over the chosen rows: the captured steps' 62,768 rows a
    call at 1,152 B, of 100 ms a call (two calls in four steps).  The
    piece's kernel: what one call of 1,024 queries at 3,073 rows or
    later requires in the THREE layers that run it, of its 0.4 s."""
    logs = []
    ctx = notes_ctx(logs, rec, man)
    cfg = ctx["config"]
    _, nbytes = costs_latents.latent_window_step(cfg, 1044.0, 16, 32)
    got = mimo.reader("latent_window_roofline.notes")(ctx)
    assert got == pytest.approx(100 * nbytes / 819e9 / 0.100)
    assert 0 < got < 100
    _, nbytes = costs_moe.latent_attention_call(cfg, 62768.0 / 16, 16, 32)
    assert nbytes == 62768 * 1152 + 2.0 * 32 * 128 * (2 * 512 + 64)
    got = mimo.reader("latent_attn_roofline.notes")(ctx)
    assert got == pytest.approx(100 * nbytes / 819e9 / 0.100)
    assert 0 < got < 100
    assert costs_latents.full_layers(cfg) == 3
    flops = costs_flash_latent.call_flops(
        dict(cfg, num_hidden_layers=3), 4096, 1024, 1024)
    got = mimo.reader("prefix_flash_roofline.notes")(ctx)
    assert got == pytest.approx(
        100 * sum(flops.values()) / mimo.PEAKS["bf16_flops_per_s"] / 0.4)
    assert 0 < got < 100
    (line,) = [r for r in logs
               if r.get("phase") == "prefix_flash_roofline.longctx"]
    assert (line["pieces"], line["calls_per_piece"]) == (1, 1.0)
    # the accepted reader counts every layer of the file: three times
    # this kernel's work here
    assert mimo.reader("prefix_flash_roofline.longctx")(ctx) == \
        pytest.approx(3 * got)


@pytest.mark.parametrize("name", READ_HERE)
def test_an_accepted_reader_reads_this_programs_capture(name, rec, man):
    """The eight accepted readers the cell is appended to, on this
    family's capture and counts.  The grouped matmuls: 10 experts'
    kernels and 16 rows in eight layers, of 200 ms a step.  The index
    scores: the captured steps' 200,000 keys a call at 256 B, of 100 ms
    a call (one call in four steps)."""
    ctx = notes_ctx([], rec, man)
    cfg = ctx["config"]
    got = mimo.reader(name)(ctx)
    if name == "moe_gmm_roofline.longctx":
        _, nbytes = costs_share.held_gmm_layer_call(cfg, 10.0, 0.0625 * 256)
        want = 100 * 8 * nbytes / 819e9 / 0.200
    elif name == "paged_index_scores_roofline.longctx":
        _, nbytes = costs_share.index_scores_call(cfg, 200000.0, 32)
        want = 100 * nbytes / 819e9 / 0.100
    else:
        want = {"decode_step_ms.ctx": 750.0,
                "window_rows_share.mixed": 100 * 2088 / 26000,
                "rows_selected_share.longctx": 100 * 125536 / 400000,
                "experts_hit_mean.longctx": 10.0,
                "sparse_select_step_ms.longctx": 100.0}.get(name)
    if want is None:
        assert 0 <= got < 100           # the device's idle share
    else:
        assert got == pytest.approx(want)


@pytest.mark.parametrize("name", NOTES)
def test_nothing_to_read_is_none_and_not_zero(name, rec, man):
    """A capture of a program without latent window layers (the parent
    commit's: no operation under ``attn/latent_window``, no such kernel,
    steps without the counts) and a run that traced nothing."""
    whole = ("prefill_piece_ms.notes",)
    ctx = notes_ctx([], rec, man, path=mimo.SCOPED_MOE, attrs={})
    if name not in whole:
        assert mimo.reader(name)(ctx) is None
    ctx = notes_ctx([], rec, man, attrs={})
    ctx["tracer"] = None
    if name not in whole:
        assert mimo.reader(name)(ctx) is None
    if name in ("latent_attn_roofline.notes",
                "latent_window_roofline.notes"):
        assert mimo.reader(name)(notes_ctx([], rec, man, attrs={})) is None


def test_the_scopes_of_the_new_program_are_read_by_kind(man):
    d = "jit(_decode_chunk)/while/body/closed_call/MoeLmModel/layer_2/"
    w = d + "attn/latent_window/attention/attention._paged_step/"
    assert scope_latents.kind_of(w + "pallas_call") == "attn/latent_window"
    assert scope_latents.kind_of(
        d + "attn/latent/attention/attn/select/top_k") == "attn/latent"
    assert scope_latents.scope_of(
        w + "kv_pool/write/window/scatter") == "kv_pool/write/window"
    assert scope_latents.scope_of(
        d + "attn/latent/attention/kv_pool/write/scatter") == \
        "kv_pool/write"
    assert scope_latents.scope_of(w + "attn/gate/gate/dot_general") == \
        "attn/gate"
    call = ('%{}.3 = bf16[32,64,1024]{{2,1,0}} custom-call(%a), '
            'custom_call_target="tpu_custom_call"')
    assert scope_latents.kernel_of(call.format("paged_latent_window")) == \
        scope_latents.WINDOW_KERNEL
    assert scope_latents.kernel_of(
        call.format("paged_latent_attention")) == "paged_latent_attention"
    assert scope_latents.kernel_of(call.format("paged_index_scores")) == \
        "paged_index_scores"
    assert scope_latents.kernel_of("%fusion.3") is None


def test_the_float32_check_rehearses_at_test_size(capsys, monkeypatch):
    """``benchmark/check_latents_f32.py --tiny``: the engine's own
    programs (pieces, the insert, paged decode steps by the interpreted
    kernels) in float32 against the reference at every row, the run the
    chip makes at published widths (PERF.md section 2)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_latents_f32",
        os.path.join(REPO, "benchmark", "check_latents_f32.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setenv("TTD_FUSED_ATTN_INTERPRET", "1")  # main's own default
    assert mod.main(["--tiny", "--tol", "1e-4"]) == 0
    read, ok = [json.loads(ln) for ln in
                capsys.readouterr().out.splitlines() if ln.startswith("{")]
    assert ok == {"ok": True} and read["fused"] is True
    assert read["rows"] == 5 + 24 and read["max_abs"] < 1e-4
    assert read["same_first_choice"] == 1.0


def _as_it_was(tmp_path):
    """A checkout whose manifest is as it was before this PR: this
    cell, its configuration and its six readers taken out BY NAME,
    the cell's name off the lists it was appended to."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["per_layer"] = [m for m in data["per_layer"]
                         if m["name"] not in NOTES]
    data["configs"] = [c for c in data["configs"] if c["name"] != CONFIG]
    data["workloads"] = [w for w in data["workloads"] if w["name"] != CELL]
    taken = 0
    for m in data["end_to_end"] + data["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].remove(CELL)
            taken += 1
    assert taken == 1 + len(SHARED)
    root = tmp_path / "before_dots3"
    root.mkdir()
    os.symlink(os.path.join(REPO, "benchmark"), root / "benchmark")
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(data, f)
    return str(root)


def test_the_tests_that_pin_the_manifest_run_whole_as_it_was(
        tmp_path, monkeypatch):
    """Two tests of ``test_benchmark_prefix_flash_latent.py`` assert
    that ITS reader (``prefix_flash_roofline.longctx``) is the LAST of
    ``per_layer``.  This PR appends a configuration, a cell and six
    readers after it, as the contract has it, and that file is not this
    PR's to edit (``tests/conftest.py`` marks the two expected
    failures).  Both run here whole, every assertion of them and of the
    tests the second runs in turn, on a checkout whose manifest is as it
    was before this cell, which it finds BY NAME: appending changed
    nothing that was there."""
    import test_benchmark_prefix_flash_latent as latent

    root = _as_it_was(tmp_path)
    latent.test_the_reader_is_found_by_name_for_its_cell_alone(
        manifest_lib.Manifest(root))
    monkeypatch.setattr(latent, "REPO", root)
    inner = tmp_path / "inner"
    inner.mkdir()
    latent.test_the_manifest_before_this_reader_is_what_the_pins_ran_on(
        inner, monkeypatch)
