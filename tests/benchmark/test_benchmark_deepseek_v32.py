"""One chip's share of an expert-parallel ``deepseek_v32`` deployment in
the benchmark: a ``family: "moe_share"`` cell added by files and
manifest entries alone runs end to end through
``harness/serve_share.py`` at test size on the CPU (and its ``fp8w``
control comes out not correct), the published file equals its catalog
row but for ``reduced``, the builder refuses what the program would not
run, ``costs_share`` by hand, the new scopes, the new readers on a
hand-built capture and where there is nothing to read, and the
reference's ``served_gaps`` whatever the padding."""

import json
import os

import numpy as np
import pytest

import cellkit
from cellkit import CELLS, REPO, run_cell

from benchmark.harness import costs_moe, costs_share, loadgen
from benchmark.harness import manifest as manifest_lib
from benchmark.harness import scope_share, scopes, serve_family, serve_share
from test_benchmark_spans import step, traced_ctx

SCOPED_SHARE = os.path.join(REPO, "benchmark", "fixtures",
                            "scoped_trace_share.json")
SCOPED_MOE = os.path.join(REPO, "benchmark", "fixtures",
                          "scoped_trace_moe.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "deepseek-v32exp-1chip.longctx-mixed"
CONFIG = "deepseek-v32exp-1chip"
LONGCTX = ("prefill_piece_ms.longctx", "decode_step_ms.longctx",
           "sparse_index_ms.longctx", "sparse_select_ms.longctx",
           "sparse_attn_ms.longctx", "rows_selected_share.longctx",
           "moe_experts_ms.longctx", "moe_gmm_roofline.longctx",
           "experts_hit_mean.longctx", "device_idle_pct.longctx",
           "paged_index_scores_roofline.longctx",
           "sparse_index_step_ms.longctx", "sparse_select_step_ms.longctx")
# Accepted metrics of a decode step's host side that the cell is
# appended to.
SHARED = ("host_self_ms.decode", "decode_lanes_mean.decode")
# Readers of whole programs and of the device: they read a parent's
# capture too (what they read is in every program).
EVERY_PROGRAM = ("prefill_piece_ms.longctx", "decode_step_ms.longctx",
                 "device_idle_pct.longctx")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def man():
    return manifest_lib.Manifest(REPO)


def reader(name):
    return manifest_lib.Manifest(REPO).layer_reader(name)


def _compared(earlier):
    checked = next(r for r in earlier if r.get("phase") == "check")
    return {row["number"]: row for row in checked["compared"]}


def test_a_share_cell_added_by_files_alone_runs_and_its_control_fails(
        cell_root, capsys):
    """2 of 8 experts (group 1 of 4), half the vocabulary, a selection
    of 16 rows under prompts of 24-96: every piece past the first and
    every decode step chooses.  float32 on both sides, so the limits
    (``ds-tiny-closed.traffic.json``) are rounding's, and the fp8
    weights of the control pass them a hundredfold."""
    from tensorflow_train_distributed_tpu.runtime import events

    root = cell_root("ds-tiny.closed", "ds-tiny", "ds-tiny-closed", 1,
                     ["serve_tokens_per_s", "gap_p95_ms"])
    seq0 = events.get_recorder().events_after(0)[0]
    rc, sound, earlier = run_cell(root, "ds-tiny.closed", seed=2 ** 31 + 5,
                                  capsys=capsys)
    assert rc == 0 and sound["correct"] is True, (sound, earlier[-1])
    assert sound["failed"] == 0 and sound["attempted"] > 0
    recorded = events.get_recorder().events_after(seq0)[1]
    for name, _, _, _, _, attrs in recorded:
        assert events.in_contract(name), name
        assert set(attrs or ()) <= events.contract_attrs(name), (name, attrs)
    steps = [e[5] for e in recorded
             if e[0] == "engine/step" and "rows_scored" in (e[5] or {})]
    assert steps and all(s["experts_held"] == 2 for s in steps)
    assert all(0.0 <= s["experts_hit"] <= 2.0 for s in steps)
    assert all(0.0 <= s["routed_here"] <= 1.0 for s in steps)
    # A lane holds 24-120 rows and a query attends 16 of them.
    assert all(0 < s["rows_selected"] < s["rows_scored"] for s in steps)
    # A piece walks the rows its lane holds with the choice as a mask:
    # it states the walk and no count the device did not make.
    pieces = [e[5] for e in recorded if e[0] == "prefill/piece"]
    assert pieces and all(
        0 < p["rows"] <= p["cache_rows"] and "rows_scored" not in p
        for p in pieces)
    window = next(r for r in earlier if r.get("phase") == "window")
    assert window["compiles_in_window"] == 0
    checked = next(r for r in earlier if r.get("phase") == "check")
    assert checked["reference"].endswith("deepseek_v32")
    sound_rows = _compared(earlier)
    rc, control, earlier = run_cell(
        root, "ds-tiny.closed", seed=2 ** 31 + 5,
        extra=["--control", "fp8w"], capsys=capsys)
    assert rc == 0 and control["correct"] is False
    rows = _compared(earlier)
    assert not rows["served_gap_mean"]["within"]
    assert rows["served_gap_mean"]["value"] > \
        100 * max(sound_rows["served_gap_mean"]["value"], 1e-7)


def test_published_file_equals_its_catalog_row_but_for_the_share(man):
    """Every number of the catalog row's ``config`` is in the file
    under the same key; only the five keys in ``reduced`` differ, each
    with its source value, its value here and a reason; no width, rank
    or head count among them."""
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "DeepSeek-V3.2-Exp")
    entry = next(c for c in man.data["configs"] if c["name"] == CONFIG)
    cfg = man.config(CONFIG)
    assert entry["source"] == row["source_url"] == cfg["source"]
    differs = {k for k, v in row["config"].items()
               if cfg.get(k, KeyError) != v}
    assert differs == set(cfg["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    for key in differs:
        assert cfg["changed"][key]["source"] == row["config"][key]
        assert cfg["changed"][key]["here"] == cfg[key]
        assert len(cfg["changed"][key]["why"]) > 40
        assert not key.endswith(("_dim", "_rank", "_size")) or \
            key == "vocab_size"
    assert "rank 0 of 16 chips" in cfg["deployment"]
    assert cfg["engine"] == {
        "slots": 16, "chunk": 8, "cache_len": 32768, "kv_block_size": 16,
        "kv_pool_blocks": None, "max_queue": 64}
    assert set(cfg["assumed"]) >= {"rope_layout", "index_keys", "weights"}


def test_the_builder_runs_the_file_and_refuses_what_it_would_not_run(man):
    """``share_config`` takes the file as it stands: a router of the
    published 256, 16 experts held from 0, 8 groups of which 4 stay,
    YaRN, the indexer's three sizes; and raises "would run" for a
    published key the program would not run as written."""
    cfg_file = man.config(CONFIG)
    cfg = serve_share.share_config(cfg_file)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_offset,
            cfg.top_k) == (256, 16, 0, 8)
    assert (cfg.n_group, cfg.topk_group) == (8, 4)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk) == (
        64, 128, 2048)
    assert cfg.rope_scaling == ("yarn", 40.0, 32.0, 1.0, 4096)
    assert (cfg.num_layers, cfg.dense_layers, cfg.vocab_size) == (
        5, 1, 16160)
    assert serve_family.FAMILIES["moe_share"][0] is serve_share.share_config
    for change in (
            {"n_routed_experts": 32}, {"n_group": 4}, {"topk_group": 8},
            {"index_topk": 1024}, {"index_n_heads": 32},
            {"index_head_dim": 64}, {"experts_offset": 16},
            {"rope_scaling": dict(cfg_file["rope_scaling"], factor=32)},
            {"changed": dict(cfg_file["changed"], n_routed_experts=dict(
                cfg_file["changed"]["n_routed_experts"], source=128))}):
        with pytest.raises(ValueError, match="would run"):
            serve_share.share_config(dict(cfg_file, **change))
    with pytest.raises(ValueError, match="the program's block has"):
        serve_share.share_config(dict(cfg_file, topk_method="greedy"))
    with pytest.raises(ValueError, match="mscale"):
        serve_share.share_config(dict(cfg_file, rope_scaling=dict(
            cfg_file["rope_scaling"], mscale_all_dim=0.7)))
    leaves = serve_family.moe_param_shapes(cfg)
    moe, attn = leaves["layer_1"]["moe"], leaves["layer_1"]["attention"]
    assert moe["experts"]["wo"]["kernel"].shape == (16, 2048, 7168)
    assert moe["router"]["kernel"].shape == (7168, 256)
    assert moe["bias"].shape == (256,)
    assert attn["index_q"]["kernel"].shape == (1536, 64 * 128)
    assert attn["index_k"]["kernel"].shape == (7168, 128)
    assert attn["index_w"]["kernel"].shape == (7168, 64)
    assert attn["index_k_norm"]["bias"].shape == (128,)
    assert leaves["lm_head"]["kernel"].shape == (7168, 16160)
    assert "moe" not in leaves["layer_0"] and "moe" in leaves["layer_4"]


def test_costs_of_the_share_by_hand(man):
    cfg = man.config(CONFIG)
    assert costs_share.index_key_bytes(cfg) == 256
    assert costs_moe.latent_row_bytes(cfg) == 1152
    # 16 lanes holding 160,000 rows between them, one query row each:
    # a row's key of 256 B in, its float32 score out, 64 heads of
    # (2 x 128 products + relu, weight, sum) a row; each lane's 64
    # query heads of 128 bf16 values and their float32 weights in.
    flops, nbytes = costs_share.index_scores_call(cfg, 160_000, 16)
    assert flops == 160_000 * 64 * (2 * 128 + 3)
    assert nbytes == 160_000 * (256 + 4) + 16 * 64 * (128 * 2 + 4)
    # 5 of the 16 held experts hit by 9 of a step's 128 pairs: their
    # kernels of 3 x 7168 x 2048 bf16 once, 9 rows in and out.
    flops, nbytes = costs_share.held_gmm_layer_call(cfg, 5.0, 9)
    assert flops == 2 * 9 * 3 * 7168 * 2048
    assert nbytes == 5 * 3 * 7168 * 2048 * 2 + 9 * (
        2 * 7168 * 2 + 2 * 2048 * 4 + 2048 * 2 + 7168 * 4)
    assert (flops, nbytes) == costs_moe.gmm_layer_call(cfg, 5.0, 9)


@pytest.mark.parametrize("op_name, want", [
    ("jit(_prefill_piece)/M/layer_1/attention/attn/index_q/index_q/dot",
     "attn/index_q"),
    ("jit(_prefill_piece)/M/layer_1/attention/attn/index_k/index_k_norm/x",
     "attn/index_k"),
    ("jit(_decode_chunk)/w/layer_2/attention/attn/index_score/pallas_call",
     "attn/index_score"),
    ("jit(_decode_chunk)/w/layer_2/attention/attn/select/top_k",
     "attn/select"),
    # the rows attention walks are up-projected inside ``attn/sparse``
    ("jit(_prefill_piece)/M/layer_1/attention/attn/sparse/while/body/"
     "attn/kv_latent/dot_general", "attn/sparse"),
    ("jit(_decode_chunk)/w/layer_2/attention/index_pool/write/scatter",
     "index_pool/write"),
    ("jit(_decode_chunk)/w/layer_2/attention/kv_pool/write/scatter",
     "kv_pool/write"),
    ("jit(_decode_chunk)/w/layer_2/moe/moe/router/moe/route_groups/top_k",
     "moe/route_groups"),
    ("jit(_decode_chunk)/w/layer_2/moe/moe/router/dot_general",
     "moe/router"),
    ("jit(_decode_chunk)/w/dynamic_slice", None),
])
def test_scope_of_the_selections_regions(op_name, want):
    assert scope_share.scope_of(op_name) == want


def _share_ctx(logs, monkeypatch, rec_steps=True, path=SCOPED_SHARE):
    from tensorflow_train_distributed_tpu.runtime import events

    ctx = traced_ctx(path, logs)
    # The profiler ran for the ring's seconds 100 to 102.
    ctx["tracer"].t0, ctx["tracer"].t1 = 100.0, 102.0
    ctx["peaks"] = PEAKS
    ctx["config"] = manifest_lib.Manifest(REPO).config(CONFIG)
    ctx["result"]["counters"].update(slots=16, kv_block_size=16)
    rec = events.Recorder(64)
    monkeypatch.setattr(events, "get_recorder", lambda: rec)
    if rec_steps:
        held = dict(experts_held=16, expert_load_cv=1.5)
        step(rec, 100.0, 1.0, lanes=7, experts_hit=5.0, routed_here=0.08,
             rows_scored=100_000.0, rows_selected=14_000.0, **held)
        rec.record_at("prefill/piece", "X", 100.5, 0.2, dict(
            rid=1, piece=3, n_pieces=9, tokens=1024, rows=4096,
            cache_rows=32768))
        step(rec, 101.0, 1.0, lanes=8, experts_hit=7.0, routed_here=0.04,
             rows_scored=120_000.0, rows_selected=16_000.0, **held)
        step(rec, 102.0, 1.0, lanes=0)              # no chunk harvested
        # after the capture, the lanes fuller: the window's, not its
        step(rec, 103.0, 1.0, lanes=9, experts_hit=9.0, routed_here=0.06,
             rows_scored=140_000.0, rows_selected=18_000.0, **held)
        rec.record_at("prefill/piece", "X", 103.5, 0.2, dict(
            rid=2, piece=0, n_pieces=5, tokens=1024, rows=1024,
            cache_rows=32768))
    return ctx


def test_readers_on_a_hand_built_capture(monkeypatch):
    """Two whole chunks of two steps (a third, cut by the capture's
    end, is left out) and one whole piece.  In the chunks: the index
    kernel 0.3 s in two calls, the choice 0.5 s, the latent kernel over
    the chosen rows 0.4 s, the groups' choice 0.1 s, the grouped
    matmuls 1.0 s and the gating product 0.2 s, the index key's write
    0.1 s, one operation under no scope 0.2 s.  In the piece: scores
    0.3 + queries 0.1 + keys 0.05 s, the choice 0.25 s, attention
    0.2 s, experts 0.1 s."""
    logs = []
    ctx = _share_ctx(logs, monkeypatch)
    assert reader("decode_step_ms.longctx")(ctx) == pytest.approx(
        1.5 / 2 * 1e3)
    assert reader("prefill_piece_ms.longctx")(ctx) == pytest.approx(1000.0)
    assert reader("sparse_index_ms.longctx")(ctx) == pytest.approx(450.0)
    assert reader("sparse_select_ms.longctx")(ctx) == pytest.approx(250.0)
    assert reader("sparse_attn_ms.longctx")(ctx) == pytest.approx(200.0)
    assert reader("moe_experts_ms.longctx")(ctx) == pytest.approx(
        1.2 / 4 * 1e3)
    # busy: -0.2..-0.1, 0..2, 2.4..3.4, 3.6..4.4, 4.9..5 of -0.2..5
    assert reader("device_idle_pct.longctx")(ctx) == pytest.approx(
        100.0 * 1.2 / 5.2)
    # a counter of the whole window ...
    assert reader("experts_hit_mean.longctx")(ctx) == pytest.approx(7.0)
    # ... the captured steps' rows, counted on the device, not the
    # later step's; a piece states none
    assert reader("rows_selected_share.longctx")(ctx) == pytest.approx(
        100.0 * 30_000 / 220_000)
    # the selection's stages of a decode step: scores 0.3 s and the
    # key's write 0.1 s, the choice 0.5 s, over 4 steps
    assert reader("sparse_index_step_ms.longctx")(ctx) == pytest.approx(
        100.0)
    assert reader("sparse_select_step_ms.longctx")(ctx) == pytest.approx(
        125.0)
    tables = {r["program"]: r for r in logs
              if r["phase"] == "ms_by_scope.longctx"}
    assert tables["_decode_chunk"]["ms"] == pytest.approx({
        "moe/experts": 300.0, "attn/select": 125.0, "attn/sparse": 100.0,
        "attn/index_score": 75.0, scopes.PLUMBING: 50.0,
        "moe/route_groups": 25.0, "index_pool/write": 25.0})
    assert tables["_decode_chunk"]["kernel_calls"] == {
        "paged_index_scores": 0.5, "paged_latent_attention": 0.25,
        "gmm": 0.5}
    assert tables["_prefill_piece"]["ms"] == pytest.approx({
        "attn/index_score": 300.0, "attn/select": 250.0,
        "attn/sparse": 200.0, "attn/index_q": 100.0, "moe/experts": 100.0,
        "attn/index_k": 50.0})
    assert len(tables) == len(
        [r for r in logs if r["phase"] == "ms_by_scope.longctx"]) == 2
    # A roofline sets its count beside device times of the capture, so
    # it takes the steps the capture overlapped: 110,000 rows scored in
    # a call of 0.15 s; 6 experts hit by 128 pairs x 0.06, four expert
    # layers in 0.25 s of ``gmm`` a step.
    cfg = ctx["config"]
    _, nbytes = costs_share.index_scores_call(cfg, 110_000.0, 16)
    assert reader("paged_index_scores_roofline.longctx")(ctx) == \
        pytest.approx(100.0 * nbytes / 819e9 / 0.15)
    _, nbytes = costs_share.held_gmm_layer_call(cfg, 6.0, 128 * 0.06)
    assert reader("moe_gmm_roofline.longctx")(ctx) == pytest.approx(
        100.0 * 4 * nbytes / 819e9 / 0.25)


@pytest.mark.parametrize("name", [n for n in LONGCTX
                                  if n not in EVERY_PROGRAM])
def test_new_reader_reads_nothing_from_a_program_without_its_names(
        name, monkeypatch):
    """A parent commit's captures (``small_trace.json``: no scope on
    any operation; ``scoped_trace_moe.json``: the block without the
    selection) and its ring (no ``rows_scored``, no ``experts_held``):
    nothing, no error, no line in the log."""
    from tensorflow_train_distributed_tpu.runtime import events

    for path in (cellkit.FIXTURE, SCOPED_MOE):
        logs = []
        ctx = traced_ctx(path, logs)
        ctx["tracer"].t0, ctx["tracer"].t1 = 100.0, 102.0
        ctx["peaks"] = PEAKS
        ctx["config"] = manifest_lib.Manifest(REPO).config(CONFIG)
        ctx["result"]["counters"].update(slots=16, kv_block_size=16)
        rec = events.Recorder(16)
        monkeypatch.setattr(events, "get_recorder", lambda rec=rec: rec)
        step(rec, 100.0, 1.0, lanes=4, kv_blocks=12, experts_hit=50.0,
             expert_load_cv=1.0)
        rec.record_at("prefill/piece", "X", 100.5, 0.2, dict(
            rid=1, piece=3, n_pieces=9, tokens=1024, rows=4096,
            cache_rows=8192))
        assert reader(name)(ctx) is None
        assert logs == []


def test_a_capture_of_the_selection_without_its_ring_gives_no_share(
        monkeypatch):
    logs = []
    ctx = _share_ctx(logs, monkeypatch, rec_steps=False)
    for name in ("paged_index_scores_roofline.longctx",
                 "moe_gmm_roofline.longctx", "experts_hit_mean.longctx",
                 "rows_selected_share.longctx"):
        assert reader(name)(ctx) is None
    assert reader("sparse_select_ms.longctx")(ctx) == pytest.approx(250.0)


def test_served_gaps_do_not_depend_on_the_padding():
    """The reference at the test cell's size (2 of 8 experts held, a
    selection of 16 rows): zero for its own greedy choice, positive
    where the served token is another, and the same numbers whatever
    ``pad_to`` / ``rows_to`` the runner pads a mix to (padding sits
    after every real position; blocks of queries change no number's
    definition)."""
    import jax.numpy as jnp

    from benchmark.harness import weights
    from benchmark.references import deepseek_v32 as reference

    with open(os.path.join(CELLS, "ds-tiny.json")) as f:
        cfg_file = json.load(f)
    cfg = serve_share.share_config(cfg_file)
    params = weights.make_params(serve_family.moe_param_shapes(cfg),
                                 2 ** 31 + 11, jnp.float32)
    assert reference.PAD == 512
    prompt = list(np.random.default_rng(3).integers(3, 128, 40))
    served = []
    for _ in range(4):                     # greedy by the reference
        lg = reference.logits_at(params, cfg_file, prompt + served,
                                 [len(prompt) + len(served) - 1])
        served.append(int(jnp.argmax(lg[0])))
    plain = reference.served_gaps(params, cfg_file, prompt, served)
    assert plain.shape == (4,) and float(np.abs(plain).max()) < 1e-5
    wrong = list(served)
    wrong[1] = (wrong[1] + 1) % 128
    first = reference.served_gaps(params, cfg_file, prompt, wrong)
    padded = reference.served_gaps(params, cfg_file, prompt, wrong,
                                   pad_to=1024, rows_to=8)
    assert first[1] > 1e-3 and float(np.abs(first[:1]).max()) < 1e-5
    # float32 sums over blocks of another size: the same to rounding
    np.testing.assert_allclose(padded, first, atol=2e-5)


def test_every_seed_offers_the_pool_in_the_order_it_was_drawn(man):
    """``"order": "pool"``: request ``i`` has the same lengths under
    every seed (the pool ``loadgen.Schedule`` draws, not shuffled), the
    token ids are the seed's; the run lends ``loadgen.Schedule``'s name
    for its own duration and gives it back, also where the run fails;
    a file that does not state the order is refused."""
    traffic = man.traffic("longctx-mixed")
    seeds = (7, 2 ** 31 + 5, 3200005001)
    ours = [serve_share.PoolOrder(traffic, s, 51.0, 16160) for s in seeds]
    plans = [[(s[i].prompt_len, s[i].max_new) for i in range(160)]
             for s in ours]
    assert plans[0] == plans[1] == plans[2]
    assert plans[0][:64] == plans[0][64:128]
    assert len(set(plans[0][:64])) == 64
    theirs = loadgen.Schedule(traffic, seeds[0], 51.0, 16160)
    shuffled = [(theirs[i].prompt_len, theirs[i].max_new)
                for i in range(64)]
    assert sorted(shuffled) == sorted(plans[0][:64])
    assert shuffled != plans[0][:64]
    assert all(4096 <= p <= 24576 and 64 <= n <= 512 for p, n in plans[0])
    first = [s.prompt_tokens(0) for s in ours]
    assert len({len(t) for t in first}) == 1
    assert first[0] != first[1] != first[2]
    assert first[0] == serve_share.PoolOrder(
        traffic, seeds[0], 51.0, 16160).prompt_tokens(0)
    with pytest.raises(ValueError, match="closed loop"):
        serve_share.PoolOrder(
            dict(man.traffic("longprompt-rate"), order="pool"), 7, 51.0,
            16160)

    seen = []

    def family_run(ctx):
        seen.append(loadgen.Schedule)
        if ctx.get("fail"):
            raise RuntimeError("stopped")
        return {"ran": True}

    generator = loadgen.Schedule
    real, serve_family.run = serve_family.run, family_run
    try:
        assert serve_share.run({"traffic": traffic}) == {"ran": True}
        with pytest.raises(RuntimeError, match="stopped"):
            serve_share.run({"traffic": traffic, "fail": True})
        for unstated in ({k: v for k, v in traffic.items() if k != "order"},
                         dict(traffic, order="seed")):
            with pytest.raises(ValueError, match='says "order": "pool"'):
                serve_share.run({"traffic": unstated})
    finally:
        serve_family.run = real
    assert seen == [serve_share.PoolOrder, serve_share.PoolOrder]
    assert loadgen.Schedule is generator


def test_new_cells_traffic_and_metrics_are_found_by_name(man):
    cell = man.workload(CELL)
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    traffic = man.traffic(cell["traffic"])
    assert traffic["kind"] == "serve_share" and traffic["loop"] == "closed"
    # The parameters the issue named, and one more: a window finishes
    # ~16 of the ~48 requests offered, so the pool (the variety's size)
    # is offered in the order it was drawn under every seed, and a seed
    # chooses no part of the work (PERF.md section 6).
    assert (traffic["callers"], traffic["pool"], traffic["mix_seed"]) == (
        32, 64, 20260929)
    assert traffic["order"] == "pool"
    assert traffic["prompt_len"] == {
        "dist": "lognormal", "median": 12288, "sigma": 0.5, "min": 4096,
        "max": 24576}
    assert traffic["output_len"] == {
        "dist": "lognormal", "median": 256, "sigma": 0.5, "min": 64,
        "max": 512}
    assert (traffic["ramp_s"], traffic["drain_s"], traffic["trace_s"]) == (
        15.0, 0.0, 2.0)
    assert traffic["engine"] == {"prefill_chunk": 1024,
                                 "prefill_budget": 2048}
    assert traffic["check"]["sample"] == 4
    limits = traffic["check"]["limits"]
    assert 0.0 < limits["served_gap_mean"] < limits["served_gap_max"]
    assert {m["name"] for m in man.end_to_end_for(cell["name"])} == {
        "serve_tokens_per_s", "setup_s"}
    ours = {m["name"] for m in man.per_layer_for(cell["name"])}
    assert ours >= set(LONGCTX)
    assert ours >= set(SHARED)
    for m in man.data["per_layer"]:
        if m["name"] in LONGCTX:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
        if m["name"] in SHARED:
            assert m["workloads"][-1] == CELL
