"""Latent attention of two kinds in one model (``dots3_note``) at test
size: ``MOE_PRESETS["dots3_note_tiny"]`` (full layers at the model's
latent sizes that choose 16 rows, window layers with ranks, heads, key
size and rotary base of their own over a window of 9) through the
serving engine's own programs (prefill in pieces on the batch-1 cache,
the insert into the full layers' pool and the window layers' rings at
each kind's own row, paged decode past two turns of a ring and past
``index_topk`` rows, by the gather leg and by the kernels, interpreted)
against the plain reference ``benchmark/references/dots3_note.py``; the
window's boundary (9 against 8 and 10 separates); each planted fault
(the window one key short, a decode step's ring row written one entry
off, ``a_kv`` left out of the window kind) is seen;
``paged_latent_attention(window=)`` against its ``jnp`` reference at two
row widths; the sixteen shares of an expert layer with the shared
expert counted once add up to the uncut layer; the kinds that were
there keep their fields and their configurations still build; the
pools' bytes at the published sizes are the issue's arithmetic."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (REPO, os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)

from benchmark.harness import serve_family, weights  # noqa: E402
from benchmark.harness import faults_latents  # noqa: E402
from benchmark.references import dots3_note as reference  # noqa: E402
from tensorflow_train_distributed_tpu.models import layers, moe  # noqa: E402
from tensorflow_train_distributed_tpu.ops import (  # noqa: E402
    pallas_kernels as pk,
)
from tensorflow_train_distributed_tpu.runtime import events  # noqa: E402
from tensorflow_train_distributed_tpu.serving import (  # noqa: E402
    ServingEngine,
)

import test_laguna  # noqa: E402  (program_logits: the engine's programs)

TINY = moe.MOE_PRESETS["dots3_note_tiny"]
BIG = moe.MOE_PRESETS["dots3_note"]
SEED = 2 ** 31 + 46
WINDOW_LAYERS = (2, 3, 4)


def cfg_file_of(cfg, **over):
    """The configuration-file keys the reference reads, for a program
    config of this family (the source's own names)."""
    sizes = [cfg.latent_sizes(i) for i in range(cfg.num_layers)]
    full = next(k for k in sizes if k.window is None)
    window = next(k for k in sizes if k.window is not None)
    out = {
        "num_hidden_layers": cfg.num_layers,
        "rms_norm_eps": cfg.rms_epsilon,
        "sliding_window_size": window.window,
        "apply_mla_qkv_lora_rescale": cfg.lora_rescale,
        "num_experts_per_tok": cfg.top_k, "norm_topk_prob": True,
        "routed_scaling_factor": cfg.routed_scaling,
        "index_n_heads": cfg.index_heads,
        "index_head_dim": cfg.index_dim, "index_topk": cfg.index_topk,
        "experts_offset": cfg.experts_offset,
        "layer_types": ["sliding_attention" if k.window is not None
                        else "full_attention" for k in sizes],
    }
    for pre, k in (("", full), ("swa_", window)):
        out.update({
            pre + "num_attention_heads": k.num_heads,
            pre + "q_lora_rank": k.q_lora_rank,
            pre + "kv_lora_rank": k.kv_lora_rank,
            pre + "qk_nope_head_dim": k.qk_nope_dim,
            pre + "qk_rope_head_dim": k.qk_rope_dim,
            pre + "v_head_dim": k.v_head_dim,
            pre + "rope_theta": k.rope_base})
    out.update(over)
    return out


@pytest.fixture(scope="module")
def params():
    return weights.make_params(serve_family.moe_param_shapes(TINY), SEED,
                               jnp.float32)


def reference_logits(params, cfg_file, seq):
    return np.asarray(reference.logits_at(
        params, cfg_file, [int(t) for t in seq], list(range(len(seq)))))


@pytest.fixture(scope="module")
def sequence():
    return np.random.default_rng(46).integers(3, 256, 61).astype(np.int32)


@pytest.fixture(scope="module")
def reference_run(params, sequence):
    return reference_logits(params, cfg_file_of(TINY), sequence)


# float32 on both sides, logits of a few units: what is left is the
# order of float32 sums (tiles of rows and the absorbed form against one
# softmax over every key): a few 1e-6.  Any stage dropped or bent moves
# a logit by 1e-2 or more.
TOL = 2e-5


def test_the_pattern_and_the_rows_are_the_published_ones():
    """Full at layers 0, 1, 5, 9, ..., 45 (thirteen), window elsewhere
    (thirty-three); ONE place resolves a layer's latent sizes: a full
    layer runs the model's and chooses, a window layer runs its own and
    does not (their cached rows, 576 values in 640 and 1,088 in 1,152,
    are held to the arithmetic below)."""
    kinds = [BIG.latent_sizes(i) for i in range(46)]
    full = [i for i, k in enumerate(kinds) if k.window is None]
    assert full == [0] + list(range(1, 46, 4)) and len(full) == 13
    f, w = kinds[1], kinds[2]
    assert dataclasses.astuple(f) == (
        128, 1024, 512, 128, 64, 128, None, 80_000_000.0, None, 64, 128,
        2048)
    assert dataclasses.astuple(w) == (
        64, 1024, 1024, 192, 64, 128, 513, 50_000.0, None, 64, 128, 0)
    assert all(k == (w if k.window else f) for k in kinds)
    assert BIG.attn_window == 513 and BIG.lora_rescale
    # the same shape at test size: a window row of two lane tiles
    tiny = [TINY.latent_sizes(i) for i in range(5)]
    assert [k.window for k in tiny] == [None, None, 9, 9, 9]
    assert [k.index_topk for k in tiny] == [16, 16, 0, 0, 0]
    # a model whose layers are alike resolves to its own fields
    glm = moe.MOE_PRESETS["glm47_flash"].latent_sizes(3)
    assert (glm.num_heads, glm.kv_lora_rank, glm.qk_nope_dim, glm.window,
            glm.index_topk) == (20, 512, 192, None, 0)


@pytest.mark.parametrize("fused", [False, True], ids=["gather", "kernel"])
def test_pieces_then_paged_decode_agree_with_the_reference(
        fused, params, sequence, reference_run, monkeypatch):
    """21 prompt tokens in three pieces of 8, then 40 paged decode
    steps: the window is 9 rows and the ring 4 blocks of 4 = 16 rows,
    so decode runs past two turns of a ring; a full layer has chosen
    among more than its 16 rows since the second piece.  By the
    gathered views and by the kernels (interpreted)."""
    if fused:
        monkeypatch.setenv("TTD_FUSED_ATTN_INTERPRET", "1")
    calls = []
    kernel = pk._paged_latent_kernel
    monkeypatch.setattr(
        pk, "_paged_latent_kernel",
        lambda *a, **kw: calls.append(kw.get("window")) or kernel(*a, **kw))
    eng, pre, dec = test_laguna.program_logits(TINY, params, sequence, 21)
    assert (eng._window, eng._ring_blocks) == (9, 4)
    assert bool(eng.fused_attn()) == fused
    # the decode program holds the kernel over a ring (3 layers) and
    # over the chosen rows (2 layers), or neither
    # (however often the program is traced)
    assert bool(calls) == fused and set(calls) <= {9, None}
    assert calls.count(9) * 2 == calls.count(None) * 3
    ours = np.concatenate([pre, dec])
    assert ours.shape == reference_run.shape == (61, 256)
    np.testing.assert_allclose(ours, reference_run, atol=TOL, rtol=0)
    assert (ours.argmax(-1) == reference_run.argmax(-1)).all()
    # pools by each kind's own row: a full layer's blocks with its index
    # keys, a window layer's rings under a table of their own
    flat = {eng._path_key(p): leaf.shape for p, leaf
            in jax.tree_util.tree_flatten_with_path(
                eng._cache_struct(2, grid=True))[0]}
    for i in range(5):
        mod = (f"layer_{i}", "attention")
        if i in WINDOW_LAYERS:
            assert flat[mod + ("latent_pool",)] == (1 + 2 * 4, 4, 256)
            assert flat[mod + ("window_table",)] == (2, 4)
            assert mod + ("index_pool",) not in flat
            assert mod + ("block_table",) not in flat
        else:
            assert flat[mod + ("latent_pool",)] == (1 + 2 * 24, 4, 128)
            assert flat[mod + ("index_pool",)] == (1 + 2 * 24, 4, 16)
            assert mod + ("window_table",) not in flat
    assert eng.kv_pool_parts() == {
        "latent_pool_bytes": 2 * 49 * 4 * 128 * 4,
        "index_pool_bytes": 2 * 49 * 4 * 16 * 4,
        "latent_ring_bytes": 3 * 9 * 4 * 256 * 4}
    assert eng.kv_pool_bytes() == sum(eng.kv_pool_parts().values())
    assert eng._kv_ring_bytes == 3 * 9 * 4 * 256 * 4
    assert eng._kv_pool.bytes_per_block == 2 * 4 * (128 + 16) * 4


def _windows(cfg, **changes):
    """``cfg`` with every WINDOW kind of its period changed."""
    return dataclasses.replace(cfg, attn_period=tuple(
        dataclasses.replace(k, **changes) if k.window is not None else k
        for k in cfg.attn_period))


@pytest.mark.parametrize("window", [8, 10])
def test_the_windows_boundary_separates(window, params, sequence,
                                        reference_run):
    """``sliding_window_size`` 9 is 9 keys of which the token itself is
    one: the program at 9 is the reference at 9 (above) and is neither
    the reference at 8 nor at 10, and a program at 8 or 10 is not the
    reference at 9; the rows before the boundary see the same keys
    either way."""
    other = reference_logits(
        params, cfg_file_of(TINY, sliding_window_size=window), sequence)
    _, pre, dec = test_laguna.program_logits(TINY, params, sequence, 21)
    off = np.abs(np.concatenate([pre, dec]) - other)
    first = min(window, 9)          # the first row that sees a difference
    assert off[:first].max() < TOL and off[first:].max() > 500 * TOL
    _, pre, dec = test_laguna.program_logits(
        _windows(TINY, window=window), params, sequence, 21)
    ours = np.concatenate([pre, dec])
    np.testing.assert_allclose(ours, other, atol=TOL, rtol=0)
    assert np.abs(ours - reference_run).max() > 500 * TOL


@pytest.mark.parametrize("fault", faults_latents.FAULTS)
def test_a_planted_fault_in_the_program_is_seen(
        fault, params, sequence, reference_run, monkeypatch):
    """The benchmark's own planted faults (``faults_latents.planted``)
    at test size, under the interpreted kernels: logits leave the
    reference by far more than rounding; a fault of the decode step
    alone leaves the pieces sound."""
    monkeypatch.setenv("TTD_FUSED_ATTN_INTERPRET", "1")
    with faults_latents.planted(fault):
        _, pre, dec = test_laguna.program_logits(TINY, params, sequence, 21)
    off_pre = np.abs(pre - reference_run[:21]).max()
    off_dec = np.abs(dec - reference_run[21:]).max()
    assert off_dec > 500 * TOL, (fault, off_dec)
    if fault in ("ringoff", "neighbour"):
        assert off_pre < TOL
    else:
        assert off_pre > 500 * TOL
    # and the program is put right again
    _, pre, dec = test_laguna.program_logits(TINY, params, sequence, 21)
    np.testing.assert_allclose(np.concatenate([pre, dec]), reference_run,
                               atol=TOL, rtol=0)


def test_the_training_forward_is_the_reference_too(params, sequence,
                                                   reference_run):
    """A whole forward with no cache: the window a band of the causal
    mask, the choice a mask, both latents rescaled, both kinds gated."""
    got = np.asarray(moe.MoeLmModel(TINY).apply(
        {"params": params}, jnp.asarray(sequence[None]))[0])
    np.testing.assert_allclose(got, reference_run, atol=TOL, rtol=0)
    # without the rescale it is another model
    plain = np.asarray(moe.MoeLmModel(dataclasses.replace(
        TINY, lora_rescale=False)).apply(
            {"params": params}, jnp.asarray(sequence[None]))[0])
    assert np.abs(plain - reference_run).max() > 500 * TOL


def test_a_window_layer_that_chooses_is_refused(params):
    bad = _windows(TINY, index_topk=4)
    with pytest.raises(ValueError, match="a window layer chooses no rows"):
        test_laguna.program_logits(bad, params, np.arange(3, 30), 21)


# -- the kernel over a ring -----------------------------------------------------

#: (heads, row, value_dim, block, ring blocks, window, q_len): a row of
#: one lane tile and a row of three, the second with a window that
#: starts mid-block and two queries a call.
RINGS = {"row128": (4, 128, 96, 4, 4, 9, 1),
         "row384-q2": (8, 384, 320, 8, 4, 20, 2)}


@pytest.mark.parametrize("case", sorted(RINGS))
def test_paged_latent_attention_over_a_ring_equals_its_reference(case):
    """Lanes that hold less than a window, a window's worth, several
    turns of the ring, and nothing (length 0, the scratch block): the
    interpreted kernel walks from the window's first block through the
    ring's entries and equals the gathered ring under ``ring_mask``;
    rows behind the window are poisoned and must not be read into the
    result."""
    heads, row, vd, bs, ring, window, q_len = RINGS[case]
    rng = np.random.default_rng(len(case))
    lengths = jnp.asarray([0, 3, window - 1, window + 5, 3 * ring * bs + 2,
                           117], jnp.int32)
    lanes = len(lengths)
    cache_len = 160
    table = jnp.asarray(1 + np.arange(lanes * ring).reshape(lanes, ring),
                        jnp.int32).at[0].set(0)
    pool = jnp.asarray(rng.standard_normal((1 + lanes * ring, bs, row)),
                       jnp.float32)
    q = jnp.asarray(rng.standard_normal((lanes, q_len, heads, row)),
                    jnp.float32)
    kw = dict(value_dim=vd, scale=row ** -0.5, cache_len=cache_len,
              window=window)
    want = pk.paged_latent_attention_reference(q, pool, table, lengths,
                                               **kw)
    got = pk.paged_latent_attention(q, pool, table, lengths,
                                    interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)
    # the rows a query does not see, poisoned: the same result
    held = pk.ring_row_positions(lengths, q_len, ring * bs)
    last = (lengths + q_len - 1)[:, None]
    unseen = (held < 0) | (held <= last - window - (q_len - 1))
    bad = pool.at[table[1:].reshape(-1)].set(jnp.where(
        unseen[1:].reshape(-1, bs)[..., None], 1e4,
        pool[table[1:].reshape(-1)]))
    again = pk.paged_latent_attention(q, bad, table, lengths,
                                      interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(again[1:]), np.asarray(want[1:]),
                               atol=2e-5, rtol=0)
    with pytest.raises(ValueError, match="window needs cache_len"):
        pk.paged_latent_attention(q, pool, table, lengths, value_dim=vd,
                                  scale=1.0, window=window)


def test_without_a_window_the_kernel_traces_as_it_did():
    """``window=None`` adds nothing to the trace: the same jaxpr as a
    call that does not name the argument."""
    q = jnp.zeros((2, 1, 4, 128), jnp.float32)
    pool = jnp.zeros((9, 4, 128), jnp.float32)
    table = jnp.zeros((2, 4), jnp.int32)
    lengths = jnp.zeros((2,), jnp.int32)

    def call(**kw):
        return str(jax.make_jaxpr(lambda *a: pk.paged_latent_attention(
            *a, value_dim=96, scale=1.0, cache_len=16, interpret=True,
            **kw))(q, pool, table, lengths))

    assert call() == call(window=None)
    assert call() != call(window=5)


# -- the share ------------------------------------------------------------------

def test_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
        params):
    """Ranks 0-15 of sixteen chips hold one expert each of a router of
    16 (the tiny layer's 8 kernels twice over, a router twice as wide)
    and the shared expert whole.  What the program's layer gives for
    each share, with the shared expert counted ONCE, adds up to the
    reference's uncut layer."""
    layer = params["layer_2"]["moe"]
    rng = np.random.default_rng(5)
    wide = {
        "router": {"kernel": jnp.asarray(
            rng.standard_normal((64, 16)) / 8.0, jnp.float32)},
        "bias": jnp.asarray(0.02 * rng.standard_normal(16), jnp.float32),
        "experts": jax.tree.map(
            lambda kernel: jnp.concatenate([kernel, -kernel[::-1]]),
            layer["experts"]),
        "shared_mlp": layer["shared_mlp"]}
    x = jnp.asarray(rng.standard_normal((1, 24, 64)), jnp.float32)
    cfg_file = cfg_file_of(TINY)
    whole = np.asarray(reference.expert_layer(x[0], wide, cfg_file))
    shared = np.asarray(reference.swiglu(x[0], wide["shared_mlp"]))
    total = np.zeros_like(whole)
    for rank in range(16):
        cfg = dataclasses.replace(TINY, num_experts=16, experts_held=1,
                                  experts_offset=rank)
        mine = dict(wide, experts=jax.tree.map(
            lambda kernel: kernel[rank:rank + 1], wide["experts"]))
        y = np.asarray(moe.MoEMlpBlock(cfg).apply({"params": mine}, x)[0])
        want = np.asarray(reference.expert_layer(
            x[0], mine, dict(cfg_file, experts_offset=rank)))
        np.testing.assert_allclose(y, want, atol=2e-5, rtol=0)
        total += y - shared           # every chip computes it alike
    np.testing.assert_allclose(total + shared, whole, atol=5e-5, rtol=0)
    # and no share is the whole: the experts elsewhere add something
    assert np.abs(y - whole).max() > 1e-3


# -- what was there stays -------------------------------------------------------

def test_the_kinds_that_were_there_keep_their_fields_and_still_build():
    """``serve_pattern.kind_of`` compares ``dataclasses.astuple`` of a
    kind with five values, ``serve_sink`` with seven: a latent kind's
    own sizes are a subclass's (``OwnLatentKind``), so ``LatentKind``
    (Ling's) keeps ``AttnKind``'s five.  The three configurations whose
    builders read kinds still build."""
    from benchmark.harness import (manifest as manifest_lib, serve_hybrid,
                                   serve_pattern, serve_sink)

    for preset in ("laguna_s21", "laguna_tiny", "ling3_flash", "ling_tiny"):
        for kind in moe.MOE_PRESETS[preset].attn_period:
            assert len(dataclasses.astuple(kind)) == 5
            assert not isinstance(kind, moe.OwnLatentKind)
    assert len(dataclasses.astuple(
        moe.MOE_PRESETS["mimo_v25"].attn_kind(1))) == 7
    own = BIG.attn_kind(2)
    assert isinstance(own, moe.LatentKind) and own.kind == "latent"
    assert dataclasses.astuple(own) == (
        64, 513, 50_000.0, 1.0, None, 1024, 1024, 192, 64, 128, 0)
    assert len(dataclasses.astuple(BIG.attn_kind(1))) == 5
    man = manifest_lib.Manifest(REPO)
    for name, build in (("laguna-s21-1chip", serve_pattern.pattern_config),
                        ("ling3-flash-1chip", serve_hybrid.hybrid_config),
                        ("mimo-v25-1chip", serve_sink.sink_config)):
        cfg = build(man.config(name))
        assert cfg.attn_period and not cfg.lora_rescale
    ling = serve_hybrid.hybrid_config(man.config("ling3-flash-1chip"))
    sizes = ling.latent_sizes(5)
    assert (sizes.num_heads, sizes.q_lora_rank, sizes.kv_lora_rank,
            sizes.window, sizes.rope_base, sizes.index_topk) == (
        32, None, 512, None, 6_000_000.0, 0)


# -- through submit / step ------------------------------------------------------

@pytest.fixture(scope="module")
def served(params):
    """An engine that served six requests on two slots, with what it
    recorded; its outputs."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, n).tolist() for n in (5, 23, 37, 12,
                                                          30, 9)]
    eng = ServingEngine(TINY, params, slots=2, cache_len=96, chunk=4,
                        prefill_chunk=8, kv_block_size=4)
    seq0 = events.get_recorder().events_after(0)[0]
    rids = [eng.submit(p, 24) for p in prompts]
    out = eng.run()
    recorded = events.get_recorder().events_after(seq0)[1]
    return eng, prompts, [out[r] for r in rids], recorded


def test_the_engine_serves_the_references_greedy_tokens(params, served):
    """Every served token is the reference's first choice at its
    position (float32 on both sides: a near-tie apart)."""
    _, prompts, outs, _ = served
    cfg_file = cfg_file_of(TINY)
    for prompt, got in zip(prompts, outs):
        gaps = reference.served_gaps(params, cfg_file, prompt,
                                     got[len(prompt):])
        assert len(gaps) == 24 and gaps.max() < 1e-4


def test_steps_count_the_rings_walk_and_the_full_layers_choice(served):
    """``engine/step`` states ``kv_window_blocks`` by the kernel's own
    walk rule, bounded by the ring whatever the lanes hold, ``kv_bytes``
    at the full layers' row with its index key, and the rows the full
    layers scored and attended (a window layer sows none); a retired
    lane leaves pool and rings as it found them; the engine refuses
    what an engine with window layers refuses."""
    eng, prompts, outs, recorded = served
    for name, _, _, _, _, attrs in recorded:
        assert events.in_contract(name), name
        assert set(attrs or ()) <= events.contract_attrs(name), (name, attrs)
    steps = [e[5] for e in recorded
             if e[0] == "engine/step" and e[5].get("lanes")]
    assert steps
    per_lane = -(-(9 + 1) // 4) + 1           # window 9, blocks of 4
    for s in steps:
        assert 0 < s["kv_window_blocks"] <= per_lane * eng.slots
        assert s["kv_window_blocks"] <= s["kv_blocks"]
        assert s["kv_bytes"] == s["kv_blocks"] * 2 * 4 * (128 + 16) * 4
    assert any(s["kv_window_blocks"] < s["kv_blocks"] for s in steps)
    chose = [s for s in steps if s.get("rows_scored")]
    assert chose and all(
        0 < s["rows_selected"] <= s["rows_scored"] for s in chose)
    assert any(s["rows_selected"] < s["rows_scored"] for s in chose)
    assert {e[5]["pool"] for e in recorded
            if e[0] == "kv/alloc"} == {"full", "window"}
    pieces = [e[5] for e in recorded if e[0] == "prefill/piece"]
    assert any(0 < p["window_rows"] for p in pieces)
    eng._flush_stale_lanes()
    assert eng._kv_pool.free_blocks() == eng._kv_pool.n_blocks
    rids = [eng.submit(p, 24) for p in prompts]
    again = eng.run()
    assert [again[r] for r in rids] == outs
    with pytest.raises(ValueError, match="window layers"):
        eng.preload_prefix(prompts[1])
    with pytest.raises(ValueError, match="window layers"):
        ServingEngine(TINY, None, slots=2, cache_len=96,
                      draft_config=TINY, draft_params=None)


def test_the_decode_program_names_both_kinds_and_the_rings_write(params):
    """The scopes the device trace is read by: ``attn/latent`` around a
    full layer, ``attn/latent_window`` around a window layer, a ring's
    write under ``kv_pool/write/window``, the choice's stages in the
    full kind alone."""
    eng = ServingEngine(TINY, params, slots=2, cache_len=96, chunk=4,
                        prefill_chunk=8, kv_block_size=4)
    program = ServingEngine._decode_chunk
    while not hasattr(program, "lower"):
        program = program.__wrapped__
    lanes = jnp.zeros((2,), jnp.int32)
    text = program.lower(
        eng, eng._variables, eng._fresh_cache(2, grid=True), lanes,
        lanes.astype(jnp.uint32), lanes).as_text(debug_info=True)
    full = [ln for ln in text.splitlines() if "attn/latent/" in ln]
    window = [ln for ln in text.splitlines() if "attn/latent_window/" in ln]
    for scope, lines in (
            ("kv_pool/write/window/", window), ("attn/absorb/", window),
            ("attn/gate/", window), ("attn/q_latent/", window),
            ("kv_pool/write/", full), ("index_pool/write/", full),
            ("attn/index_score/", full), ("attn/select/", full),
            ("attn/sparse/", full), ("attn/gate/", full)):
        assert any(scope in ln for ln in lines), scope
    assert not any("kv_pool/write/window/" in ln for ln in full)
    for scope in ("attn/select/", "attn/index_", "index_pool/",
                  "attn/sparse/"):
        assert not any(scope in ln for ln in window), scope


# -- the arithmetic at the published sizes --------------------------------------

def test_the_pools_at_the_published_sizes_are_the_issues_arithmetic():
    """``dots3-note-1chip`` as the benchmark builds it, from shapes
    alone: 4.60 B parameters; three full layers' pools of 32 x 16,384
    rows at (640 + 128) x 2 B and six window layers' rings of 34 blocks
    a lane at 1,152 x 2 B; a piece's batch-1 cache at each kind's own
    row."""
    import flax.linen as nn

    from benchmark.harness import serve_latents

    with open(os.path.join(REPO, "benchmark", "configs",
                           "dots3-note-1chip.json")) as f:
        cfg_file = json.load(f)
    cfg = serve_latents.latents_config(cfg_file)
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
        nn.meta.unbox(jax.eval_shape(lambda: moe.MoeLmModel(cfg).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))))["params"])
    count = sum(x.size for x in jax.tree.leaves(shapes))
    assert abs(count - 4_603.4e6) < 0.1e6
    kw = {k: v for k, v in cfg_file["engine"].items() if k != "max_queue"}
    eng = ServingEngine(cfg, shapes, cast_params=False, prefill_chunk=1024,
                        prefill_budget=2048, **kw)
    assert (eng._window, eng._ring_blocks) == (513, 34)
    blocks = 1 + 32 * 1024          # the scratch block and 32 lanes' worth
    assert eng.kv_pool_parts() == {
        "latent_pool_bytes": 3 * blocks * 16 * 640 * 2,
        "index_pool_bytes": 3 * blocks * 16 * 128 * 2,
        "latent_ring_bytes": 6 * (1 + 32 * 34) * 16 * 1152 * 2}
    assert eng.kv_pool_bytes() == 2_656_862_208
    assert eng._kv_pool.bytes_per_block == 3 * 16 * 1536
    cache_1 = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        eng._cache_struct(1)))
    assert cache_1 == 16_384 * (3 * 1536 + 6 * 2304) + 9 * 4
