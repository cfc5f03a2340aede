"""Delta-rule linear-attention layers beside one latent-attention layer
(``bailing_hybrid``) at test size: ``MOE_PRESETS["ling_tiny"]`` through
the serving engine's own programs against the plain reference
``benchmark/references/ling_hybrid.py``.

The chunked scan against the token recurrence and the reference's layer
(lengths that are and are not multiples of the chunk, the decay at both
ends of its range); the state step's kernel against its ``jnp`` form; a
prompt prefilled whole, in pieces and in calls of several pieces with a
padded last piece (state, tail, first token, logits); prefill, insert
and paged decode against the reference's full forward pass, with a slot
taken by a second request after a longer first one and two lanes of
unequal length; faults planted in the state's handling are seen; what
the engine refuses beside recurrent layers; the new spans' names and
attrs are the contract's."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import serve_family, weights  # noqa: E402
from benchmark.references import ling_hybrid as reference  # noqa: E402
from tensorflow_train_distributed_tpu import serving  # noqa: E402
from tensorflow_train_distributed_tpu.models import layers, moe  # noqa: E402
from tensorflow_train_distributed_tpu.models.generate import (  # noqa: E402
    generate,
)
from tensorflow_train_distributed_tpu.ops import attention  # noqa: E402
from tensorflow_train_distributed_tpu.ops import (  # noqa: E402
    pallas_kernels as pk,
)
from tensorflow_train_distributed_tpu.runtime import events  # noqa: E402
from tensorflow_train_distributed_tpu.serving import (  # noqa: E402
    ServingEngine,
)

TINY = moe.MOE_PRESETS["ling_tiny"]
SEED = 2 ** 31 + 39
# float32 on both sides, logits of a few units: what is left is the
# order of float32 sums (chunks of 16 rows against a token at a time,
# tiles of rows against one softmax): a few 1e-6.  Any fault planted
# below moves a logit by 1e-3 or more.
TOL = 2e-5


def cfg_file_of(cfg, **over):
    """The configuration-file keys the reference reads, for a program
    config of this family (the source's own names)."""
    latent = next(k for k in cfg.attn_period if k.kind == "latent")
    out = {
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": latent.num_heads,
        "head_dim": cfg.head_dim, "layer_group_size": len(cfg.attn_period),
        "first_k_dense_replace": cfg.dense_layers,
        "rms_norm_eps": cfg.rms_epsilon,
        "kda_lower_bound": cfg.linear_decay_floor, "kda_safe_gate": True,
        "linear_silu": True, "num_kv_heads_for_linear_attn": 0,
        "short_conv_kernel_size": cfg.linear_conv,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_dim,
        "qk_rope_head_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim,
        "rope_theta": latent.rope_base, "rope_scaling": None,
        "num_experts_per_tok": cfg.top_k, "n_group": cfg.n_group,
        "topk_group": cfg.topk_group, "norm_topk_prob": True,
        "routed_scaling_factor": cfg.routed_scaling,
        "experts_offset": cfg.experts_offset,
    }
    out.update(over)
    return out


def seeded_decay(params, seed, lo=-9.0, hi=-1.0):
    """``params`` with every linear layer's ``decay/bias`` uniform on
    (lo, hi) and its ``a_log`` zero: decays a step from ~0.27 to
    ~0.9999, so a state carries across chunks and calls (the plain
    seeded rule's would forget in two tokens)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.key(seed), len(flat))

    def fill(path, leaf, key):
        names = [getattr(p, "key", "") for p in path]
        if names[-2:] == ["decay", "bias"]:
            return jax.random.uniform(key, leaf.shape, leaf.dtype, lo, hi)
        if names[-2:] == ["a_log", "bias"]:
            return jnp.zeros_like(leaf)
        return leaf

    return jax.tree_util.tree_unflatten(
        treedef, [fill(p, x, k) for (p, x), k in zip(flat, keys)])


@pytest.fixture(scope="module")
def params():
    plain = weights.make_params(serve_family.moe_param_shapes(TINY), SEED,
                                jnp.float32)
    return seeded_decay(plain, SEED)


def engine(params, **kw):
    base = dict(slots=2, chunk=4, cache_len=96, kv_block_size=4,
                prefill_chunk=8)
    base.update(kw)
    return ServingEngine(TINY, params, **base)


# -- the scan, the step ------------------------------------------------------

def _rows(seed, b, t, h, dk, dv, lo, hi):
    ks = jax.random.split(jax.random.key(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5,
            unit(jax.random.normal(ks[1], (b, t, h, dk))),
            jax.random.normal(ks[2], (b, t, h, dv)),
            -jax.random.uniform(ks[3], (b, t, h, dk), minval=lo, maxval=hi),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h))),
            jax.random.normal(ks[5], (b, h, dk, dv)))


@pytest.mark.parametrize("decay", [(0.0, 1e-3), (4.9, 5.0), (0.0, 5.0)],
                         ids=["none", "floor", "mixed"])
@pytest.mark.parametrize("rows", [1, 16, 37, 64, 100])
def test_chunked_scan_equals_the_token_recurrence(rows, decay):
    """Lengths that are and are not multiples of the chunk of 16, the
    log decay at both ends of (-5, 0) and across it, from a state that
    is not zero: float32, to a few 1e-7."""
    args = _rows(rows, 2, rows, 3, 16, 8, *decay)
    o, s = attention.delta_rule_scan(*args)
    o_t, s_t = attention.delta_rule_recurrence(*args)
    np.testing.assert_allclose(o, o_t, atol=1e-5, rtol=0)
    np.testing.assert_allclose(s, s_t, atol=1e-5, rtol=0)


def test_padding_rows_of_the_scan_are_the_identity():
    q, k, v, g, beta, s0 = _rows(3, 1, 24, 2, 16, 16, 0.0, 2.0)
    live = jnp.arange(24) < 19
    g_p = jnp.where(live[None, :, None, None], g, 0.0)
    b_p = jnp.where(live[None, :, None], beta, 0.0)
    o, s = attention.delta_rule_scan(q, k, v, g_p, b_p, s0)
    o_r, s_r = attention.delta_rule_scan(
        q[:, :19], k[:, :19], v[:, :19], g[:, :19], beta[:, :19], s0)
    np.testing.assert_allclose(s, s_r, atol=1e-6, rtol=0)
    np.testing.assert_allclose(o[:, :19], o_r, atol=1e-6, rtol=0)


@pytest.mark.parametrize("lanes, heads, d", [(3, 4, 128), (2, 2, 128)])
def test_state_step_kernel_equals_its_reference(lanes, heads, d):
    q, k, v, g, beta, s0 = _rows(lanes, lanes, 1, heads, d, d, 0.0, 5.0)
    step = (s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    s_k, o_k = pk.delta_state_step(*step, interpret=True)
    s_r, o_r = pk.delta_state_step_reference(*step)
    np.testing.assert_allclose(s_k, s_r, atol=1e-6, rtol=0)
    np.testing.assert_allclose(o_k, o_r, atol=1e-6, rtol=0)


@pytest.mark.parametrize("bias", [-20.0, 20.0, None],
                         ids=["no_decay", "floor", "seeded"])
@pytest.mark.parametrize("rows", [16, 37])
def test_the_layer_equals_the_references_layer(params, rows, bias):
    """``layers.DeltaAttention`` over a whole sequence (convolution,
    normalisation, gates, chunked scan, output norm, gate, projection)
    against the reference's token-by-token layer; ``decay/bias`` at
    +-20 puts every channel's decay at an end of its range."""
    w = params["layer_1"]["attention"]
    if bias is not None:
        w = dict(w, decay=dict(w["decay"], bias=jnp.full_like(
            w["decay"]["bias"], bias)))
    x = jax.random.normal(jax.random.key(rows), (1, rows, TINY.d_model))
    ours = layers.DeltaAttention(
        num_heads=4, head_dim=16, out_gate=True).apply({"params": w}, x)
    want = reference.linear_attention(x[0], w, cfg_file_of(TINY))
    np.testing.assert_allclose(ours[0], want, atol=1e-5, rtol=0)


# -- prefill: whole, in pieces, in calls of several pieces -------------------

def prefilled(eng, prompt):
    """``prompt`` through the engine's own piece schedule (its
    ``_advance_piece`` with nobody decoding): the finished batch-1
    cache's state leaves, and the first token."""
    eng.submit(prompt, 2)
    eng._stage_from_queue()
    (slot, task), = eng._staging.items()
    eng._compile_piece_shapes()
    calls = []
    with eng._ctx():
        while task.cursor < task.n_pieces:
            i = task.cursor
            k = eng._piece_counts[-1]
            if task.n_pieces - i < k:
                k = 1
            task.cache_1 = task.cache_1 or eng._fresh_cache(1)
            task.cache_1, task.first = eng._run_target_piece(
                task.cache_1, task.padded, task.piece, i, len(task.work),
                task.seed, task.resume, k)
            task.cursor += k
            calls.append(k)
    leaves = {eng._path_key(p): np.asarray(x) for p, x in
              jax.tree_util.tree_flatten_with_path(task.cache_1)[0]
              if eng._path_key(p)[-1] in serving._STATE_LEAVES}
    return leaves, int(task.first), calls


@pytest.fixture(scope="module")
def prompt():
    return np.random.default_rng(39).integers(3, 256, 37).tolist()


@pytest.fixture(scope="module")
def whole(params, prompt):
    """One piece of 48 rows, 11 of them padding."""
    return prefilled(engine(params, prefill_chunk=48), prompt)


@pytest.mark.parametrize("how, kw, calls", [
    ("pieces", dict(prefill_chunk=8), [1] * 5),
    ("calls", dict(prefill_chunk=8, prefill_budget=32), [4, 1]),
    ("calls_of_two", dict(prefill_chunk=8, prefill_budget=16), [2, 2, 1]),
])
def test_a_prompt_whole_in_pieces_and_in_calls_leaves_the_same_state(
        params, prompt, whole, how, kw, calls):
    """37 tokens as one padded piece of 48, as five pieces of 8 (the
    last with 3 rows of padding), as a call of four pieces and one
    piece, as two calls of two and one: the same state, tail and first
    token; six linear layers' leaves each."""
    leaves, first, ran = prefilled(engine(params, **kw), prompt)
    assert ran == calls
    want, want_first, want_ran = whole
    assert want_ran == [1] and first == want_first
    assert sorted(k[-1] for k in leaves).count("delta_state") == 6
    assert leaves.keys() == want.keys()
    for key in leaves:
        np.testing.assert_allclose(leaves[key], want[key], atol=TOL,
                                   rtol=0, err_msg=str(key))
    # and the tail is the last three REAL rows' projections, not zeros
    tail = next(v for k, v in leaves.items() if k[-1] == "conv_tail")
    assert tail.shape == (1, 3, 3 * 64) and np.abs(tail).min(axis=-1).all()


def program_logits(cfg, params, seq, n_prompt, *, piece=8, pieces_a_call=1,
                   cache_len=96, block=4, bend=None):
    """Float32 logits [len(seq), V] of the ENGINE's programs over
    ``seq``: the prompt in calls of ``pieces_a_call`` pieces of
    ``piece`` tokens on the batch-1 cache (the model call of
    ``_prefill_piece`` with its ``pad_rows``), ``_paged_insert`` into
    lane 1 of a two-lane grid whose lane 1 held ANOTHER request's state
    before (lane 0 idles), then one paged decode step a token,
    teacher-forced.  ``bend`` plants a fault."""
    eng = ServingEngine(cfg, params, slots=2, chunk=4, cache_len=cache_len,
                        kv_block_size=block, prefill_chunk=piece)
    variables = eng._variables
    call = piece * pieces_a_call

    def prefill(tokens, n):
        cache_1 = eng._fresh_cache(1)
        padded = np.zeros(-(-n // call) * call, np.int32)
        padded[:n] = tokens[:n]
        got = []
        for i in range(len(padded) // call):
            pad = max(0, (i + 1) * call - n)
            if bend == "padding_advances_the_state":
                pad = 0
            if bend == "tail_one_row_off":
                pad = pad + 1 if pad else 0
            cache_1 = jax.tree_util.tree_map_with_path(
                lambda p, leaf: jnp.full_like(leaf, pad)
                if eng._path_key(p)[-1] == "pad_rows" else leaf, cache_1)
            logits, vs = eng._prefill_model.apply(
                dict(variables, cache=cache_1),
                jnp.asarray(padded[None, i * call:(i + 1) * call]),
                mutable=["cache"])
            cache_1 = vs["cache"]
            got.append(np.asarray(logits[0]))
        return cache_1, np.concatenate(got)[:n]

    grid = eng._fresh_cache(2, grid=True)
    # a first, longer occupant of lane 1, retired before ours arrives
    other = np.random.default_rng(7).integers(3, 256, n_prompt + 9)
    kv0 = eng._kv_claim(0, other.tolist(), 4)
    grid = eng._paged_insert(grid, prefill(other, len(other))[0],
                             jnp.int32(1), eng._kv_table(kv0),
                             jnp.int32(0), jnp.int32(len(other)))
    eng._kv_release(kv0)
    grid = eng._reset_lanes(grid, jnp.asarray([False, True]))
    cache_1, pre = prefill(seq, n_prompt)
    kv = eng._kv_claim(1, [int(t) for t in seq[:n_prompt]],
                       len(seq) - n_prompt)
    before = jax.tree.map(jnp.copy, grid)
    cache = eng._paged_insert(grid, cache_1, jnp.int32(1),
                              eng._kv_table(kv), jnp.int32(0),
                              jnp.int32(n_prompt))
    if bend == "state_not_overwritten_at_insert":
        cache = jax.tree_util.tree_map_with_path(
            lambda p, new, old: old if eng._path_key(p)[-1]
            in serving._STATE_LEAVES else new, cache, before)

    @jax.jit
    def decode(cache, toks):
        def step(cache, t):
            logits, upd = eng._model.apply(
                dict(variables, cache=cache),
                jnp.stack([jnp.int32(3), t])[:, None],
                mutable=["cache", "moe_stats", "attn_stats"])
            return upd["cache"], logits[1, -1]
        return jax.lax.scan(step, cache, toks)

    _, dec = decode(cache, jnp.asarray(seq[n_prompt:]))
    return eng, pre, np.asarray(dec)


def reference_logits(params, cfg_file, seq):
    return np.asarray(reference.logits_at(
        params, cfg_file, [int(t) for t in seq], list(range(len(seq)))))


@pytest.fixture(scope="module")
def sequence():
    return np.random.default_rng(39).integers(3, 256, 61).astype(np.int32)


@pytest.fixture(scope="module")
def reference_run(params, sequence):
    return reference_logits(params, cfg_file_of(TINY), sequence)


@pytest.mark.parametrize("pieces_a_call", [1, 2])
def test_prefill_insert_and_paged_decode_agree_with_the_reference(
        params, sequence, reference_run, pieces_a_call):
    """21 prompt tokens in three pieces of 8 (or a call of two and a
    padded call), the insert over a slot that held a longer request's
    state, then 40 paged decode steps: every logit the reference's."""
    eng, pre, dec = program_logits(TINY, params, sequence, 21,
                                   pieces_a_call=pieces_a_call)
    assert eng._state_layers == 6 and not eng._share_prefix
    ours = np.concatenate([pre, dec])
    assert ours.shape == reference_run.shape == (61, 256)
    np.testing.assert_allclose(ours, reference_run, atol=TOL, rtol=0)
    assert (ours.argmax(-1) == reference_run.argmax(-1)).all()


@pytest.mark.parametrize("bend", ["state_not_overwritten_at_insert",
                                  "padding_advances_the_state",
                                  "tail_one_row_off"])
def test_a_fault_in_the_states_handling_is_seen(
        params, sequence, reference_run, bend):
    _, pre, dec = program_logits(TINY, params, sequence, 21, bend=bend)
    assert np.abs(dec - reference_run[21:]).max() > 1e-3


def test_the_fused_step_serves_what_the_reference_form_serves(
        params, monkeypatch):
    """The decode step's kernel (interpreted) in the slot grid's
    program: the tokens of the ``jnp`` form."""
    prompts = [list(range(3, 22)), list(range(30, 41))]
    plain = engine(params)
    rids = [plain.submit(p, 6) for p in prompts]
    want = plain.run()
    monkeypatch.setenv("TTD_FUSED_ATTN_INTERPRET", "1")
    fused = engine(params)
    rids_f = [fused.submit(p, 6) for p in prompts]
    got = fused.run()
    assert [got[r] for r in rids_f] == [want[r] for r in rids]


# -- the engine whole --------------------------------------------------------

@pytest.fixture(scope="module")
def served(params):
    """An engine that served six requests of unequal length on two
    slots (a long first one, then shorter ones into its slot), with
    what it recorded; its outputs."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(3, 256, n).tolist() for n in (41, 5, 23, 12,
                                                          30, 9)]
    eng = engine(params, prefill_budget=16)
    seq0 = events.get_recorder().events_after(0)[0]
    rids = [eng.submit(p, 24) for p in prompts]
    out = eng.run()
    recorded = events.get_recorder().events_after(seq0)[1]
    return eng, prompts, [out[r] for r in rids], recorded


def test_engine_serves_the_references_first_choice(params, served):
    """Every served token is the reference's own first choice given
    the tokens before it (float32 on both sides), and the engine
    equals ``generate()``."""
    _, prompts, outs, _ = served
    cfg_file = cfg_file_of(TINY)
    for prompt, got in zip(prompts, outs):
        gaps = reference.served_gaps(params, cfg_file, prompt,
                                     got[len(prompt):])
        assert gaps.shape == (24,) and float(gaps.max()) == 0.0
        want = np.asarray(generate(TINY, params, jnp.asarray([prompt]),
                                   24))[0].tolist()
        assert got == want


def test_a_reused_slot_serves_as_a_fresh_engine_does(params, served):
    """The same requests again through the same engine, whose slots all
    hold their last occupants' states: the same tokens."""
    eng, prompts, outs, _ = served
    rids = [eng.submit(p, 24) for p in reversed(prompts)]
    again = eng.run()
    assert [again[r] for r in rids] == list(reversed(outs))
    assert eng._kv_pool.free_blocks() == eng._kv_pool.n_blocks


def test_steps_and_pieces_count_the_state(served):
    """``engine/step`` states the bytes of state its live lanes hold,
    ``prefill/piece`` and ``prefill/dispatch`` the real rows scanned,
    ``kv/alloc`` the state's kind of pool; all the contract's."""
    eng, prompts, _, recorded = served
    for name, _, _, _, _, attrs in recorded:
        assert events.in_contract(name), name
        assert set(attrs or ()) <= events.contract_attrs(name), (name, attrs)
    # a lane and layer: 4 heads x 16 x 16 float32 and 3 rows of 3 x 64
    lane = 6 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert eng.state_pool_bytes() == 2 * lane
    steps = [e[5] for e in recorded
             if e[0] == "engine/step" and e[5].get("lanes")]
    assert steps and all(s["state_bytes"] == s["lanes"] * lane
                         for s in steps)
    # the rows walked in bytes: a block of the one latent layer's rows
    assert all(s["kv_bytes"] == s["kv_blocks"] * 4 * 128 * 4
               for s in steps)
    # a call's linear layers scan its real rows, each once: ``tokens``
    pieces = [e[5] for e in recorded if e[0] == "prefill/piece"]
    assert sum(p["tokens"] for p in pieces) == sum(map(len, prompts))
    assert any(p["pieces"] == 2 for p in pieces)
    sent = [e[5] for e in recorded if e[0] == "prefill/dispatch"]
    assert [d["tokens"] for d in sent] == [p["tokens"] for p in pieces]
    assert {e[5]["pool"] for e in recorded
            if e[0] == "kv/alloc"} == {"full", "state"}
    # the latent layer's rows are the pool's, the state is apart
    assert eng.kv_pool_bytes() == (1 + 2 * 24) * 4 * 128 * 4


def test_nothing_is_shared_exported_or_preloaded_beside_recurrent_layers(
        params):
    """The state at a prefix's end is not kept, so the engine takes no
    radix match, ships no KV (the receiver prefills), installs none,
    and ``preload_prefix`` raises with the reason."""
    eng = engine(params)
    prompt = list(range(3, 40))
    with pytest.raises(ValueError, match="recurrent layers"):
        eng.preload_prefix(prompt[:16])
    first = eng.submit(prompt, 6)
    done = eng.run()
    second = eng.submit(prompt, 6)
    while eng.pending():
        done.update(eng.serve_step())
        live = [s for s in eng._slot_states if s is not None]
        if live:
            meta, blob = eng.export_lane(live[0].request_id)
            assert meta["kind"] == "lane" and meta["kv"] is None
            assert blob == b""
    assert eng.kv_stats["prefix_hits"] == 0
    assert done[first] == done[second]
    assert eng.export_prefix_kv(prompt) is None
    assert eng.install_prefix_kv({"tokens": prompt[:16], "n": 16,
                                  "leaves": []}, b"") == 0
    assert eng.install_lane({"kv": {"tokens": prompt[:16], "n": 16,
                                    "leaves": []}}, b"x") == 0


@pytest.mark.parametrize("what, kw", [
    ("draft_config", dict(draft_config=TINY, speculative_k=2)),
    ("mesh=", dict(mesh=object())),
])
def test_the_engine_refuses_by_name_beside_recurrent_layers(params, what,
                                                            kw):
    if "draft_config" in kw:
        kw = dict(kw, draft_params=params)
    with pytest.raises(ValueError, match="recurrent layers") as err:
        engine(params, **kw)
    assert what in str(err.value)


def test_a_recurrent_draft_is_refused_too(params):
    dense = moe.MOE_PRESETS["glm_lite_tiny"]
    with pytest.raises(ValueError, match="recurrent layers"):
        ServingEngine(dense, {}, slots=1, cache_len=64, draft_config=TINY,
                      draft_params=params, speculative_k=2)


def test_kinds_of_layer_a_config_may_name():
    """A period's latent layers need the model's latent sizes, and a
    model with latent sizes has no MHA/GQA layer."""
    toks = jnp.zeros((1, 4), jnp.int32)
    for bad in (
            dataclasses.replace(TINY, kv_lora_rank=None),
            dataclasses.replace(TINY, attn_period=TINY.attn_period + (
                moe.AttnKind(num_heads=4),)),
            dataclasses.replace(TINY, attn_period=(
                type("RingKind", (moe.AttnKind,), {"kind": "ring"})(
                    num_heads=4),))):
        with pytest.raises(ValueError, match="kinds"):
            jax.eval_shape(moe.MoeLmModel(bad).init, jax.random.key(0),
                           toks)
    assert TINY.recurrent_layers == 6
    assert moe.MOE_PRESETS["ling3_flash"].recurrent_layers == 35
    assert moe.MOE_PRESETS["laguna_tiny"].recurrent_layers == 0


def test_latent_attention_without_a_query_rank_refuses_a_selection():
    attn = layers.LatentAttention(
        num_heads=2, q_lora_rank=None, kv_lora_rank=16, qk_nope_dim=8,
        qk_rope_dim=8, v_head_dim=8, index_heads=2, index_dim=8,
        index_topk=4)
    with pytest.raises(ValueError, match="q_lora_rank"):
        attn.init(jax.random.key(0), jnp.zeros((1, 8, 32)))
