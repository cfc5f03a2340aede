"""The consecutive pieces of one prompt as ONE ``_prefill_piece`` call
(``ServingEngine._advance_piece``): a prompt of four pieces prefilled
as 1+1+1+1, 2+2, 4 and 1+2+1 builds the same batch-1 cache and picks
the same first token in every family (dense, latent, latent with the
learned selection, window and full layers side by side); attention and
the selection over a multi-block query are the per-block calls to the
bit and a one-block query is the expression it was; the schedule under
a budget; every shape an engine may dispatch exists once it admitted a request.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import weights  # noqa: E402
from tensorflow_train_distributed_tpu.models import llama, moe  # noqa: E402
from tensorflow_train_distributed_tpu.ops import (  # noqa: E402
    attention as attention_ops,
)
from tensorflow_train_distributed_tpu.runtime import events  # noqa: E402
from tensorflow_train_distributed_tpu.runtime.lint import (  # noqa: E402
    compilecheck,
)
from tensorflow_train_distributed_tpu.serving import (  # noqa: E402
    ServingEngine,
)

PIECE = 16
FAMILIES = {
    "dense": llama.LLAMA_PRESETS["llama_tiny"],
    "latent": moe.MOE_PRESETS["glm_lite_tiny"],
    "latent-selection": moe.MOE_PRESETS["deepseek_v32_tiny"],
    "window-pattern": moe.MOE_PRESETS["laguna_tiny"],
    "latent-beside-linear": moe.MOE_PRESETS["ling_tiny"],
}


def _params(cfg):
    model = (moe.MoeLmModel if isinstance(cfg, moe.MoeConfig)
             else llama.LlamaModel)(cfg)
    boxed = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    return weights.make_params(weights.plain_shapes(boxed)["params"],
                               2 ** 33 + 38, jnp.float32)


def _engine(cfg, params, budget=4 * PIECE, **kw):
    return ServingEngine(cfg, params, slots=2, chunk=2, cache_len=128,
                         kv_block_size=8, prefill_chunk=PIECE,
                         prefill_budget=budget, **kw)


def _prefilled(eng, prompt, calls):
    """(batch-1 cache, first token) of ``prompt`` through the engine's
    own piece calls of ``calls`` pieces each."""
    m = len(prompt)
    padded = np.zeros((1, -(-m // PIECE) * PIECE), np.int32)
    padded[0, :m] = prompt
    cache, i = eng._fresh_cache(1), 0
    for k in calls:
        cache, first = eng._run_target_piece(cache, padded, PIECE, i, m,
                                             seed=5, k=k)
        i += k
    assert i * PIECE == padded.shape[1]
    return jax.tree.map(np.asarray, cache), int(first)


# -- (a) one prompt, four groupings of its pieces --------------------------

@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_prompt_prefills_the_same_however_its_pieces_are_called(
        family, walk_in_tiles):
    """Four pieces (the last ragged) at contexts of four cache tiles and
    four times ``index_topk`` / eight windows: the caches agree to
    float32 rounding (the matmuls are row-wise; each attention row
    walks its own piece's tiles), the indices exactly, and the first
    token is one token."""
    walks = walk_in_tiles(PIECE)
    cfg = FAMILIES[family]
    eng = _engine(cfg, _params(cfg))
    # (an engine whose attention chooses its rows runs one piece a
    # call, PERF.md PR 38; its program takes any count all the same)
    assert eng._piece_counts == (
        (1,) if getattr(cfg, "index_topk", 0) else (1, 4))
    prompt = np.random.default_rng(3).integers(2, 256, 3 * PIECE + 9)
    want_cache, want_first = _prefilled(eng, prompt, (1, 1, 1, 1))
    # (calls of two pieces are what an engine of a two-piece budget
    # compiles; the program takes any count)
    for calls in ((2, 2), (4,), (1, 2, 1)):
        cache, first = _prefilled(eng, prompt, calls)
        assert first == want_first, calls
        for (path, got), want in zip(
                jax.tree_util.tree_flatten_with_path(cache)[0],
                jax.tree.leaves(want_cache)):
            if got.dtype.kind == "i":
                np.testing.assert_array_equal(got, want, err_msg=str(path))
            else:
                np.testing.assert_allclose(got, want, atol=2e-5, rtol=0,
                                           err_msg=f"{calls} {path}")
    # every walk traced was one piece's queries long, whatever the call
    # (and one token long where the cache's shape was asked for)
    assert {q_len for q_len, _, _ in walks} - {1} == {PIECE}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_caches_shapes_are_traced_without_the_feed_forward_blocks(family):
    """``_cache_struct`` leaves the dense and routed feed-forward blocks
    out of its trace (they hold no cache leaf and hand on their input's
    shape): the struct is the whole model's, for the batch-1 prefill
    cache and for the slot grid's pools."""
    cfg = FAMILIES[family]
    eng = _engine(cfg, _params(cfg))
    for batch, grid, model in ((1, False, eng._prefill_model),
                               (eng.slots, True, eng._model)):
        whole = jax.eval_shape(
            lambda v: model.apply(v, jnp.zeros((batch, 1), jnp.int32),
                                  mutable=["cache"])[1]["cache"],
            eng._variables)
        got = eng._cache_struct(batch, grid=grid)
        assert jax.tree.structure(got) == jax.tree.structure(whole)
        assert jax.tree.leaves(got) == jax.tree.leaves(whole)


# -- (b) the ops: a multi-block query is its blocks, to the bit ------------

def _attention_case(q_len, window=None, keep=False, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (1, 4, q_len, 8), jnp.float32)
    cache = tuple(jax.random.normal(k, (1, 128, 2, 8), jnp.float32)
                  for k in ks[1:3])
    kept = (jax.random.bernoulli(ks[3], 0.7, (1, q_len, 128))
            if keep else None)

    def kv_of(rows):
        return [jnp.repeat(r, 2, axis=2).transpose(0, 2, 1, 3)
                for r in rows]

    return q, cache, kv_of, kept, window


@pytest.mark.parametrize("start", [0, 16, 40])
@pytest.mark.parametrize("kind", ["full", "window", "keep"])
def test_attention_over_blocks_is_the_blocks_attention_to_the_bit(
        kind, start):
    q, cache, kv_of, keep, window = _attention_case(
        64, window=8 if kind == "window" else None, keep=kind == "keep")

    def attend(q, keep, start, block=None):
        return jax.jit(lambda q, keep, start: (
            attention_ops.prefix_attention(
                q, cache, start, kv_of, tile=16, keep=keep, window=window,
                block=block)))(q, keep, jnp.array([start]))

    whole = attend(q, keep, start, block=16)
    apart = jnp.concatenate([
        attend(q[:, :, j:j + 16], None if keep is None
               else keep[:, j:j + 16], start + j)
        for j in range(0, 64, 16)], axis=2)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(apart))
    # and the walk is bounded where one call over all queries is not:
    # rows past a block's own tiles are not read by that block
    if kind == "full" and start == 0:
        poisoned = tuple(c.at[:, 16:].set(jnp.nan) for c in cache)
        first = attention_ops.prefix_attention(
            q, poisoned, jnp.array([0]), kv_of, tile=16, block=16)[:, :, :16]
        np.testing.assert_array_equal(np.asarray(first),
                                      np.asarray(whole[:, :, :16]))


@pytest.mark.parametrize("start", [0, 16, 40])
def test_scores_and_selection_over_blocks_are_the_blocks_to_the_bit(start):
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 16), jnp.float32)
    w = jax.random.normal(ks[1], (1, 64, 4), jnp.float32)
    keys = jax.random.normal(ks[2], (1, 128, 16), jnp.float32)
    at = jnp.array([start])

    def chosen(q, w, at, block=None):
        scores = attention_ops.prefix_index_scores(q, w, keys, at, tile=16,
                                                   block=block)
        return scores, attention_ops.select_top_rows(scores, 16, at,
                                                     tile=16, block=block)

    scores, keep = jax.jit(lambda q, w, at: chosen(q, w, at, 16))(q, w, at)
    apart = [jax.jit(chosen)(q[:, j:j + 16], w[:, j:j + 16], at + j)
             for j in range(0, 64, 16)]
    np.testing.assert_array_equal(
        np.asarray(scores), np.concatenate([np.asarray(s)
                                            for s, _ in apart], axis=1))
    np.testing.assert_array_equal(
        np.asarray(keep), np.concatenate([np.asarray(k)
                                          for _, k in apart], axis=1))
    # a query keeps what it sees up to 16 rows, and 16 of more
    seen = np.minimum(start + 1 + np.arange(64), 128)
    np.testing.assert_array_equal(np.asarray(keep).sum(-1)[0],
                                  np.minimum(seen, 16))


@pytest.mark.parametrize("block", [None, 16, 64])
def test_a_query_of_one_block_is_the_expression_it_was(block):
    """No more queries than a block: the jaxpr of the call without one
    (what an engine of one piece a call compiles)."""
    q, cache, kv_of, keep, _ = _attention_case(16, keep=True)
    at = jnp.array([24])

    def attend(block):
        return str(jax.make_jaxpr(lambda q, keep, at: (
            attention_ops.prefix_attention(
                q, cache, at, kv_of, tile=16, keep=keep, window=8,
                block=block)))(q, keep, at))

    def select(block):
        return str(jax.make_jaxpr(lambda s, at: (
            attention_ops.select_top_rows(s, 4, at, tile=16,
                                          block=block)))(keep * 1.0, at))

    assert attend(block) == attend(None)
    assert select(block) == select(None)


# -- (c) the schedule -------------------------------------------------------

@pytest.fixture(scope="module")
def dense():
    cfg = FAMILIES["dense"]
    return cfg, _params(cfg)


def _steps_and_calls(recorded):
    """[[pieces of each call] of each step that ran a piece]."""
    steps = []
    for name, ph, t0, dur, tid, attrs in recorded:
        if name == "engine/step" and attrs.get("piece_calls"):
            calls = [a["pieces"] for n, _, c0, _, ctid, a in recorded
                     if n == "prefill/piece" and ctid == tid
                     and t0 <= c0 <= t0 + dur]
            assert len(calls) == attrs["piece_calls"]
            assert sum(calls) == attrs["pieces"]
            steps.append(calls)
    return steps


def test_a_steps_budget_goes_to_the_head_request_as_one_call(dense):
    """Budget of four pieces, prompts of 5, 1 and 3 pieces behind a
    decoding lane: 4 | 1, 1, 1, 1 | 1 (a call is one piece or the four
    the engine compiled), the budget never exceeded, and the step's
    counters say so."""
    cfg, params = dense
    eng = ServingEngine(cfg, params, slots=4, chunk=2, cache_len=128,
                        prefill_chunk=PIECE, prefill_budget=4 * PIECE)
    rng = np.random.default_rng(0)
    busy = eng.submit(rng.integers(2, 256, 5).tolist(), 40)
    while not any(s is not None for s in eng._slot_states):
        eng.serve_step()
    seq0 = events.get_recorder().events_after(0)[0]
    before = eng.prefill_stats["installments"]
    prompts = [rng.integers(2, 256, n).tolist()
               for n in (4 * PIECE + 3, PIECE - 2, 2 * PIECE + 1)]
    rids = [eng.submit(p, 3) for p in prompts]
    out = eng.run()
    recorded = events.get_recorder().events_after(seq0)[1]
    steps = _steps_and_calls(recorded)
    assert steps == [[4], [1, 1, 1, 1], [1]]
    tokens = [a["prefill_tokens"] for n, *_, a in recorded
              if n == "engine/step" and a.get("piece_calls")]
    assert tokens == [4 * PIECE, 3 + PIECE - 2 + 2 * PIECE, 1]
    assert max(tokens) <= eng.prefill_budget
    assert eng.prefill_stats["installments"] - before == 6   # programs
    # the outputs are those of one piece a call
    plain = ServingEngine(cfg, params, slots=4, chunk=2, cache_len=128,
                          prefill_chunk=PIECE)
    assert plain._piece_counts == (1,)
    want = [plain.submit(p, 3) for p in prompts]
    got = plain.run()
    assert [out[r] for r in rids] == [got[r] for r in want]
    assert len(out[busy]) == 45


def test_with_no_lane_decoding_the_largest_compiled_call_is_taken(dense):
    """The waiver: nobody to delay, so a prompt of 7 pieces runs in one
    step, as a call of 4 and three of 1; a budget that is no power of
    two of pieces rounds down (3 pieces: calls of 2 and 1)."""
    cfg, params = dense
    eng = ServingEngine(cfg, params, slots=2, chunk=2, cache_len=128,
                        prefill_chunk=PIECE, prefill_budget=4 * PIECE)
    seq0 = events.get_recorder().events_after(0)[0]
    eng.submit(list(range(2, 2 + 6 * PIECE + 5)), 2)
    eng.run()
    assert _steps_and_calls(
        events.get_recorder().events_after(seq0)[1]) == [[4, 1, 1, 1]]
    three = ServingEngine(cfg, params, slots=2, chunk=2, cache_len=128,
                          prefill_chunk=PIECE, prefill_budget=3 * PIECE)
    assert three._piece_counts == (1, 2)
    busy = three.submit([5, 6, 7], 30)
    while not any(s is not None for s in three._slot_states):
        three.serve_step()
    seq0 = events.get_recorder().events_after(0)[0]
    three.submit(list(range(2, 2 + 4 * PIECE + 5)), 2)
    three.run()
    assert _steps_and_calls(
        events.get_recorder().events_after(seq0)[1]) == [[2, 1], [2]]
    del busy


def test_a_draft_follows_the_same_rule(dense):
    """A speculative engine's draft cache is built by the same calls
    after the target's, and greedy output stays the target's."""
    cfg, params = dense
    prompt = np.random.default_rng(2).integers(2, 256, 3 * PIECE + 4).tolist()
    eng = ServingEngine(cfg, params, slots=2, chunk=2, cache_len=128,
                        prefill_chunk=PIECE, prefill_budget=4 * PIECE,
                        draft_config=cfg, draft_params=params,
                        speculative_k=2)
    seq0 = events.get_recorder().events_after(0)[0]
    rid = eng.submit(prompt, 6)
    out = eng.run()[rid]
    calls = [(a["pieces"], a["draft"]) for n, *_, a in
             events.get_recorder().events_after(seq0)[1]
             if n == "prefill/dispatch"]
    assert calls == [(4, 0), (4, 1)]
    plain = ServingEngine(cfg, params, slots=2, chunk=2, cache_len=128,
                          prefill_chunk=PIECE)
    rid = plain.submit(prompt, 6)
    assert plain.run()[rid] == out


# -- (d) no shape is first met by a request --------------------------------

def _piece_signatures():
    return sum(len(group["sigs"])
               for (site, _), group in compilecheck._GROUPS.items()
               if site.endswith("ServingEngine._prefill_piece"))


def test_every_piece_shape_exists_once_a_request_was_admitted(dense):
    """The guard for a benchmark window that must compile nothing: the
    first admission, of a prompt of one piece, adds one signature a
    piece count, and traffic that meets every count (4 and 1 pieces a
    call) adds none.  Construction runs nothing (parameters may be
    shapes alone), and an engine of one piece a call compiles its one
    shape on first use, as it did."""
    cfg, params = dense
    assert compilecheck.armed()
    before = _piece_signatures()
    eng = ServingEngine(cfg, params, slots=2, chunk=2, cache_len=128,
                        prefill_chunk=PIECE, prefill_budget=4 * PIECE)
    ServingEngine(cfg, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params),
        slots=2, chunk=2, cache_len=128, prefill_chunk=PIECE,
        prefill_budget=4 * PIECE, cast_params=False)
    assert _piece_signatures() == before
    eng.submit([3, 4, 5], 2)
    eng.run()
    warmed = _piece_signatures()
    assert warmed - before == len(eng._piece_counts) == 2
    seq0 = events.get_recorder().events_after(0)[0]
    eng.submit(list(range(2, 2 + 6 * PIECE + 5)), 2)
    eng.run()
    met = {a["pieces"] for n, *_, a in
           events.get_recorder().events_after(seq0)[1]
           if n == "prefill/piece"}
    assert met == {1, 4}
    assert _piece_signatures() == warmed
    plain = ServingEngine(cfg, params, slots=2, chunk=2, cache_len=128,
                          prefill_chunk=PIECE)
    plain.submit([3, 4, 5], 2)
    plain.run()
    assert _piece_signatures() == warmed + 1


# -- (e) the walk as one kernel ---------------------------------------------

@pytest.mark.parametrize("family, layers", [
    ("dense", 2), ("window-pattern", 5), ("latent", 0)])
def test_an_engine_serves_the_same_tokens_with_the_walk_as_the_kernel(
        family, layers, flash_interpreted):
    """The pieces' attention through ``prefix_flash_attention``
    (interpreted, a float32 program at test size, blocks of half a
    piece against tiles of a piece) serves the tokens the XLA walk
    serves, calls of one piece and of four alike, and ``prefill/piece``
    says how many of the call's attention layers ran it: every plain
    K/V layer, none of a latent family's."""
    cfg = FAMILIES[family]
    params = _params(cfg)
    assert cfg.num_layers == layers or family == "latent"
    rng = np.random.default_rng(11)
    prompts = [rng.integers(2, 256, n).tolist()
               for n in (4 * PIECE + 5, PIECE - 3, 2 * PIECE)]

    def serve():
        eng = _engine(cfg, params)
        seq0 = events.get_recorder().events_after(0)[0]
        rids = [eng.submit(p, 6) for p in prompts]
        out = eng.run()
        pieces = [a for n, *_, a in
                  events.get_recorder().events_after(seq0)[1]
                  if n == "prefill/piece"]
        return [out[r] for r in rids], pieces

    want, walked = serve()
    assert {p["flash_layers"] for p in walked} == {0}
    traced = flash_interpreted(PIECE // 2, PIECE)
    got, pieces = serve()
    assert got == want
    assert set(traced) == ({PIECE, 4 * PIECE} if layers else set())
    assert [p["pieces"] for p in pieces] == [p["pieces"] for p in walked]
    assert {p["pieces"] for p in pieces} == {1, 4}
    assert {p["flash_layers"] for p in pieces} == {layers}


@pytest.mark.parametrize("family, layers, calls", [
    ("latent", 3, {(PIECE, False), (4 * PIECE, False)}),
    ("latent-selection", 3, {(PIECE, True)}),
    ("latent-beside-linear", 1, {(PIECE, False), (4 * PIECE, False)}),
    ("dense", 0, set()), ("window-pattern", 0, set())])
def test_an_engine_serves_the_same_tokens_with_the_latent_walk_as_the_kernel(
        family, layers, calls, latent_interpreted):
    """The pieces' attention over latent rows through
    ``prefix_flash_latent`` (interpreted, a float32 program at test
    size, blocks of half a piece against tiles of a piece) serves the
    tokens the XLA walk serves, and ``prefill/piece`` says how many of
    the call's attention layers ran it: every latent layer (the one of
    seven beside the linear layers; under the learned choice, whose
    engine runs one piece a call, ``keep`` goes in), none of a family
    of plain K/V rows, and none on the CPU path."""
    cfg = FAMILIES[family]
    params = _params(cfg)
    rng = np.random.default_rng(12)
    prompts = [rng.integers(2, 256, n).tolist()
               for n in (4 * PIECE + 5, PIECE - 3, 2 * PIECE)]

    def serve():
        eng = _engine(cfg, params)
        seq0 = events.get_recorder().events_after(0)[0]
        rids = [eng.submit(p, 6) for p in prompts]
        out = eng.run()
        pieces = [a for n, *_, a in
                  events.get_recorder().events_after(seq0)[1]
                  if n == "prefill/piece"]
        return [out[r] for r in rids], pieces

    want, walked = serve()
    assert {p["flash_layers"] for p in walked} == {0}
    traced = latent_interpreted(PIECE // 2, PIECE)
    got, pieces = serve()
    assert got == want
    assert set(traced) == calls
    assert [p["pieces"] for p in pieces] == [p["pieces"] for p in walked]
    assert {p["flash_layers"] for p in pieces} == {layers}
