"""The engine's pipelined step: one chunk dispatched ahead of the harvest,
admission staged under a budget behind it.

The contract: the pipeline changes WHEN the host learns about tokens and
when a prompt's pieces run, never the tokens — greedy output equals
``models.generate``'s token for token, a sampled request equals itself
served alone in a one-slot engine, speculative serving included, also
through stop-token trims whose decision lags one chunk.  The fast tier
proves the lookahead engages (overlapped-harvest counter moves), that a
long prompt's installments spread over steps while lanes commit, and
that a budget of 0 is refused.  The slow tier runs the parity matrix
plus the gateway streaming check.
"""

import argparse
import importlib.util
import json
import os
import urllib.request

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from tensorflow_train_distributed_tpu.models.generate import generate
from tensorflow_train_distributed_tpu.models.llama import (
    LLAMA_PRESETS,
    LlamaModel,
)
from tensorflow_train_distributed_tpu.serving import ServingEngine

CFG = LLAMA_PRESETS["llama_tiny"]


@pytest.fixture(scope="module")
def params():
    return LlamaModel(CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


def _ref(params, prompt, max_new):
    return np.asarray(generate(
        CFG, params, jnp.asarray([prompt], jnp.int32), max_new))[0].tolist()


def _serve(params, reqs, **kw):
    """Outputs in submission order; request ``i`` draws from seed ``i``."""
    eng = ServingEngine(CFG, params, **kw)
    ids = [eng.submit(p, m, seed=i) for i, (p, m) in enumerate(reqs)]
    out = eng.run()
    return [out[i] for i in ids], eng


def _alone(params, reqs, **kw):
    """Each request by itself in a one-slot engine, one after the
    other, same seeds: what a sampled request must produce wherever the
    scheduler puts it (no refill, no neighbour, nothing in flight when
    its prompt finishes)."""
    eng = ServingEngine(CFG, params, **dict(kw, slots=1))
    outs = []
    for i, (p, m) in enumerate(reqs):
        rid = eng.submit(p, m, seed=i)
        outs.append(eng.run()[rid])
    return outs


def _draft_kw():
    dcfg = LLAMA_PRESETS["llama_tiny_scan"]
    dparams = LlamaModel(dcfg).init(
        jax.random.PRNGKey(99), jnp.zeros((1, 4), jnp.int32))["params"]
    return dict(draft_config=dcfg, draft_params=dparams, speculative_k=3)


# ── tier-1 smoke: the lookahead engages ────────────────────────────────


def test_overlap_smoke(params):
    """Multi-chunk run: the lookahead must actually engage
    (overlapped-harvest counter > 0, ratio > 0) and serve generate()'s
    tokens."""
    reqs = [([1, 2, 3], 6), ([4, 5], 5)]
    out, eng = _serve(params, reqs, slots=2, cache_len=16, chunk=2,
                      prompt_buckets=(8,))
    assert eng.overlap_stats["chunks"] >= 3          # multi-chunk run
    assert eng.overlap_stats["overlapped_harvests"] > 0
    assert eng.overlap_ratio() > 0.0
    for got, (p, m) in zip(out, reqs):
        assert got == _ref(params, p, m)


# ── tier-1 smoke: staged admission engages ─────────────────────────────


def _instrument(eng):
    """Record the engine's device-dispatch order: 'p' per prefill
    piece, 'd' per decode chunk (instance attributes shadow the jitted
    methods — the established idiom from tests/test_serving.py)."""
    events = []
    orig_p, orig_d = eng._prefill_piece, eng._decode_chunk

    def p(variables, cache, toks, local, seed, count0):
        events.append("p")
        return orig_p(variables, cache, toks, local, seed, count0)

    def d(variables, cache, tok, seeds, counts):
        events.append("d")
        return orig_d(variables, cache, tok, seeds, counts)

    eng._prefill_piece, eng._decode_chunk = p, d
    return events


def test_interleave_smoke(params):
    """Decode-priority scheduling engages: a long admission (3 budget
    installments) does not run its prefill pieces back-to-back —
    decode chunks for the active lane are dispatched BETWEEN them, so
    the lane's inter-token gap is bounded by one installment instead
    of the whole prompt — and both requests get generate()'s tokens."""
    rng = np.random.default_rng(17)
    active = list(rng.integers(1, 200, 3))
    long_prompt = list(rng.integers(1, 200, 12))   # 3 pieces of 4
    eng = ServingEngine(CFG, params, slots=2, cache_len=64, chunk=2,
                        prefill_chunk=4)
    events = _instrument(eng)

    class Busy:                 # the newest program, never finished
        polls = 0

        def is_ready(self):
            Busy.polls += 1
            return False

    busy = Busy()
    eng._handle_of = lambda out: busy
    out = {}
    a = eng.submit(active, 16)
    out.update(eng.serve_step())
    out.update(eng.serve_step())
    mark = len(events)
    b = eng.submit(long_prompt, 4)                 # arrives mid-stream
    committed = []                  # the active lane's tokens, a step
    while eng.pending():
        out.update(eng.serve_step())
        committed.append(eng.progress().get(a))
    tail = events[mark:]
    assert eng.prefill_stats["staged_requests"] >= 1
    assert eng.prefill_stats["installments"] >= 3
    pieces = [i for i, e in enumerate(tail) if e == "p"]
    assert len(pieces) == 3                        # 12 tokens / 4-chunk
    between = tail[pieces[0] + 1:pieces[-1]]
    # The tentpole property: decode kept flowing through the admission.
    assert between.count("d") >= 2, tail
    # ... and the lane kept committing while the installments ran.
    assert committed[0] < committed[1] < committed[2], committed
    # ... behind a chunk in flight at every poll: never starved.
    assert busy.polls > 0 and eng.device_starved_s() == 0.0
    assert out[a] == _ref(params, active, 16)
    assert out[b] == _ref(params, long_prompt, 4)


# ── tier-1: a finished prefill's first token stays on the device ───────


def _cut_at(tokens, n_prompt, eos):
    """``tokens`` as an engine with ``eos_id=eos`` hands them back."""
    for i in range(n_prompt, len(tokens)):
        if tokens[i] == eos:
            return tokens[:i + 1]
    return tokens


@pytest.mark.parametrize("draft", [False, True], ids=["plain", "draft"])
@pytest.mark.parametrize("sampling", [False, True],
                         ids=["greedy", "sampled"])
def test_deferred_first_token_parity(params, sampling, draft):
    """A closed loop whose every slot refills mid-stream, among its
    requests one of ONE token, one of none, one of three pieces and one
    whose FIRST token is the stop token: the tokens are those of each
    request served alone with nothing in flight (and, greedy, those of
    ``generate()``), cut at the stop token.  The first tokens were read
    at harvests: every finished prompt left its pick on the device."""
    from tensorflow_train_distributed_tpu.runtime import events

    rng = np.random.default_rng(41)
    kw = dict(cache_len=64, chunk=3, prefill_chunk=4)
    if sampling:
        kw.update(temperature=0.8, top_k=20)
    if draft:
        kw.update(_draft_kw())
    reqs = [(list(rng.integers(1, 200, n)), m)
            for n, m in [(5, 6), (3, 9), (7, 5), (4, 12), (6, 1), (2, 0),
                         (11, 7), (3, 1)]]
    free = _alone(params, reqs, **kw)               # no stop token
    if not sampling:
        for got, (p, m) in zip(free, reqs):
            assert got == _ref(params, p, m)
    eos = free[2][len(reqs[2][0])]          # request 2 stops at its first
    want = [_cut_at(t, len(p), eos) for t, (p, _) in zip(free, reqs)]
    assert len(want[2]) == len(reqs[2][0]) + 1

    eng = ServingEngine(CFG, params, slots=2, eos_id=eos, **kw)
    rec = events.get_recorder()
    seq0 = rec.events_after(0)[0]
    out = {}
    ids = [eng.submit(p, m, seed=i) for i, (p, m) in enumerate(reqs[:3])]
    out.update(eng.serve_step())
    out.update(eng.serve_step())
    ids += [eng.submit(p, m, seed=3 + i)            # arrive mid-stream
            for i, (p, m) in enumerate(reqs[3:])]
    while eng.pending():
        out.update(eng.serve_step())
    assert [out[i] for i in ids] == want
    steps = [e[5] for e in rec.events_after(seq0)[1]
             if e[0] == "engine/step"]
    prompts = sum(1 for _, m in reqs if m)
    assert sum(s["first_deferred"] for s in steps) == prompts
    assert sum(s["committed"] for s in steps) == sum(
        len(t) - len(p) for t, (p, _) in zip(want, reqs))


def test_cancel_while_the_first_token_is_pending(params):
    """A lane cancelled between its insert and the harvest that would
    have read its first token: the lane is freed and refilled, its
    request never resolves, the others' tokens are ``generate()``'s."""
    rng = np.random.default_rng(43)
    reqs = [(list(rng.integers(1, 200, n)), m)
            for n, m in [(5, 7), (4, 9), (6, 5)]]
    eng = ServingEngine(CFG, params, slots=2, cache_len=32, chunk=3,
                        prompt_buckets=(8,))
    a, b = (eng.submit(p, m) for p, m in reqs[:2])
    out = dict(eng.serve_step())
    # Both prompts finished in that step and nothing was harvested yet.
    assert eng.progress() == {a: len(reqs[0][0]), b: len(reqs[1][0])}
    assert eng.cancel(a)
    c = eng.submit(*reqs[2])                 # takes the freed lane
    while eng.pending():
        out.update(eng.serve_step())
    assert a not in out and not eng.cancel(a)
    assert out[b] == _ref(params, *reqs[1])
    assert out[c] == _ref(params, *reqs[2])


def test_export_reads_a_pending_first_token(params):
    """Off the serving path the token is read where it is needed: a
    lane exported before any harvest ships its whole history, first
    token included, and serves on; a request of one token whose token
    is awaited is finished but for that read and exports as None."""
    prompt = [3, 1, 4, 1, 5]
    ref = _ref(params, prompt, 9)
    eng = ServingEngine(CFG, params, slots=2, cache_len=32, chunk=3,
                        prompt_buckets=(8,))
    rid = eng.submit(prompt, 9)
    eng.serve_step()
    assert eng.progress() == {rid: len(prompt)}          # pending
    meta, _ = eng.export_lane(rid)
    assert meta["kind"] == "lane"
    assert meta["tokens"] == ref[:len(prompt) + 1]
    assert meta["last_token"] == meta["tokens"][-1]
    assert (meta["remaining"], meta["count"]) == (8, 1)
    assert eng.progress() == {rid: len(prompt) + 1}
    one = eng.submit([2, 7, 1], 1)          # prefilled behind a chunk
    out = dict(eng.serve_step())
    assert eng.export_lane(one) is None     # awaited, or handed back
    while eng.pending():
        out.update(eng.serve_step())
    assert out[rid] == ref and out[one] == _ref(params, [2, 7, 1], 1)


def test_progress_shows_the_prompt_until_a_harvest_reads_the_first(params):
    """``progress()`` / ``snapshot()`` between a lane's insert and the
    harvest of its first chunk show the prompt alone; that harvest
    brings the first token and the chunk's together.  Later lanes: the
    prompt alone, or with its first token where a harvest found that
    ready before the lane's first chunk, then whole chunks."""
    prompt, chunk = [3, 1, 4, 1, 5], 3
    ref = _ref(params, prompt, 11)
    eng = ServingEngine(CFG, params, slots=2, cache_len=32, chunk=chunk,
                        prompt_buckets=(8,))
    rid = eng.submit(prompt, 11)
    eng.serve_step()                 # prefill, insert, the first dispatch
    assert eng.progress() == {rid: len(prompt)}
    assert eng.snapshot() == {rid: prompt}
    eng.serve_step()                 # harvests the lane's first chunk
    assert eng.progress() == {rid: len(prompt) + 1 + chunk}
    assert eng.snapshot() == {rid: ref[:len(prompt) + 1 + chunk]}
    late = [9, 2, 6]
    rid2 = eng.submit(late, 6)       # admitted behind a chunk in flight
    seen = []
    while eng.pending():
        done = eng.serve_step()
        seen.append(len(done.get(rid2) or eng.snapshot().get(rid2) or ()))
    seen = [n for n in seen if n]
    assert seen == sorted(seen) and seen[-1] == len(late) + 6
    assert set(seen) <= {len(late)} | {
        len(late) + 1 + i * chunk for i in range(3)} | {len(late) + 6}


def test_no_step_waits_for_the_newest_program_while_a_lane_decodes(
        params, monkeypatch):
    """THE invariant: with a lane decoding, a step that enqueues a
    prompt's last piece reads nothing of it.  No ``prefill/wait`` in a
    step that had lanes; ``first_deferred`` counts every finished
    prompt in the step that enqueued its last piece; a chunk is in
    flight across every return; and every scalar the host reads inside
    such a step (a first token) had run already when it was read: the
    read waited for nothing (the step's one wait is its
    ``decode/wait``, for a chunk with its successor queued behind)."""
    from jax._src import array as jax_array

    from tensorflow_train_distributed_tpu.runtime import events

    rng = np.random.default_rng(47)
    reqs = [(list(rng.integers(1, 200, n)), m)
            for n, m in [(4, 14), (5, 6), (9, 5), (3, 7), (6, 4), (2, 8)]]
    eng = ServingEngine(CFG, params, slots=2, cache_len=64, chunk=2,
                        prefill_chunk=4)
    watching, waited = [False], []
    value = jax_array.ArrayImpl.__dict__["_value"]

    def logged(self):
        if watching[0] and self.ndim == 0 and not self.is_ready():
            waited.append(self)
        return value.fget(self)

    monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(logged))
    rec = events.get_recorder()
    seq0 = rec.events_after(0)[0]
    out, ids, todo = {}, [eng.submit(*reqs[0])], list(reqs[1:])
    while eng.pending():
        watching[0] = any(s is not None for s in eng._slot_states)
        out.update(eng.serve_step())
        watching[0] = False
        if any(s is not None for s in eng._slot_states):
            assert eng._inflight is not None     # across the return
        if todo and len(out) + 2 > len(ids):     # keep two in the loop
            ids.append(eng.submit(*todo.pop(0)))
    for rid, (p, m) in zip(ids, reqs):
        assert out[rid] == _ref(params, p, m)
    assert waited == []
    _, evs = rec.events_after(seq0)
    steps = [e for e in evs if e[0] == "engine/step"]
    assert sum(s[5]["first_deferred"] for s in steps) == len(reqs)

    def inside(e, s):
        return e[4] == s[4] and s[2] <= e[2] <= s[2] + s[3]

    for s in steps:
        inserts = [e for e in evs
                   if e[0] == "prefill/insert" and inside(e, s)]
        assert s[5]["first_deferred"] == len(inserts)
    assert not [e for e in evs if e[0] == "prefill/wait"]
    # ... and the chunk a step waited for had its successor queued
    # behind it, but where every lane was to retire in it
    # (``_skip_eager_dispatch``: the successor would be garbage).
    waits = [e[5]["overlapped"] for e in evs if e[0] == "decode/wait"]
    assert waits.count(True) >= 10 > waits.count(False)


def test_prefill_budget_zero_is_refused(params):
    """0 used to select atomic admission; it is input from outside and
    is refused, by the engine and through the CLI's flag, with an error
    that names the staged default."""
    with pytest.raises(ValueError, match="one prefill piece a step"):
        ServingEngine(CFG, params, prefill_budget=0)
    spec = importlib.util.spec_from_file_location(
        "serve_under_test", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "serve.py"))
    serve = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve)
    parser = argparse.ArgumentParser()
    serve.add_engine_args(parser)
    args = parser.parse_args(["--config", "llama_tiny_sft",
                              "--checkpoint-dir", "unused",
                              "--prefill-budget", "0"])
    serve.load_decoder_params = lambda args, cfg, is_moe: (cfg, params)
    with pytest.raises(SystemExit, match="one prefill piece a step"):
        serve.build_engine(args, CFG, False, [])


# ── slow tier: the full parity matrix ──────────────────────────────────


@pytest.mark.slow
@pytest.mark.parametrize("sampling", [False, True],
                         ids=["greedy", "seeded-sampling"])
def test_overlap_parity_with_refills(params, sampling):
    """Six mixed-length requests through two slots (every slot refills;
    one request resolves at prefill, one is a no-op): greedy must equal
    generate(), sampled each request served alone."""
    rng = np.random.default_rng(0)
    kw = dict(slots=2, cache_len=64, chunk=4, prompt_buckets=(8, 16))
    if sampling:
        kw.update(temperature=0.8, top_k=20)
    reqs = [(list(rng.integers(1, 200, n)), m)
            for n, m in [(5, 6), (3, 9), (7, 4), (4, 12), (6, 1), (2, 0)]]
    on, eng = _serve(params, reqs, **kw)
    assert eng.overlap_stats["overlapped_harvests"] > 0
    if sampling:
        assert on == _alone(params, reqs, **kw)
    else:
        for got, (p, m) in zip(on, reqs):
            assert got == _ref(params, p, m)


@pytest.mark.slow
@pytest.mark.parametrize("sampling", [False, True],
                         ids=["greedy", "sampled"])
def test_overlap_parity_speculative(params, sampling):
    """Speculative rounds pipeline too: the device advances each
    slot's rng counter by its own ``emitted`` inside the round program,
    so round N+1 enqueues before round N's host copy exists — greedy
    must equal generate(), sampled each request served alone, and the
    budget trims must account for every token."""
    rng = np.random.default_rng(21)
    kw = dict(slots=2, cache_len=48, chunk=3, prompt_buckets=(8,),
              **_draft_kw())
    if sampling:
        kw.update(temperature=1.0, top_k=8)
    reqs = [(list(rng.integers(1, 200, n)), m)
            for n, m in [(5, 9), (3, 7), (6, 11), (4, 5)]]
    on, eng = _serve(params, reqs, **kw)
    assert eng.overlap_stats["overlapped_harvests"] > 0
    # The termination accounting (budget trims): every token past a
    # request's first came out of a round, and none twice.
    assert eng.spec_stats["emitted"] == sum(m - 1 for _, m in reqs)
    if sampling:
        assert on == _alone(params, reqs, **kw)
    else:
        for got, (p, m) in zip(on, reqs):
            assert got == _ref(params, p, m)


@pytest.mark.slow
def test_overlap_stop_token_mid_chunk_trims(params):
    """EOS landing mid-chunk: the stop decision lags one chunk (the
    successor is already in flight when the host sees the EOS), so the
    trim path must cut the overshoot — output identical to generate()
    truncated at the first EOS."""
    rng = np.random.default_rng(2)
    prompt = list(rng.integers(1, 200, 5))
    full = _ref(params, prompt, 12)
    continuation = full[5:]
    eos = continuation[3]                 # mid-chunk for chunk=4 below
    cut = continuation.index(eos) + 1
    other = list(rng.integers(1, 200, 4))  # keeps the batch contended
    eng = ServingEngine(CFG, params, slots=2, cache_len=64, chunk=4,
                        prompt_buckets=(8,), eos_id=eos)
    rid = eng.submit(prompt, 12)
    eng.submit(other, 10)
    assert eng.run()[rid] == full[:5 + cut]
    assert eng.overlap_stats["overlapped_harvests"] > 0


@pytest.mark.slow
def test_overlap_online_submission_and_cancel(params):
    """serve_step() online pattern: requests submitted
    mid-flight come out identical to generate(); cancel() mid-flight
    frees the slot (the in-flight chunk's tokens for it are trimmed by
    the rid guard) and the survivor finishes normally."""
    rng = np.random.default_rng(11)
    reqs = [(list(rng.integers(1, 200, n)), m)
            for n, m in [(5, 9), (3, 7), (6, 5)]]
    eng = ServingEngine(CFG, params, slots=2, cache_len=32, chunk=3,
                        prompt_buckets=(8,))
    out = {}
    ids = [eng.submit(*reqs[0])]
    out.update(eng.serve_step())
    ids.append(eng.submit(*reqs[1]))      # arrives mid-flight
    out.update(eng.serve_step())
    ids.append(eng.submit(*reqs[2]))
    while eng.pending():
        out.update(eng.serve_step())
    for rid, (p, m) in zip(ids, reqs):
        assert out[rid] == _ref(params, p, m), f"request {rid}"

    # Cancel mid-flight: the canceled id never resolves, the other
    # request is unaffected.
    long_rid = eng.submit(list(rng.integers(1, 200, 4)), 12)
    short = list(rng.integers(1, 200, 3))
    short_rid = eng.submit(short, 5)
    eng.serve_step()                      # both decoding, chunk in flight
    assert eng.cancel(long_rid)
    final = {}
    while eng.pending():
        final.update(eng.serve_step())
    assert long_rid not in final
    assert final[short_rid] == _ref(params, short, 5)


def _serve_mid_stream(params, reqs_active, long_req, tail_req,
                      **kw):
    """The interleave scenario: active lanes decoding, then a long
    prompt (several budget installments) plus a trailing short arrive
    mid-stream; everything runs to completion.  Returns outputs in
    submission order (request ``i`` draws from seed ``i``)."""
    eng = ServingEngine(CFG, params, **kw)
    out = {}
    ids = [eng.submit(p, m, seed=i) for i, (p, m) in
           enumerate(reqs_active)]
    out.update(eng.serve_step())
    out.update(eng.serve_step())
    ids.append(eng.submit(*long_req, seed=len(ids)))
    ids.append(eng.submit(*tail_req, seed=len(ids)))
    while eng.pending():
        out.update(eng.serve_step())
    return [out[i] for i in ids], eng


@pytest.mark.slow
@pytest.mark.parametrize("sampling", [False, True],
                         ids=["greedy", "seeded-sampling"])
def test_interleave_parity_mid_stream_long_admission(params, sampling):
    """A prompt spanning 3 budget installments admitted while other
    lanes are mid-stream: greedy equals generate(), sampled each
    request served alone."""
    rng = np.random.default_rng(23)
    kw = dict(slots=2, cache_len=64, chunk=3, prefill_chunk=4)
    if sampling:
        kw.update(temperature=0.8, top_k=20)
    active = [(list(rng.integers(1, 200, 4)), 14)]
    long_req = (list(rng.integers(1, 200, 12)), 6)   # 3 installments
    tail_req = (list(rng.integers(1, 200, 3)), 5)
    reqs = active + [long_req, tail_req]
    on, eng = _serve_mid_stream(params, active, long_req, tail_req, **kw)
    assert eng.prefill_stats["staged_requests"] >= 2
    assert eng.prefill_stats["installments"] >= 5
    if sampling:
        assert on == _alone(params, reqs, **kw)
    else:
        for got, (p, m) in zip(on, reqs):
            assert got == _ref(params, p, m)


@pytest.mark.slow
def test_interleave_parity_speculative(params):
    """Speculative serving: the DRAFT's prefill stages alongside the
    target's (same piece grid, budget-metered too) — outputs must
    equal generate()'s and every token be accounted for."""
    rng = np.random.default_rng(27)
    kw = dict(slots=2, cache_len=64, chunk=3, prefill_chunk=4,
              **_draft_kw())
    active = [(list(rng.integers(1, 200, 4)), 9)]
    long_req = (list(rng.integers(1, 200, 12)), 6)
    tail_req = (list(rng.integers(1, 200, 3)), 5)
    reqs = active + [long_req, tail_req]
    on, eng = _serve_mid_stream(params, active, long_req, tail_req, **kw)
    assert eng.spec_stats["emitted"] == sum(m - 1 for _, m in reqs)
    assert eng.prefill_stats["staged_requests"] >= 2
    for got, (p, m) in zip(on, reqs):
        assert got == _ref(params, p, m)


@pytest.mark.slow
def test_interleave_budget_groups_installments(params):
    """An explicit ``prefill_budget`` spanning two pieces advances two
    pieces per step: the 12-token admission takes 2 installments (and
    one decode chunk lands between them) — the knob actually meters
    tokens, not just pieces."""
    rng = np.random.default_rng(29)
    active = list(rng.integers(1, 200, 3))
    long_prompt = list(rng.integers(1, 200, 12))
    eng = ServingEngine(CFG, params, slots=2, cache_len=64, chunk=2,
                        prefill_chunk=4, prefill_budget=8)
    events = _instrument(eng)
    out = {}
    a = eng.submit(active, 12)
    out.update(eng.serve_step())
    out.update(eng.serve_step())
    mark = len(events)
    b = eng.submit(long_prompt, 4)
    while eng.pending():
        out.update(eng.serve_step())
    tail = events[mark:]
    pieces = [i for i, e in enumerate(tail) if e == "p"]
    # Budget 8 = two 4-token pieces per step: pieces 1+2 run together,
    # as ONE call of the piece program, piece 3 next step — exactly one
    # decode dispatch in between.
    assert len(pieces) == 2
    assert tail[pieces[0] + 1:pieces[1]].count("d") == 1, tail
    assert out[a] == _ref(params, active, 12)
    assert out[b] == _ref(params, long_prompt, 4)


@pytest.mark.slow
def test_prefix_reuse_under_overlap_with_midstream_refill(params):
    """preload_prefix + suffix-only prefill through the pipelined
    step, including a refill that
    hits the prefix cache MID-STREAM (submitted while chunks are in
    flight) — token-identical to the no-prefix path and to generate(),
    and the prefix must actually ENGAGE (suffix-sized pieces only)."""
    rng = np.random.default_rng(31)
    system = list(rng.integers(1, 200, 6))
    reqs = [(system + list(rng.integers(1, 200, 3)), 6),
            (system + list(rng.integers(1, 200, 5)), 5),
            (list(rng.integers(1, 200, 4)), 5),        # no prefix match
            (system + list(rng.integers(1, 200, 2)), 7)]

    def serve(preload):
        eng = ServingEngine(CFG, params, slots=2, cache_len=64,
                            chunk=4, prompt_buckets=(8, 16))
        if preload:
            eng.preload_prefix(system)
        pieces = []
        orig = eng._prefill_piece

        def counting(variables, cache, toks, local, seed, count0):
            pieces.append(int(toks.shape[1]))
            return orig(variables, cache, toks, local, seed, count0)

        eng._prefill_piece = counting
        out = {}
        ids = [eng.submit(p, m) for p, m in reqs[:2]]
        out.update(eng.serve_step())
        out.update(eng.serve_step())
        # Mid-stream arrivals: their refills hit the prefix cache
        # while a decode chunk is in flight.
        ids += [eng.submit(p, m) for p, m in reqs[2:]]
        while eng.pending():
            out.update(eng.serve_step())
        assert eng.overlap_stats["overlapped_harvests"] > 0
        return [out[i] for i in ids], pieces

    with_prefix, pieces = serve(True)
    no_prefix, _ = serve(False)
    assert with_prefix == no_prefix
    # Suffixes of 3/5/2 tokens and the 4-token non-match all fit the
    # 8-bucket; full prompts would have needed the 16-bucket twice.
    assert pieces == [8, 8, 8, 8], pieces
    for got, (p, m) in zip(with_prefix, reqs):
        assert got == _ref(params, p, m)


@pytest.mark.slow
def test_overlap_gateway_streaming_chunk_granular(params):
    """Gateway streaming over the pipelined engine: tokens must still
    arrive chunk-granularly (multiple NDJSON token chunks, not one
    final blob) and concatenate to exactly the batch-engine output."""
    from tensorflow_train_distributed_tpu.server import ServingGateway

    kw = dict(slots=2, cache_len=32, chunk=2, prompt_buckets=(8,))
    prompt, max_new = [3, 1, 4, 1], 10
    ref_eng = ServingEngine(CFG, params, **kw)
    ref_rid = ref_eng.submit(prompt, max_new)
    ref = ref_eng.run()[ref_rid]

    eng = ServingEngine(CFG, params, **kw)
    gw = ServingGateway(eng, host="127.0.0.1", port=0).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{gw.port}/v1/generate",
            data=json.dumps({"prompt": prompt, "max_new": max_new,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            lines = [json.loads(x) for x in r.read().splitlines() if x]
        assert "id" in lines[0]
        assert lines[-1] == {"done": True}
        token_chunks = [ln["tokens"] for ln in lines[1:-1]]
        # Chunk-granular delivery preserved: the 10 generated tokens
        # arrive across several commits (chunk=2), not one blob.
        assert len(token_chunks) >= 3, token_chunks
        streamed = [t for c in token_chunks for t in c]
        assert prompt + streamed == ref
        assert eng.overlap_stats["overlapped_harvests"] > 0
        # The driver-visible proof: the gateway's overlap gauge reads
        # the engine's ratio (> 0 once the lookahead engaged).
        with urllib.request.urlopen(
                f"http://127.0.0.1:{gw.port}/metrics", timeout=30) as r:
            prom = r.read().decode()
        line = [ln for ln in prom.splitlines()
                if ln.startswith("ttd_engine_overlap_ratio ")][0]
        assert float(line.split()[1]) > 0.0
        # ... and the starved-device gauge reads the engine's own sum
        # (nothing pending now, so it stands still).
        line = [ln for ln in prom.splitlines() if ln.startswith(
            "ttd_engine_device_starved_seconds ")][0]
        assert float(line.split()[1]) == pytest.approx(
            eng.device_starved_s()) and eng.device_starved_s() >= 0.0
    finally:
        gw.drain(timeout=30)
