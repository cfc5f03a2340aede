"""Continuous-batching serving engine tests.

North star: engine output is TOKEN-IDENTICAL to ``generate()`` greedy
for every request, regardless of slot contention, arrival order, prompt
bucketing, or mid-flight refills — the engine changes *when* work
happens, never the math (per-slot cache positions give each request the
same RoPE/mask view it would have alone).
"""

import dataclasses

import pytest

pytestmark = pytest.mark.slow  # decode-scan compiles: full-suite tier

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


def test_engine_matches_generate_with_refills(params):
    """Six requests through two slots: every slot refills at least once,
    prompt lengths span two buckets, one request finishes at prefill
    (max_new=1) and one is a no-op (max_new=0)."""
    rng = np.random.default_rng(0)
    eng = ServingEngine(CFG, params, slots=2, cache_len=64, chunk=4,
                        prompt_buckets=(8, 16))
    reqs = [(list(rng.integers(1, 200, n)), m)
            for n, m in [(5, 6), (3, 9), (7, 4), (4, 12), (6, 1), (2, 0)]]
    ids = [eng.submit(p, m) for p, m in reqs]
    out = eng.run()
    for rid, (p, m) in zip(ids, reqs):
        assert out[rid] == _ref(params, p, m), f"request {rid}"


def test_engine_single_slot_serializes_correctly(params):
    rng = np.random.default_rng(1)
    eng = ServingEngine(CFG, params, slots=1, cache_len=32, chunk=3,
                        prompt_buckets=(8,))
    reqs = [(list(rng.integers(1, 200, 4)), 5),
            (list(rng.integers(1, 200, 6)), 7)]
    ids = [eng.submit(p, m) for p, m in reqs]
    out = eng.run()
    for rid, (p, m) in zip(ids, reqs):
        assert out[rid] == _ref(params, p, m)


def test_eos_stops_early(params):
    """eos_id cut: the engine's output is generate()'s, truncated right
    after the first EOS occurrence in the continuation."""
    rng = np.random.default_rng(2)
    prompt = list(rng.integers(1, 200, 5))
    full = _ref(params, prompt, 12)
    continuation = full[len(prompt):]
    eos = continuation[3]  # stop after the 4th generated token (or
    #                        earlier if it repeats before index 3)
    cut = continuation.index(eos) + 1
    eng = ServingEngine(CFG, params, slots=2, cache_len=64, chunk=4,
                        prompt_buckets=(8,), eos_id=eos)
    rid = eng.submit(prompt, 12)
    out = eng.run()
    assert out[rid] == full[:len(prompt) + cut]


def test_run_is_reentrant(params):
    """A second submit/run cycle on the same engine reuses the compiled
    programs and stale slot caches without contamination."""
    rng = np.random.default_rng(3)
    eng = ServingEngine(CFG, params, slots=2, cache_len=32, chunk=4,
                        prompt_buckets=(8,))
    p1 = list(rng.integers(1, 200, 5))
    rid1 = eng.submit(p1, 6)
    assert eng.run()[rid1] == _ref(params, p1, 6)
    p2 = list(rng.integers(1, 200, 7))
    rid2 = eng.submit(p2, 5)
    assert eng.run()[rid2] == _ref(params, p2, 5)


def test_validation_errors(params):
    eng = ServingEngine(CFG, params, slots=2, cache_len=32,
                        prompt_buckets=(8,))
    with pytest.raises(ValueError, match="empty"):
        eng.submit([], 4)
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit([1] * 30, 10)           # prompt+new > cache_len
    with pytest.raises(ValueError, match="bucket"):
        eng.submit([1] * 20, 2)            # no bucket >= 20
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1, 2], -1)
    wcfg = dataclasses.replace(CFG, sliding_window=8)
    with pytest.raises(ValueError, match="sliding_window"):
        ServingEngine(wcfg, params)
    # kv_cache_int8 configs SERVE through the engine since PR 11 (the
    # batch-1 cache and the pool quantize with the linear recipe) — the
    # old rejection must stay lifted.
    icfg = dataclasses.replace(CFG, kv_cache_int8=True)
    eng8 = ServingEngine(icfg, params, slots=2, cache_len=32,
                         prompt_buckets=(8,))
    assert eng8.kv_cache_int8 and eng8.kv_pool_bytes() > 0


def test_slot_decode_layer_guards():
    from tensorflow_train_distributed_tpu.models import layers as L

    x = jnp.zeros((2, 4, 16))
    attn = L.MultiHeadAttention(num_heads=2, head_dim=8, slot_decode=True)
    with pytest.raises(ValueError, match="decode=True"):
        attn.init(jax.random.PRNGKey(0), x)
    attn = L.MultiHeadAttention(num_heads=2, head_dim=8, decode=True,
                                cache_len=8, slot_decode=True, window=4)
    with pytest.raises(ValueError, match="LINEAR"):
        attn.init(jax.random.PRNGKey(0), x)


def test_slot_decode_without_decode_raises_under_scan_layers():
    """The guard must fire on the depth-scanned path too (slot_decode
    threads through both _ScannedBlock branches)."""
    cfg = dataclasses.replace(CFG, scan_layers=True)
    model = LlamaModel(cfg, slot_decode=True)  # decode left False
    with pytest.raises(ValueError, match="decode=True"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_moe_family_matches_generate():
    """One engine serves the MoE decoder family too (same dispatch rule
    as generate): token-identical under contention and refill."""
    from tensorflow_train_distributed_tpu.models import moe

    cfg = moe.MOE_PRESETS["moe_tiny"]
    rng = np.random.default_rng(5)
    params = moe.MoeLmModel(cfg).init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ServingEngine(cfg, params, slots=2, cache_len=32, chunk=3,
                        prompt_buckets=(8,))
    reqs = [(list(rng.integers(1, cfg.vocab_size, n)), m)
            for n, m in [(4, 6), (6, 5), (3, 8)]]
    ids = [eng.submit(p, m) for p, m in reqs]
    out = eng.run()
    for rid, (p, m) in zip(ids, reqs):
        ref = np.asarray(generate(
            cfg, params, jnp.asarray([p], jnp.int32), m))[0].tolist()
        assert out[rid] == ref, f"moe request {rid}"


@pytest.mark.parametrize("tile", [None, 4], ids=["one-tile", "tile-4"])
def test_sharded_engine_matches_unsharded(params, mesh_2d, walk_in_tiles,
                                          tile):
    """Tensor-parallel serving: under a data×tensor mesh the engine's
    logical constraints shard weights/cache over ``tensor`` (GSPMD
    inserts the collectives) and the outputs stay token-identical.
    ``tile-4``: the constraints sit inside the tile loop of the cache
    walk (up to all eight tiles of the cache of 32)."""
    reqs = [([3, 1, 4, 1, 5], 6), ([2, 7, 1], 8)]

    def serve(mesh):
        eng = ServingEngine(CFG, params, slots=2, cache_len=32, chunk=4,
                            prompt_buckets=(8,), mesh=mesh)
        ids = [eng.submit(p, n) for p, n in reqs]
        out = eng.run()
        return [out[i] for i in ids]

    want = serve(None)
    walk_in_tiles(tile)
    assert serve(mesh_2d) == want


def test_expert_sharded_moe_serving_matches_unsharded():
    """MoE engine serving under a data×expert mesh: the dense dispatch
    einsums shard over experts via GSPMD during decode too — outputs
    token-identical to unsharded serving."""
    from tensorflow_train_distributed_tpu.models import moe
    from tensorflow_train_distributed_tpu.runtime.mesh import (
        MeshConfig, build_mesh,
    )

    cfg = moe.MOE_PRESETS["moe_tiny"]
    params = moe.MoeLmModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    reqs = [([5, 6, 7], 5), ([9, 8, 7, 6], 4)]

    def serve(mesh):
        eng = ServingEngine(cfg, params, slots=2, cache_len=32, chunk=3,
                            mesh=mesh)
        ids = [eng.submit(p, m) for p, m in reqs]
        out = eng.run()
        return [out[i] for i in ids]

    mesh = build_mesh(MeshConfig(data=2, expert=4))
    assert serve(None) == serve(mesh)


def test_int8_engine_matches_int8_generate(params):
    """int8 weight-only serving through the engine: token-identical to
    generate(quant_scales=...) — the quant interceptor rewrites the
    same Dense call sites in both paths."""
    from tensorflow_train_distributed_tpu.models import quant

    qparams, scales = quant.quantize_params(params)
    rng = np.random.default_rng(6)
    eng = ServingEngine(CFG, qparams, slots=2, cache_len=32, chunk=3,
                        prompt_buckets=(8,), quant_scales=scales)
    reqs = [(list(rng.integers(1, 200, n)), m)
            for n, m in [(4, 6), (6, 5), (3, 7)]]
    ids = [eng.submit(p, m) for p, m in reqs]
    out = eng.run()
    for rid, (p, m) in zip(ids, reqs):
        ref = np.asarray(generate(
            CFG, qparams, jnp.asarray([p], jnp.int32), m,
            quant_scales=scales))[0].tolist()
        assert out[rid] == ref, f"int8 request {rid}"
    # Pairing contract: int8 params without scales fail loudly.
    with pytest.raises(ValueError, match="quant_scales"):
        ServingEngine(CFG, qparams, slots=2, cache_len=32,
                      prompt_buckets=(8,))


class TestSampling:
    def test_topk1_equals_greedy(self, params):
        """temperature with top_k=1 collapses to argmax — an EXACT pin
        on the sampling path without needing to match any rng stream."""
        rng = np.random.default_rng(8)
        reqs = [(list(rng.integers(1, 200, n)), m)
                for n, m in [(4, 6), (6, 5)]]
        outs = {}
        for name, kw in (("greedy", {}),
                         ("topk1", dict(temperature=5.0, top_k=1))):
            eng = ServingEngine(CFG, params, slots=2, cache_len=32,
                                chunk=3, prompt_buckets=(8,), **kw)
            ids = [eng.submit(p, m) for p, m in reqs]
            out = eng.run()
            outs[name] = [out[i] for i in ids]
        assert outs["greedy"] == outs["topk1"]

    def test_sampled_stream_is_placement_independent(self, params):
        """A request's sampled tokens depend only on (params, prompt,
        seed) — not on slot placement, neighbors, or chunk boundaries:
        the rng key is fold_in(key(seed), tokens_drawn)."""
        rng = np.random.default_rng(9)
        prompt = list(rng.integers(1, 200, 5))
        other = list(rng.integers(1, 200, 7))

        def serve_alone():
            eng = ServingEngine(CFG, params, slots=1, cache_len=32,
                                chunk=5, prompt_buckets=(8,),
                                temperature=0.8, top_k=20)
            rid = eng.submit(prompt, 8, seed=123)
            return eng.run()[rid]

        def serve_contended():
            eng = ServingEngine(CFG, params, slots=2, cache_len=32,
                                chunk=3, prompt_buckets=(8,),
                                temperature=0.8, top_k=20)
            rid = eng.submit(prompt, 8, seed=123)
            eng.submit(other, 10, seed=7)
            return eng.run()[rid]

        alone = serve_alone()
        contended = serve_contended()
        assert alone == contended
        assert serve_contended() == contended  # reproducible

    def test_sampling_validation(self, params):
        with pytest.raises(ValueError, match="temperature"):
            ServingEngine(CFG, params, temperature=-0.1)
        with pytest.raises(ValueError, match="top_k/top_p"):
            ServingEngine(CFG, params, top_k=5)  # greedy + filter
        with pytest.raises(ValueError, match="top_p"):
            ServingEngine(CFG, params, temperature=1.0, top_p=1.5)
        eng = ServingEngine(CFG, params, slots=1, cache_len=32,
                            prompt_buckets=(8,))
        # Out-of-range seeds fail at submit, not mid-run (an
        # OverflowError inside run() would abort in-flight requests).
        with pytest.raises(ValueError, match="seed"):
            eng.submit([1, 2], 3, seed=-1)
        with pytest.raises(ValueError, match="seed"):
            eng.submit([1, 2], 3, seed=2 ** 32)


@pytest.mark.parametrize("tile", [None, 4], ids=["one-tile", "tile-4"])
def test_chunked_prefill_matches_generate(params, walk_in_tiles, tile):
    """prefill_chunk: prompts run through one per-piece program in
    fixed-size pieces (lengths off and ON the piece boundary, one
    shorter than a piece, one of three pieces) — token-identical to
    generate().  ``tile-4``: a tile is a piece, so the second and third
    pieces of a prompt walk two and three tiles of the cache of eight,
    and decode steps up to all of them."""
    rng = np.random.default_rng(12)
    reqs = [(list(rng.integers(1, 200, n)), m)
            for n, m in [(5, 6), (8, 5), (3, 7), (4, 4), (11, 5)]]
    want = [_ref(params, p, m) for p, m in reqs]   # whole-cache attention
    walks = walk_in_tiles(tile)
    eng = ServingEngine(CFG, params, slots=2, cache_len=32, chunk=3,
                        prefill_chunk=4)
    ids = [eng.submit(p, m) for p, m in reqs]
    out = eng.run()
    assert [out[rid] for rid in ids] == want
    assert set(walks) == ({(4, 4, 32), (1, 4, 32)} if tile else set())


def test_chunked_prefill_takes_over_bucket_prompts(params):
    """With prefill_chunk set, prompts longer than every bucket (the
    feature's whole point) are accepted and still match generate()."""
    rng = np.random.default_rng(13)
    prompt = list(rng.integers(1, 200, 12))  # > largest bucket (8)
    eng = ServingEngine(CFG, params, slots=1, cache_len=32, chunk=3,
                        prefill_chunk=4, prompt_buckets=(8,))
    rid = eng.submit(prompt, 5)
    assert eng.run()[rid] == _ref(params, prompt, 5)
    # Empty-bucket construction (cache_len below every default bucket)
    # works too when chunked prefill carries the load.
    eng2 = ServingEngine(CFG, params, slots=1, cache_len=16,
                         prefill_chunk=4)
    rid2 = eng2.submit(prompt, 3)
    assert eng2.run()[rid2] == _ref(params, prompt, 3)


def test_chunked_prefill_rejected_for_moe():
    from tensorflow_train_distributed_tpu.models import moe

    cfg = moe.MOE_PRESETS["moe_tiny"]
    params = moe.MoeLmModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingEngine(cfg, params, prefill_chunk=4)


def test_online_submission_mid_flight(params):
    """serve_step(): requests submitted WHILE others decode still come
    out token-identical — online serving never changes the math."""
    rng = np.random.default_rng(11)
    reqs = [(list(rng.integers(1, 200, n)), m)
            for n, m in [(5, 9), (3, 7), (6, 5)]]
    eng = ServingEngine(CFG, params, slots=2, cache_len=32, chunk=3,
                        prompt_buckets=(8,))
    out = {}
    ids = [eng.submit(*reqs[0])]
    out.update(eng.serve_step())          # request 0 starts decoding
    ids.append(eng.submit(*reqs[1]))      # arrives mid-flight
    out.update(eng.serve_step())
    ids.append(eng.submit(*reqs[2]))      # and another
    while eng.pending():
        out.update(eng.serve_step())
    for rid, (p, m) in zip(ids, reqs):
        assert out[rid] == _ref(params, p, m), f"request {rid}"


class TestSpeculativeServing:
    """Speculative decoding across slots: per-slot acceptance lengths
    with per-slot cache rewinds (the library path is batch-1 precisely
    because the shared-index cache cannot do this)."""

    def _reqs(self, seed):
        rng = np.random.default_rng(seed)
        return [(list(rng.integers(1, 200, n)), m)
                for n, m in [(5, 9), (3, 7), (6, 11), (4, 5)]]

    def _serve(self, params, draft_cfg, draft_params, reqs, k=3):
        eng = ServingEngine(CFG, params, slots=2, cache_len=48, chunk=3,
                            prompt_buckets=(8,), draft_config=draft_cfg,
                            draft_params=draft_params, speculative_k=k)
        ids = [eng.submit(p, m) for p, m in reqs]
        out = eng.run()
        return [out[i] for i in ids], eng.spec_stats

    def test_self_draft_matches_generate(self, params):
        """Draft == target: every draft accepted, outputs exactly the
        target's greedy decode under contention and refill."""
        reqs = self._reqs(20)
        outs, stats = self._serve(params, CFG, params, reqs)
        for got, (p, m) in zip(outs, reqs):
            assert got == _ref(params, p, m)
        # Perfect draft: near-total acceptance (>= slot_rounds*k - k
        # hedges a potential last-bit argmax tie flip between matmul
        # widths, the same hedge as tests/test_speculative.py).
        assert (stats["drafted_accepted"]
                >= 3 * stats["slot_rounds"] - 3)
        # Engine rounds step ALL active slots at once.
        assert stats["rounds"] <= stats["slot_rounds"]

    def test_disagreeing_draft_still_exact(self, params):
        """A randomly-initialized draft (near-zero acceptance) must not
        change a single output token — speculation is a latency lever,
        never a correctness knob."""
        dcfg = LLAMA_PRESETS["llama_tiny_scan"]
        dparams = LlamaModel(dcfg).init(
            jax.random.PRNGKey(99), jnp.zeros((1, 4), jnp.int32))["params"]
        reqs = self._reqs(21)
        outs, stats = self._serve(params, dcfg, dparams, reqs)
        for got, (p, m) in zip(outs, reqs):
            assert got == _ref(params, p, m)
        # Each request's token 1 comes from prefill; spec rounds emit
        # the remaining m-1.
        assert stats["emitted"] == sum(m - 1 for _, m in reqs)

    def test_validation(self, params):
        with pytest.raises(ValueError, match="speculative_k"):
            ServingEngine(CFG, params, draft_config=CFG,
                          draft_params=params)
        with pytest.raises(ValueError, match="draft_config"):
            ServingEngine(CFG, params, speculative_k=3)
        dcfg = dataclasses.replace(CFG, vocab_size=128)
        with pytest.raises(ValueError, match="vocab"):
            ServingEngine(CFG, params, draft_config=dcfg,
                          draft_params=params, speculative_k=3)

    def test_sampled_self_draft_full_acceptance_reproducible(self,
                                                             params):
        """Sampled speculative with draft == target: p == q, so the
        rejection rule accepts every draft (u < p/q = 1 a.s. — the
        small hedge covers batched-vs-stepped matmul rounding), and
        per-request rng streams make the whole run reproducible."""
        reqs = self._reqs(22)

        def serve():
            eng = ServingEngine(CFG, params, slots=2, cache_len=48,
                                chunk=3, prompt_buckets=(8,),
                                draft_config=CFG, draft_params=params,
                                speculative_k=3, temperature=1.0,
                                top_k=8)
            ids = [eng.submit(p, m) for p, m in reqs]
            out = eng.run()
            return [out[i] for i in ids], dict(eng.spec_stats)

        outs1, stats1 = serve()
        outs2, stats2 = serve()
        assert outs1 == outs2 and stats1 == stats2
        assert (stats1["drafted_accepted"]
                >= 3 * stats1["slot_rounds"] - 3)
        assert stats1["emitted"] == sum(m - 1 for _, m in reqs)

    def test_sampled_spec_matches_plain_sampled_distribution(
            self, params, monkeypatch):
        """The output-law property: rejection-sampled speculative serving
        follows the SAME output law as plain sampled serving even with
        a disagreeing draft.  Per-position chi-square homogeneity test
        on empirical marginals over two independent 768-stream samples
        — the null (one law) must SURVIVE at alpha=1e-3 per position,
        and the test proves its own power in-code: a mutated
        accept-everything law (the canonical bug — emitting the
        draft's samples un-rejected) must be REJECTED at p < 1e-6 on
        the very same seeds.  (Replaces the old per-position TV<0.3
        bound, which admitted visible skew on the 256-token vocab.)"""
        from scipy import stats as sps

        from tensorflow_train_distributed_tpu.models import speculative

        dcfg = LLAMA_PRESETS["llama_tiny_scan"]
        dparams = LlamaModel(dcfg).init(
            jax.random.PRNGKey(99), jnp.zeros((1, 4), jnp.int32))["params"]
        prompt, max_new, n = [5, 1], 4, 768

        def counts(spec, seed_base):
            kw = (dict(draft_config=dcfg, draft_params=dparams,
                       speculative_k=3) if spec else {})
            eng = ServingEngine(CFG, params, slots=8, cache_len=16,
                                chunk=4, prompt_buckets=(4,),
                                temperature=1.0, top_k=4, **kw)
            # Disjoint seed ranges: independent samples of the law.
            ids = [eng.submit(prompt, max_new, seed=s + seed_base)
                   for s in range(n)]
            out = eng.run()
            c = np.zeros((max_new, CFG.vocab_size))
            for i in ids:
                for t, tok in enumerate(out[i][len(prompt):]):
                    c[t, tok] += 1
            return c, eng.spec_stats

        def pvalue(c1, c2, t):
            """Two-sample chi-square on position ``t``'s marginals;
            tokens seen fewer than 10 times across both samples pool
            into one tail cell (expected-count validity)."""
            col = c1[t] + c2[t]
            keep = col >= 10
            rows = [np.concatenate([c[t][keep], [c[t][~keep].sum()]])
                    for c in (c1, c2)]
            if rows[0][-1] + rows[1][-1] == 0:
                rows = [r[:-1] for r in rows]
            return sps.chi2_contingency(np.stack(rows))[1]

        plain, _ = counts(spec=False, seed_base=0)
        spec, stats = counts(spec=True, seed_base=100_000)
        assert stats["rounds"] >= 1           # the spec path engaged
        k, sr = 3, stats["slot_rounds"]
        assert 0 <= stats["drafted_accepted"] <= k * sr
        # Null survives: measured p = [.19 .69 .19 .64] (deterministic
        # — fixed seed streams) at near-zero acceptance (~0.02), so
        # each emitted token exercised the full reject-and-resample
        # path.  Position 0 is prefill (shared code), 1.. _spec_round.
        for t in range(max_new):
            p = pvalue(plain, spec, t)
            assert p > 1e-3, f"position {t}: chi-square p={p}"

        # Power, on the same seeds: force every draft accepted
        # (bypassing the rejection rule) and the decode positions must
        # fail catastrophically (measured p <= 1e-119; position 0 is
        # prefill — untouched by the mutation).
        monkeypatch.setattr(
            speculative, "_accept_count",
            lambda ok: jnp.full((ok.shape[0],), ok.shape[1], jnp.int32))
        mutated, mstats = counts(spec=True, seed_base=200_000)
        assert mstats["drafted_accepted"] == k * mstats["slot_rounds"]
        for t in range(1, max_new):
            p = pvalue(plain, mutated, t)
            assert p < 1e-6, f"position {t}: mutated law p={p}"


def test_serve_cli_roundtrip(tmp_path):
    """tools/serve.py: train a tiny checkpoint, then batch-serve
    MIXED-LENGTH prompts through the engine CLI — one JSONL line per
    request, each prefixed with its own prompt."""
    import importlib.util
    import json
    import os

    from tensorflow_train_distributed_tpu import launch

    ckpt = str(tmp_path / "ck")
    launch.run(launch.build_parser().parse_args([
        "--config", "llama_tiny_sft", "--steps", "3",
        "--global-batch-size", "8", "--checkpoint-dir", ckpt,
        "--checkpoint-every", "3", "--log-every", "3"]))
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    reqs = tmp_path / "reqs.jsonl"
    reqs.write_text(json.dumps({"prompt": [9, 8, 7, 6], "max_new": 3,
                                "seed": 5}) + "\n")
    out_path = str(tmp_path / "out.jsonl")
    spec = importlib.util.spec_from_file_location(
        "serve_under_test", os.path.join(tools, "serve.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--config", "llama_tiny_sft", "--checkpoint-dir", ckpt,
                   "--prompt", "1,2,3", "--prompt", "4,5,6,7,8",
                   "--max-new", "5", "--requests", str(reqs),
                   "--slots", "2", "--chunk", "3",
                   "--output", out_path])
    assert rc == 0
    lines = [json.loads(ln) for ln in open(out_path)]
    assert len(lines) == 3
    assert lines[0]["tokens"][:3] == [1, 2, 3]
    assert len(lines[0]["tokens"]) == 3 + 5
    assert lines[1]["tokens"][:5] == [4, 5, 6, 7, 8]
    assert lines[2]["tokens"][:4] == [9, 8, 7, 6]
    assert len(lines[2]["tokens"]) == 4 + 3


def test_serve_cli_speculative(tmp_path, capsys):
    """tools/serve.py --speculative-*: the engine must actually run
    speculative rounds (stderr stats prove it — a silent fall-through
    to plain decoding once shipped unnoticed) and emit byte-identical
    output to plain serving."""
    import importlib.util
    import os

    from tensorflow_train_distributed_tpu import launch

    ckpt = str(tmp_path / "ck")
    draft = str(tmp_path / "dk")
    for d, steps in ((ckpt, "3"), (draft, "2")):
        launch.run(launch.build_parser().parse_args([
            "--config", "llama_tiny_sft", "--steps", steps,
            "--global-batch-size", "8", "--checkpoint-dir", d,
            "--checkpoint-every", steps, "--log-every", "3"]))
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    spec = importlib.util.spec_from_file_location(
        "serve_spec_under_test", os.path.join(tools, "serve.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    base = ["--config", "llama_tiny_sft", "--checkpoint-dir", ckpt,
            "--prompt", "1,2,3", "--prompt", "4,5,6,7",
            "--max-new", "6", "--slots", "2"]
    assert mod.main(base + ["--speculative-draft-config",
                            "llama_tiny_sft",
                            "--speculative-draft-checkpoint", draft,
                            "--speculative-k", "3"]) == 0
    cap = capsys.readouterr()
    spec_lines = [ln for ln in cap.out.splitlines() if ln.startswith("{")]
    assert "speculative: rounds=" in cap.err
    rounds = int(cap.err.split("rounds=")[1].split()[0])
    assert rounds >= 1
    assert mod.main(base) == 0
    plain_lines = [ln for ln in capsys.readouterr().out.splitlines()
                   if ln.startswith("{")]
    assert spec_lines == plain_lines
    with pytest.raises(SystemExit, match="draft-config"):
        mod.main(base + ["--speculative-draft-checkpoint", draft])


def test_submit_rejects_over_bucket_prompt(params):
    """Over-bucket prompts fail at submit() — failing inside run()
    would silently drop the request and abort others mid-flight."""
    eng = ServingEngine(CFG, params, slots=2, cache_len=32,
                        prompt_buckets=(8,))
    with pytest.raises(ValueError, match="bucket"):
        eng.submit([1] * 12, 2)


def test_slot_decode_matches_shared_index_when_uniform():
    """With every slot at the same position, the per-slot path must
    reproduce the shared-index decode numerics exactly."""
    cfg = CFG
    tok = jnp.asarray(
        np.random.default_rng(4).integers(1, 200, (2, 12)), jnp.int32)
    m_reg = LlamaModel(cfg, decode=True, cache_len=16)
    m_slot = LlamaModel(cfg, decode=True, cache_len=16, slot_decode=True)
    v = m_reg.init(jax.random.PRNGKey(0), tok[:, :1])
    params = {"params": v["params"]}
    lr, cr = m_reg.apply(params, tok, mutable=["cache"])
    ls, cs = m_slot.apply(params, tok, mutable=["cache"])
    np.testing.assert_array_equal(np.asarray(lr), np.asarray(ls))
    nt = jnp.argmax(lr[:, -1], -1)[:, None].astype(jnp.int32)
    lr2, _ = m_reg.apply(dict(params, cache=cr["cache"]), nt,
                         mutable=["cache"])
    ls2, _ = m_slot.apply(dict(params, cache=cs["cache"]), nt,
                          mutable=["cache"])
    np.testing.assert_array_equal(np.asarray(lr2), np.asarray(ls2))


def test_moe_exact_prefill_warns_on_new_lengths(caplog):
    """MoE prefills at the exact prompt length (router capacity is
    length-dependent) — one XLA program per distinct length.  The
    engine warns once per NEW length from the second distinct length
    on, so a varied-length request stream announces its compile storm
    (MIGRATION.md §8 documents the pad-host-side mitigation)."""
    import logging

    from tensorflow_train_distributed_tpu.models import moe

    cfg = moe.MOE_PRESETS["moe_tiny"]
    rng = np.random.default_rng(6)
    params = moe.MoeLmModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ServingEngine(cfg, params, slots=2, cache_len=32, chunk=3)
    with caplog.at_level(logging.WARNING,
                         logger="tensorflow_train_distributed_tpu.serving"):
        for n, m in [(4, 3), (4, 2), (6, 3), (6, 2), (5, 2)]:
            eng.submit(list(rng.integers(1, cfg.vocab_size, n)), m)
        eng.run()
    warns = [r for r in caplog.records
             if "prompt length" in r.getMessage()]
    # Lengths 4, 6, 5: the first is free, repeats are silent, each new
    # one warns — two warnings total.
    assert len(warns) == 2
    assert "6" in warns[0].getMessage()


def test_moe_gmm_bucketed_and_chunked_prefill_match_generate():
    """Dropless (dispatch='gmm') MoE routes every token independently —
    no capacity competition — so pad tokens cannot perturb real ones
    and the engine may bucket or chunk its prefill like a dense
    decoder: outputs must stay token-identical to generate()'s
    exact-length prefill.  (Dense dispatch keeps exact-length prefill;
    see test_moe_exact_prefill_warns_on_new_lengths.)"""
    from tensorflow_train_distributed_tpu.models import moe

    cfg = dataclasses.replace(moe.MOE_PRESETS["moe_tiny"],
                              dispatch="gmm")
    rng = np.random.default_rng(7)
    params = moe.MoeLmModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    reqs = [(list(rng.integers(1, cfg.vocab_size, n)), m)
            for n, m in [(3, 5), (6, 4), (5, 6)]]
    refs = [np.asarray(generate(
        cfg, params, jnp.asarray([p], jnp.int32), m))[0].tolist()
        for p, m in reqs]

    def serve(**kw):
        eng = ServingEngine(cfg, params, slots=2, cache_len=32, chunk=3,
                            **kw)
        assert not eng._exact_prefill    # gmm frees the exact-length rule
        ids = [eng.submit(p, m) for p, m in reqs]
        out = eng.run()
        return [out[i] for i in ids]

    # Bucketed: lengths 3/5/6 all pad to the single 8-bucket (one
    # program), yet every output matches the unpadded reference.
    assert serve(prompt_buckets=(8,)) == refs
    # Chunked: 4-token pieces (rejected for dense MoE, sound for gmm).
    assert serve(prefill_chunk=4) == refs


def test_serve_cli_dispatch_gmm_engages_buckets_and_prefix(capsys):
    """--dispatch at the serving CLIs: 'gmm' applied
    through serve.py's shared helper frees the MoE exact-length prefill
    rule — bucketed prefill and prefix caching ENGAGE, token-identical
    to generate() — while the same checkpoint under dense dispatch
    refuses prefix reuse and triggers the varied-length compile-storm
    hint; a dense decoder config rejects the flag outright."""
    import argparse
    import importlib.util
    import os

    from tensorflow_train_distributed_tpu.models import moe

    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    spec_ = importlib.util.spec_from_file_location(
        "serve_dispatch_under_test", os.path.join(tools, "serve.py"))
    serve = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(serve)

    base = moe.MOE_PRESETS["moe_tiny"]
    args = argparse.Namespace(dispatch="gmm")
    gcfg = serve.apply_dispatch_arg(args, base, is_moe=True)
    assert gcfg.dispatch == "gmm" and base.dispatch == "dense"
    with pytest.raises(SystemExit, match="dense decoder"):
        serve.apply_dispatch_arg(args, CFG, is_moe=False)

    # dense and gmm share one parameter tree (the flag's checkpoint-
    # compatibility contract): one init serves both engines.
    params_moe = moe.MoeLmModel(base).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(12)
    system = list(rng.integers(1, base.vocab_size, 3))
    reqs = [(system + list(rng.integers(1, base.vocab_size, d)), m)
            for d, m in [(2, 4), (4, 3)]]

    dense_eng = ServingEngine(base, params_moe, slots=2, cache_len=32,
                              chunk=3)
    assert dense_eng._exact_prefill
    with pytest.raises(ValueError, match="dispatch='gmm'"):
        dense_eng.preload_prefix(system)     # dense refuses prefix reuse
    serve.maybe_dense_moe_hint(dense_eng, [len(p) for p, _ in reqs])
    assert "--dispatch gmm" in capsys.readouterr().err
    serve.maybe_dense_moe_hint(dense_eng, [5, 5])   # uniform: silent
    assert capsys.readouterr().err == ""

    gmm_eng = ServingEngine(gcfg, params_moe, slots=2, cache_len=32,
                            chunk=3, prompt_buckets=(8,))
    assert not gmm_eng._exact_prefill        # buckets engage
    gmm_eng.preload_prefix(system)           # ...and so does prefix reuse
    assert gmm_eng._match_prefix(reqs[0][0])[0] == len(system)
    serve.maybe_dense_moe_hint(gmm_eng, [len(p) for p, _ in reqs])
    assert capsys.readouterr().err == ""     # no hint for gmm
    ids = [gmm_eng.submit(p, m) for p, m in reqs]
    out = gmm_eng.run()
    for rid, (p, m) in zip(ids, reqs):
        ref = np.asarray(generate(
            gcfg, params_moe, jnp.asarray([p], jnp.int32), m))[0].tolist()
        assert out[rid] == ref, f"request {rid}"


def test_int8_speculative_engine_matches_int8_generate(params):
    """int8 weight-only serving composes with speculative decoding (the
    production pairing — decode is weight-HBM-bound on BOTH models):
    greedy outputs must be token-identical to int8 generate(), with a
    disagreeing draft and with a perfect self-draft."""
    from tensorflow_train_distributed_tpu.models import quant

    qparams, scales = quant.quantize_params(params)
    dcfg = LLAMA_PRESETS["llama_tiny_scan"]
    dparams = LlamaModel(dcfg).init(
        jax.random.PRNGKey(99), jnp.zeros((1, 4), jnp.int32))["params"]
    dq, dscales = quant.quantize_params(dparams)
    rng = np.random.default_rng(8)
    reqs = [(list(rng.integers(1, 200, n)), m)
            for n, m in [(4, 6), (6, 5), (3, 7)]]
    refs = [np.asarray(generate(
        CFG, qparams, jnp.asarray([p], jnp.int32), m,
        quant_scales=scales))[0].tolist() for p, m in reqs]

    def serve(drc, drp, drs):
        eng = ServingEngine(CFG, qparams, slots=2, cache_len=48,
                            chunk=3, prompt_buckets=(8,),
                            quant_scales=scales, draft_config=drc,
                            draft_params=drp, draft_quant_scales=drs,
                            speculative_k=3)
        ids = [eng.submit(p, m) for p, m in reqs]
        out = eng.run()
        return [out[i] for i in ids], eng.spec_stats

    outs, stats = serve(dcfg, dq, dscales)      # disagreeing int8 draft
    assert outs == refs
    assert stats["rounds"] >= 1
    outs, _ = serve(CFG, qparams, scales)       # perfect int8 self-draft
    assert outs == refs
    # Pairing contract holds per-tree: an int8 draft without its scales
    # fails loudly, as do orphan draft scales.
    with pytest.raises(ValueError, match="quant_scales"):
        ServingEngine(CFG, qparams, quant_scales=scales,
                      draft_config=dcfg, draft_params=dq,
                      speculative_k=3, prompt_buckets=(8,))
    with pytest.raises(ValueError, match="draft_quant_scales"):
        ServingEngine(CFG, qparams, quant_scales=scales,
                      draft_quant_scales=dscales, prompt_buckets=(8,))


class TestPrefixCaching:
    """preload_prefix(): shared prompt prefixes prefill once; suffix
    prefill on a copied cache must be token-identical to full prefill."""

    @pytest.mark.parametrize("tile", [None, 4], ids=["one-tile", "tile-4"])
    def test_prefix_reuse_matches_full_prefill(self, params, walk_in_tiles,
                                               tile):
        """``tile-4``: a suffix piece appended at row 6 of the copied
        prefix cache walks from row 0 through its own last tile."""
        rng = np.random.default_rng(9)
        system = list(rng.integers(1, 200, 6))
        reqs = [(system + list(rng.integers(1, 200, d)), m)
                for d, m in [(3, 6), (5, 5), (1, 7)]]
        reqs.append((list(rng.integers(1, 200, 4)), 5))  # no prefix match
        want = [_ref(params, p, m) for p, m in reqs]
        walks = walk_in_tiles(tile)
        eng = ServingEngine(CFG, params, slots=2, cache_len=64, chunk=4,
                            prompt_buckets=(8, 16))
        eng.preload_prefix(system)
        # Count device prefill calls: suffixes of 3/5/1 tokens hit the
        # 8-bucket once each, the non-matching 4-prompt once, and the
        # preload itself paid one — full prompts would have needed the
        # 16-bucket for the 6+3 and 6+5 cases.
        calls = []
        orig = eng._prefill_piece

        def counting(variables, cache, toks, local, seed, count0):
            calls.append(int(toks.shape[1]))
            return orig(variables, cache, toks, local, seed, count0)

        eng._prefill_piece = counting
        ids = [eng.submit(p, m) for p, m in reqs]
        out = eng.run()
        assert [out[rid] for rid in ids] == want
        assert calls == [8, 8, 8, 8]   # suffix-sized pieces only
        assert set(walks) == ({(8, 4, 64), (1, 4, 64)} if tile else set())

    def test_longest_prefix_wins_and_exact_prompt_is_excluded(self,
                                                              params):
        eng = ServingEngine(CFG, params, slots=1, cache_len=64, chunk=4,
                            prompt_buckets=(8, 16))
        eng.preload_prefix([7, 7])
        eng.preload_prefix([7, 7, 7, 7])
        assert eng._match_prefix([7, 7, 7, 7, 9])[0] == 4
        assert eng._match_prefix([7, 7, 9])[0] == 2
        # A prompt EQUAL to a stored prefix still needs one real token
        # prefilled to produce its first logits — the shorter store wins.
        assert eng._match_prefix([7, 7, 7, 7])[0] == 2
        assert eng._match_prefix([8, 7])[0] == 0
        rid = eng.submit([7, 7, 7, 7, 9], 5)
        assert eng.run()[rid] == _ref(params, [7, 7, 7, 7, 9], 5)

    def test_prefix_guards(self, params):
        from tensorflow_train_distributed_tpu.models import moe

        eng = ServingEngine(CFG, params, slots=1, cache_len=16,
                            prompt_buckets=(8,))
        with pytest.raises(ValueError, match="empty"):
            eng.preload_prefix([])
        with pytest.raises(ValueError, match="cache room"):
            eng.preload_prefix([1] * 16)
        mcfg = moe.MOE_PRESETS["moe_tiny"]
        mparams = moe.MoeLmModel(mcfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
        meng = ServingEngine(mcfg, mparams, slots=1, cache_len=16)
        with pytest.raises(ValueError, match="dispatch='gmm'"):
            meng.preload_prefix([1, 2])

    def test_prefix_composes_with_speculative(self, params):
        """Speculative + prefix caching: the DRAFT model's prefix cache
        is stored alongside the target's, and greedy outputs stay
        token-identical to plain generate() — the full composition
        (continuous batching × speculation × prefix reuse)."""
        dcfg = LLAMA_PRESETS["llama_tiny_scan"]
        dparams = LlamaModel(dcfg).init(
            jax.random.PRNGKey(99), jnp.zeros((1, 4), jnp.int32))["params"]
        rng = np.random.default_rng(12)
        system = list(rng.integers(1, 200, 6))
        reqs = [(system + list(rng.integers(1, 200, d)), m)
                for d, m in [(3, 6), (2, 5)]]
        eng = ServingEngine(CFG, params, slots=2, cache_len=48, chunk=3,
                            prompt_buckets=(8,), draft_config=dcfg,
                            draft_params=dparams, speculative_k=3)
        eng.preload_prefix(system)
        assert eng._match_prefix(reqs[0][0])[0] == len(system)
        ids = [eng.submit(p, m) for p, m in reqs]
        out = eng.run()
        for rid, (p, m) in zip(ids, reqs):
            assert out[rid] == _ref(params, p, m), f"request {rid}"
        assert eng.spec_stats["rounds"] >= 1


def test_moe_gmm_prefix_caching_matches_generate():
    """Prefix caching composes with dropless MoE (per-token routing —
    the reason gmm escapes the exact-length rule covers this too)."""
    from tensorflow_train_distributed_tpu.models import moe

    cfg = dataclasses.replace(moe.MOE_PRESETS["moe_tiny"],
                              dispatch="gmm")
    rng = np.random.default_rng(10)
    params = moe.MoeLmModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    system = list(rng.integers(1, cfg.vocab_size, 5))
    reqs = [(system + list(rng.integers(1, cfg.vocab_size, d)), m)
            for d, m in [(2, 4), (3, 3)]]
    eng = ServingEngine(cfg, params, slots=2, cache_len=32, chunk=3,
                        prompt_buckets=(8,))
    eng.preload_prefix(system)
    ids = [eng.submit(p, m) for p, m in reqs]
    out = eng.run()
    for rid, (p, m) in zip(ids, reqs):
        ref = np.asarray(generate(
            cfg, params, jnp.asarray([p], jnp.int32), m))[0].tolist()
        assert out[rid] == ref, f"gmm prefix request {rid}"


def test_prefix_allows_prompts_beyond_largest_bucket(params):
    """A long shared system prompt + short tail is the feature's
    primary use: submit() must size its bucket check on the SUFFIX
    after the longest preloaded prefix, not the full prompt."""
    rng = np.random.default_rng(11)
    system = list(rng.integers(1, 200, 12))
    tail = list(rng.integers(1, 200, 5))
    eng = ServingEngine(CFG, params, slots=1, cache_len=64, chunk=4,
                        prompt_buckets=(8, 16))
    with pytest.raises(ValueError, match="bucket"):
        eng.submit(system + tail, 4)       # 17 > 16, no prefix yet
    eng.preload_prefix(system)
    rid = eng.submit(system + tail, 4)     # suffix 5 fits the 8-bucket
    assert eng.run()[rid] == _ref(params, system + tail, 4)


def test_long_prefix_preloads_in_bucket_mode(params):
    """A prefix LONGER than the largest bucket preloads as
    largest-bucket-sized pieces (the shared _pieces_for rule) — the
    long-system-prompt case needs no prefill_chunk setting."""
    rng = np.random.default_rng(13)
    system = list(rng.integers(1, 200, 21))     # > largest bucket (16)
    tail = list(rng.integers(1, 200, 3))
    eng = ServingEngine(CFG, params, slots=1, cache_len=64, chunk=4,
                        prompt_buckets=(8, 16))
    eng.preload_prefix(system)
    rid = eng.submit(system + tail, 4)
    assert eng.run()[rid] == _ref(params, system + tail, 4)


class TestCancel:
    """cancel() across a request's whole lifecycle: queued, staged
    mid-prefill (the interleaved scheduler's new state — the lane must
    free IMMEDIATELY and the partial cache be discarded), and decoding
    — survivors always finish token-identical to generate()."""

    def test_cancel_while_queued(self, params):
        rng = np.random.default_rng(40)
        eng = ServingEngine(CFG, params, slots=1, cache_len=32,
                            chunk=3, prompt_buckets=(8,))
        pa = list(rng.integers(1, 200, 4))
        a = eng.submit(pa, 8)
        eng.serve_step()                   # a decoding; the lane is busy
        b = eng.submit(list(rng.integers(1, 200, 5)), 5)
        assert eng.queue_depth() == 1
        assert eng.cancel(b)
        assert eng.queue_depth() == 0
        assert not eng.cancel(b)           # already gone
        out = {}
        while eng.pending():
            out.update(eng.serve_step())
        assert b not in out
        assert out[a] == _ref(params, pa, 8)

    def test_cancel_mid_staged_prefill_frees_lane(self, params):
        """Cancelling a request whose prefill is STAGED (some budget
        installments done, not yet inserted) frees its lane at once:
        occupancy drops immediately, a later request reuses the lane,
        and the in-flight lanes are untouched."""
        rng = np.random.default_rng(41)
        eng = ServingEngine(CFG, params, slots=2, cache_len=64,
                            chunk=2, prefill_chunk=4)
        pa = list(rng.integers(1, 200, 4))
        a = eng.submit(pa, 16)
        eng.serve_step()
        eng.serve_step()
        victim = eng.submit(list(rng.integers(1, 200, 12)), 5)
        eng.serve_step()                   # one installment of 3 done
        assert eng.prefill_stats["staged_requests"] >= 1
        assert eng.active_slots() == 2     # decoding + staged lane
        assert eng.pending() == 2
        assert eng.cancel(victim)
        assert eng.active_slots() == 1     # staged lane freed NOW
        assert eng.pending() == 1
        assert not eng.cancel(victim)
        pc = list(rng.integers(1, 200, 3))
        c = eng.submit(pc, 6)              # reuses the freed lane
        out = {}
        while eng.pending():
            out.update(eng.serve_step())
        assert victim not in out
        assert out[a] == _ref(params, pa, 16)
        assert out[c] == _ref(params, pc, 6)


def test_snapshot_streams_inflight_tokens(params):
    """snapshot(): between serve_step calls the in-flight view grows
    monotonically as a prefix of the final output (streaming UIs poll
    this); finished requests leave the snapshot."""
    prompt = [3, 1, 4, 1, 5]
    eng = ServingEngine(CFG, params, slots=1, cache_len=32, chunk=2,
                        prompt_buckets=(8,))
    rid = eng.submit(prompt, 8)
    assert eng.snapshot() == {}            # nothing in flight yet
    seen = []
    final = {}
    while eng.pending():
        final.update(eng.serve_step())
        snap = eng.snapshot()
        if rid in snap:
            seen.append(snap[rid])
    full = final[rid]
    assert full == _ref(params, prompt, 8)
    for partial in seen:                   # each snapshot is a prefix
        assert partial == full[:len(partial)]
    assert rid not in eng.snapshot()       # finished → left the view
    assert len(seen) >= 2                  # chunk=2 over 8 tokens: grew


def test_prefix_caching_composes_with_tp_mesh(params, mesh_2d):
    """Prefix caching under tensor-parallel serving: the stored prefix
    cache is sharded like every other engine buffer (the copy preserves
    shardings), and outputs stay token-identical to the unsharded
    prefix-cached engine."""
    system = [3, 1, 4, 1, 5, 9]
    reqs = [(system + [9, 2, 7], 6), (system + [8, 2, 6, 4, 1], 5)]

    def serve(mesh):
        eng = ServingEngine(CFG, params, slots=2, cache_len=64, chunk=4,
                            prompt_buckets=(8, 16), mesh=mesh)
        eng.preload_prefix(system)
        # Prove the prefix ENGAGES under the mesh (a silent
        # full-prefill fallback would still be token-identical): after
        # the preload's own piece, request prefills must be
        # suffix-sized only.
        pieces = []
        orig = eng._prefill_piece

        def counting(variables, cache, toks, local, seed, count0):
            pieces.append(int(toks.shape[1]))
            return orig(variables, cache, toks, local, seed, count0)

        eng._prefill_piece = counting
        ids = [eng.submit(p, n) for p, n in reqs]
        out = eng.run()
        assert pieces == [8, 8], pieces  # 3/5-token suffixes → the
        #    8-bucket; a full 9/11-token prompt would need the 16-bucket
        return [out[i] for i in ids]

    plain = serve(None)
    assert serve(mesh_2d) == plain
    # And the unsharded prefix outputs equal full-prefill generate().
    for got, (p, m) in zip(plain, reqs):
        assert got == _ref(params, p, m)
