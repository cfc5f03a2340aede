"""Runtime recompilation sanitizer tests (TTD_COMPILECHECK=1).

conftest arms the sanitizer for the WHOLE tier-1 suite — these tests
pin that (a) the annotated package sites really are instrumented, (b)
a planted recompile storm (un-bucketed prompt lengths fed straight to
a serving program) raises ``RecompileError`` with the signatures
diffed — the acceptance criterion, (c) the trainer's AOT
``.lower().compile()`` path routes through the same instrumented seam
as the live step (the PR's regression fix), (d) compile events land in
the flight recorder and on ``ttd_engine_compiles_total``, (e) the
``TTD_NO_COMPILECHECK`` escape hatch works LIVE, and (f) the
already-compiled dispatch fast path stays inside a measured overhead
bar (< 5 us — the lockcheck <25 us/acquire discipline, tighter
because this sits on the per-chunk decode path).
"""

import os
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import flax.linen as nn

from tensorflow_train_distributed_tpu.runtime import events
from tensorflow_train_distributed_tpu.runtime.lint import compilecheck
from tensorflow_train_distributed_tpu.runtime.lint.compilecheck import (
    RecompileError,
)
from tensorflow_train_distributed_tpu.runtime.lint.registry import (
    compile_site,
)


@compile_site(site="test.toy", statics=(0,), donates=(), max_compiles=2)
@partial(jax.jit, static_argnums=(0,))
def _toy(tag, x):
    return x + 1


# ── the package really is instrumented in tier-1 ───────────────────────


def test_conftest_armed_and_package_sites_registered():
    assert compilecheck.armed(), "conftest should arm TTD_COMPILECHECK"
    import tensorflow_train_distributed_tpu.serving  # noqa: F401

    sites = compilecheck.sites()
    for site in ("serving.ServingEngine._prefill_piece",
                 "serving.ServingEngine._decode_chunk",
                 "serving.ServingEngine._spec_round",
                 "serving.ServingEngine._paged_insert",
                 "generate._generate"):
        assert site in sites, f"{site} not registered (got {sites})"
    # The wrapper actually wrapped (armed path, not the bare jit).
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    assert getattr(ServingEngine._decode_chunk,
                   "__ttd_compile_wrapped__", False)


def test_env_flags_spelled_for_audit():
    """TTD_COMPILECHECK / TTD_NO_COMPILECHECK drive this whole module
    via conftest; assert the arming env is what we think it is."""
    assert os.environ.get("TTD_COMPILECHECK") == "1"
    assert os.environ.get("TTD_NO_COMPILECHECK") in (None, "", "0")


# ── budget enforcement ─────────────────────────────────────────────────


def test_budget_raises_on_first_excess_with_signature_diff():
    compilecheck.reset("test.toy")
    _toy("a", jnp.ones((2,)))
    _toy("a", jnp.ones((2,)))          # same signature: free
    _toy("a", jnp.ones((3,)))          # second bucket: last in budget
    with pytest.raises(RecompileError) as ei:
        _toy("a", jnp.ones((4,)))
    msg = str(ei.value)
    assert "test.toy" in msg
    assert "max_compiles=2" in msg
    # Both signatures, diffed: the old shape and the would-be new one.
    assert "(3,)" in msg and "(4,)" in msg
    # The budget is not consumed by the refusal: the excess keeps
    # raising (a storm cannot burn through by retrying).
    with pytest.raises(RecompileError):
        _toy("a", jnp.ones((4,)))


def test_budget_groups_are_per_static_args():
    """A new engine/config (static group) legitimately compiles its own
    bucket set — budgets must not bleed across instances."""
    compilecheck.reset("test.toy")
    _toy("a", jnp.ones((2,)))
    _toy("a", jnp.ones((3,)))          # group "a" at budget
    _toy("b", jnp.ones((2,)))          # fresh group: fresh budget
    _toy("b", jnp.ones((3,)))
    with pytest.raises(RecompileError):
        _toy("b", jnp.ones((4,)))


def test_same_signature_never_recounts():
    compilecheck.reset("test.toy")
    _toy("c", jnp.ones((5,)))
    before = compilecheck.total_compiles()
    for _ in range(10):
        _toy("c", jnp.ones((5,)))
    assert compilecheck.total_compiles() == before


# ── the acceptance storm: un-bucketed lengths into a real program ──────


def test_planted_storm_on_real_engine_prefill_raises():
    """The acceptance criterion: un-bucketed prompt lengths fed
    straight to the engine's prefill program (bypassing
    ``_pieces_for``'s bucket rule, exactly what the static checker
    forbids at call sites) raise ``RecompileError`` under the armed
    sanitizer — on the FIRST dispatch past the site's budget, before
    the excess compile happens."""
    from tensorflow_train_distributed_tpu.models.llama import (
        LLAMA_PRESETS,
        LlamaModel,
    )
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    cfg = LLAMA_PRESETS["llama_tiny"]
    params = LlamaModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    eng = ServingEngine(cfg, params, slots=2, cache_len=32, chunk=2,
                        prompt_buckets=(8,))
    site = "serving.ServingEngine._prefill_piece"
    with compilecheck.override_budget(site, 2):
        cache = eng._fresh_cache(1)
        with pytest.raises(RecompileError, match="_prefill_piece"):
            for n in (3, 5, 7):        # three un-bucketed lengths
                cache, _ = eng._prefill_piece(
                    eng._variables, cache,
                    jnp.zeros((1, n), jnp.int32), jnp.int32(n - 1),
                    jnp.uint32(0), jnp.int32(0))
    compilecheck.reset(site)           # don't leak the planted sigs


def test_bucketed_serving_stays_inside_budget():
    """The same engine serving THROUGH the bucket discipline compiles
    one prefill-piece signature total (one bucket) — the storm above
    is the bypass, not the path."""
    from tensorflow_train_distributed_tpu.models.llama import (
        LLAMA_PRESETS,
        LlamaModel,
    )
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    cfg = LLAMA_PRESETS["llama_tiny"]
    params = LlamaModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    eng = ServingEngine(cfg, params, slots=2, cache_len=16, chunk=2,
                        prompt_buckets=(8,))
    rid_a = eng.submit([1, 2, 3], 3)
    rid_b = eng.submit([4, 5, 6, 7, 8], 3)   # same bucket, longer
    out = eng.run()
    assert len(out[rid_a]) == 6 and len(out[rid_b]) == 8
    spec = compilecheck.site_spec("serving.ServingEngine._prefill_piece")
    assert spec is not None and spec.max_compiles is not None


# ── satellite: the trainer's AOT path shares the live step's seam ──────


class _TinyMLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(3)(nn.relu(nn.Dense(8)(x)))


class _TinyTask:
    def __init__(self):
        self.model = _TinyMLP()

    def init_variables(self, rng, batch):
        return self.model.init(rng, jnp.zeros(batch["x"].shape,
                                              jnp.float32))

    def loss_fn(self, params, model_state, batch, rng, train):
        logits = self.model.apply({"params": params}, batch["x"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), batch["label"]).mean()
        return loss, ({}, model_state)


def test_trainer_aot_lower_routes_through_compilecheck_seam(mesh8):
    """Regression (the PR's satellite fix): ``lower_train_step`` used
    to call raw ``jax.jit(...).lower`` — invisible to compilecheck.
    It now routes through the same 'trainer.train_step' site as the
    live step: the site registers, the lower is recorded as a compile
    event, and the compile counter moves."""
    from tensorflow_train_distributed_tpu.training.trainer import (
        Trainer,
        TrainerConfig,
    )

    trainer = Trainer(_TinyTask(), optax.adam(1e-2), mesh8,
                      config=TrainerConfig())
    batch = {"x": np.zeros((8, 4), np.float32),
             "label": np.zeros((8,), np.int64)}
    before = compilecheck.total_compiles()
    lowered = trainer.lower_train_step(batch)
    assert "trainer.train_step" in compilecheck.sites()
    assert compilecheck.total_compiles() == before + 1, \
        "the AOT .lower() must be recorded as a compile event"
    # And the lowering is the real thing: it compiles.
    assert lowered.compile() is not None


# ── observability: flight-recorder spans + /metrics counter ────────────


def test_compile_spans_land_in_flight_recorder():
    compilecheck.reset("test.toy")
    rec = events.get_recorder()
    rec.clear()
    _toy("span-probe", jnp.ones((6,)))
    spans = [e for e in rec.events() if e[0] == "compile/test.toy"]
    assert len(spans) == 1
    name, ph, t0, dur, tid, attrs = spans[0]
    assert ph == "X" and dur >= 0
    assert attrs["site"] == "test.toy"
    assert attrs["signature"] == 1
    # The already-compiled dispatch records NO span (fast path).
    rec.clear()
    _toy("span-probe", jnp.ones((6,)))
    assert [e for e in rec.events()
            if e[0].startswith("compile/")] == []


def test_trace_report_folds_compile_spans():
    from tools.trace_report import compile_summary

    rec = events.get_recorder()
    rec.clear()
    compilecheck.reset("test.toy")
    _toy("report-probe", jnp.ones((7,)))
    evs = rec.export_chrome_trace()["traceEvents"]
    rows = compile_summary(evs)
    assert rows and rows[0][0] == "test.toy" and rows[0][1] == 1


def test_metrics_counter_samples_the_sanitizer():
    from tensorflow_train_distributed_tpu.server.metrics import (
        GatewayMetrics,
    )

    m = GatewayMetrics(lambda: 0, lambda: 0, 1)
    before = compilecheck.total_compiles()
    rendered = m.render()
    assert "ttd_engine_compiles_total" in rendered
    assert f"ttd_engine_compiles_total {before}" in rendered
    compilecheck.reset("test.toy")
    _toy("metrics-probe", jnp.ones((9,)))
    assert m.compiles.value() == before + 1


# ── escape hatch + overhead bar ────────────────────────────────────────


def test_no_compilecheck_escape_hatch_is_live(monkeypatch):
    """Unlike arming (decoration-time), the veto is re-read per
    dispatch: an operator can disarm a misbehaving sanitizer with an
    env flip, no redeploy, no re-import."""
    compilecheck.reset("test.toy")
    _toy("hatch", jnp.ones((2,)))
    _toy("hatch", jnp.ones((3,)))      # at budget
    monkeypatch.setenv("TTD_NO_COMPILECHECK", "1")
    assert not compilecheck.armed()
    before = compilecheck.total_compiles()
    _toy("hatch", jnp.ones((4,)))      # would raise; vetoed through
    assert compilecheck.total_compiles() == before
    monkeypatch.delenv("TTD_NO_COMPILECHECK")
    assert compilecheck.armed()
    with pytest.raises(RecompileError):
        _toy("hatch", jnp.ones((5,)))


def test_overhead_bar_already_compiled_dispatch_flat_args():
    """The measured bar conftest's suite-wide arming rides on: the
    sanitizer's bookkeeping on an ALREADY-COMPILED dispatch of a
    flat-array signature (scalars + arrays, no pytree containers)
    stays under 5 us — it sits on the per-chunk decode path, so the
    bound is 5x tighter than lockcheck's 25 us/acquire.  Measured as
    wrapped-minus-raw dispatch time, best-of-5 legs so scheduler noise
    cannot fail a healthy build."""
    compilecheck.reset("test.toy")
    x = jnp.ones((8,))
    _toy("bar", x)                     # compile once
    inner = _toy.__wrapped__
    n = 2000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            _toy("bar", x)
        t1 = time.perf_counter()
        for _ in range(n):
            inner("bar", x)
        t2 = time.perf_counter()
        best = min(best, ((t1 - t0) - (t2 - t1)) / n)
    per_op = max(0.0, best)
    assert per_op < 5e-6, f"{per_op * 1e6:.2f} us/dispatch overhead"


def test_overhead_bar_already_compiled_dispatch_pytree_args():
    """The honest second bar: programs carrying pytree containers (the
    engine's variables + cache trees) pay jax.tree_flatten per
    dispatch — flatten-dominated, leaf-proportional (measured ~18 us
    on the real llama_tiny ``_decode_chunk``, 21+8 leaves, ≈0.04% of
    a decode chunk's device work).  Pinned so an accidental
    O(leaves^2) or per-dispatch stringification regression (hundreds
    of us) fails here instead of shipping.  Bar retuned 40 us → 120 us
    for this host: the estimator is the DIFFERENCE of two ~1 ms-leg
    timing sums, so a few percent of background load swings it — the
    unmodified parent tree measured up to 58 us under load (~50%
    flake at the old bar); 120 us keeps 2x headroom over the observed
    noise floor while staying an order of magnitude under any real
    regression."""
    from tensorflow_train_distributed_tpu.models.llama import (
        LLAMA_PRESETS,
        LlamaModel,
    )
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    cfg = LLAMA_PRESETS["llama_tiny"]
    params = LlamaModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    eng = ServingEngine(cfg, params, slots=2, cache_len=16, chunk=2,
                        prompt_buckets=(8,))
    rid = eng.submit([1, 2, 3], 4)
    eng.run()                          # warm: decode program compiled
    del rid
    inner = type(eng)._decode_chunk.__wrapped__
    tok = jnp.zeros((2,), jnp.int32)
    seeds = jnp.zeros((2,), jnp.uint32)
    counts = jnp.zeros((2,), jnp.int32)
    n = 500
    cache = eng._cache                 # donated: thread the returned one
    best = float("inf")
    for _ in range(6):                 # more reps: the min needs one
        t0 = time.perf_counter()       # quiet rep to land under the bar
        for _ in range(n):
            cache, _, _, _, _ = eng._decode_chunk(
                eng._variables, cache, tok, seeds, counts)
        t1 = time.perf_counter()
        for _ in range(n):
            cache, _, _, _, _ = inner(
                eng, eng._variables, cache, tok, seeds, counts)
        t2 = time.perf_counter()
        best = min(best, ((t1 - t0) - (t2 - t1)) / n)
    per_op = max(0.0, best)
    assert per_op < 120e-6, f"{per_op * 1e6:.2f} us/dispatch overhead"


def test_dead_instance_groups_are_purged():
    """Long-lived armed processes churn engines/trainers: a dead
    instance's signature groups must not accumulate forever — the
    instance token carries a weakref finalizer that drops its groups
    at gc (the ``_prefix_caches`` unbounded-growth lesson, applied to
    the sanitizer's own bookkeeping)."""
    import gc

    class _Owner:
        pass

    owner = _Owner()
    # Through the seam's ``group=`` (jax never sees the owner, so its
    # jit cache cannot pin it alive — the engine/trainer lifecycle).
    f = compilecheck.jit(lambda x: x + 1, site="test.purge",
                         group=owner)
    f(jnp.ones((3,)))
    tok = ("tok", owner.__ttd_cc_token__)
    assert any(compilecheck._skey_contains(k[1], tok)
               for k in compilecheck._GROUPS), "group should exist"
    del owner, f
    gc.collect()
    assert not any(compilecheck._skey_contains(k[1], tok)
                   for k in compilecheck._GROUPS), \
        "dead instance's signature groups must be purged at gc"
    compilecheck.reset("test.purge")
