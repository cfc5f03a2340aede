#!/usr/bin/env python3
"""The quickest proof that train → checkpoint → serve still starts on the chip.

    python chip_smoke.py             # one chip: device, kernels, train, serve
    python chip_smoke.py --chips 4   # ONLY the mesh path: data=2,tensor=2
                                     # over four chips vs one of them

One process holds the chip for the whole run and drives the system
through the entry points a user calls: ``launch.main`` trains
``llama_350m_lm`` exactly as registered and saves one checkpoint;
``tools/serve_http.py``'s gateway serves that checkpoint over HTTP with
every default on (paged KV, fused paged attention).
Nothing here may fall back: the TPU platform is forced, an unknown
``device_kind`` is an error, every kernel must appear as a
``tpu_custom_call`` in its lowered program, and any phase that raises
makes the exit code non-zero.

The LAST stdout line is the result, and only a run that passed every
phase on a TPU prints ``"ok": true``:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Earlier lines are one JSON object per phase (losses, step times, compile
seconds, persistent-cache hits, peak HBM) — smoke output, not a
benchmark.  They are also written to ``chiprun_out/``.

``--rehearse-cpu`` with ``--config llama_tiny_sft`` is the no-chip
rehearsal (on-chip-measurement guide §2.1/2.2): the same control flow on
the CPU backend, kernels interpreted.  It always ends ``"ok": false``
with a non-zero exit — it proves the script, never the chip.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import http.client
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))   # serve_http, serve, sample

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Tolerances, as max|got - want| / max|want| unless stated, with reasons.
TOL = {
    # f32 math inside both paths, one bf16 rounding of the result: the
    # two may land one bf16 ulp (2^-8) apart.
    "rms_norm": 1e-2,
    # dscale sums 8192 rows of bf16-rounded products in another order.
    "rms_norm_dscale": 2e-2,
    # f32 in, f32 out; online vs two-pass logsumexp over 32k columns.
    "fused_ce": 1e-4,
    # bf16 operands on the MXU with f32 accumulation, blockwise online
    # softmax vs one dense softmax: a few bf16 ulps of the output scale.
    "flash": 2e-2,
    # the backward re-rounds p and ds to bf16 per block.
    "flash_grad": 4e-2,
    # f32 math in the kernel vs bf16 einsums in the reference.
    "paged_attention": 2e-2,
    # |loss_mesh - loss_one_chip| per step, absolute, on a loss near
    # ln(32000) = 10.4: bf16 matmuls re-associated across the tensor
    # axis, and the vocab-sharded jnp CE on the mesh vs the fused CE
    # kernel on one chip; drift compounds over the steps.
    "mesh_loss_abs": 5e-2,
    # greedy parity vs models.generate: a token may differ only where
    # the reference's own top-2 logits are closer than this fraction of
    # that position's logit std (a bf16 near-tie — random-ish weights
    # have near-flat logits, gap ~ std/4.5 at vocab 32k).
    "greedy_tie_std": 0.1,
}


class Compiles:
    """Backend compiles and persistent-cache traffic, from jax.monitoring."""

    def __init__(self):
        self.events = []          # (wall time at end, seconds)
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.time(), secs))

    def _evt(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return len(self.events), self.hits, self.misses

    def since(self, mark) -> dict:
        n, h, m = mark
        return {"compiles": len(self.events) - n,
                "compile_s": round(sum(s for _, s in self.events[n:]), 2),
                "cache_hits": self.hits - h, "cache_misses": self.misses - m}


def emit(record: dict, sink) -> None:
    line = json.dumps(record)
    print(line, flush=True)
    sink.write(line + "\n")
    sink.flush()


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def hbm(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats.get(k) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


# ── device ──────────────────────────────────────────────────────────────


def phase_device(opts) -> dict:
    from tensorflow_train_distributed_tpu.training.memory import tpu_peaks

    devs = jax.devices()
    d0 = devs[0]
    out = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devs), "bytes_limit": hbm(d0)["bytes_limit"]}
    if opts.rehearse_cpu:
        return out
    if d0.platform != "tpu":
        raise RuntimeError(f"need a TPU, JAX found {d0.platform!r}")
    if len(devs) != opts.chips:
        raise RuntimeError(f"--chips {opts.chips} but JAX sees {len(devs)}")
    out["peaks"] = tpu_peaks(d0.device_kind)       # unknown kind raises
    return out


# ── kernels ─────────────────────────────────────────────────────────────


def _compiled_not_interpreted(opts, fn, *args) -> bool:
    """True when ``fn``'s lowered program holds a Mosaic kernel.  On the
    chip a missing one is an error (a quiet reference path or interpret
    mode must not pass); the rehearsal interprets, so there is none."""
    has = "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()
    if not has and not opts.rehearse_cpu:
        raise AssertionError("no tpu_custom_call in the lowered program")
    return has


@contextlib.contextmanager
def _kernels_interpreted():
    """The rehearsal's stand-in for a chip: the same kernel calls run in
    Pallas TPU interpret mode on the CPU, and the flash dispatcher,
    which asks ``default_backend()``, is steered to its TPU branch."""
    from jax.experimental.pallas import tpu as pltpu

    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        with pltpu.force_tpu_interpret_mode():
            yield
    finally:
        jax.default_backend = real


def phase_kernels(opts, entry) -> dict:
    """Each main-path kernel against its pure-jax reference, at the
    shapes the train and serve phases use."""
    with (_kernels_interpreted() if opts.rehearse_cpu
          else contextlib.nullcontext()):
        return _kernel_checks(opts, entry["task_factory"]().config,
                              entry["global_batch_size"],
                              entry["dataset_kwargs"]["seq_len"])


def _kernel_checks(opts, cfg, batch: int, seq: int) -> dict:
    from tensorflow_train_distributed_tpu.ops import attention
    from tensorflow_train_distributed_tpu.ops import pallas_kernels as pk

    key = iter(jax.random.split(jax.random.key(opts.seed), 32))
    d, heads = cfg.d_model, cfg.num_heads
    kvh = cfg.num_kv_heads or heads
    hd = getattr(cfg, "head_dim", None) or d // heads
    dt = cfg.dtype
    out = {}

    # rms_norm at the train shape (fwd + grad) and the decode shape.
    x = jax.random.normal(next(key), (batch, seq, d), dt)
    scale = 1 + 0.1 * jax.random.normal(next(key), (d,), jnp.float32)

    def rms(x, s):
        return pk.rms_norm(x, s, use_pallas=True)

    def rms_loss(f):
        return lambda x, s: (f(x, s).astype(jnp.float32) ** 2).sum()

    _compiled_not_interpreted(opts, rms, x, scale)
    out["rms_norm"] = rel_err(jax.jit(rms)(x, scale),
                              pk.rms_norm_reference(x, scale))
    gx, gs = jax.jit(jax.grad(rms_loss(rms), (0, 1)))(x, scale)
    rx, rs = jax.jit(jax.grad(rms_loss(pk.rms_norm_reference), (0, 1)))(
        x, scale)
    out["rms_norm_dx"] = rel_err(gx, rx)
    out["rms_norm_dscale"] = rel_err(gs, rs)
    xd = jax.random.normal(next(key), (opts.slots, 1, d), dt)
    out["rms_norm_decode"] = rel_err(jax.jit(rms)(xd, scale),
                                     pk.rms_norm_reference(xd, scale))
    assert max(out["rms_norm"], out["rms_norm_dx"],
               out["rms_norm_decode"]) <= TOL["rms_norm"], out
    assert out["rms_norm_dscale"] <= TOL["rms_norm_dscale"], out

    # fused cross-entropy at [batch*seq, vocab] f32, fwd + grad.
    logits = jax.random.normal(next(key), (batch, seq, cfg.vocab_size),
                               jnp.float32)
    labels = jax.random.randint(next(key), (batch, seq), 0, cfg.vocab_size)

    def ce(lg):
        return pk.fused_cross_entropy(lg, labels, use_pallas=True)

    _compiled_not_interpreted(opts, ce, logits)
    out["fused_ce"] = rel_err(jax.jit(ce)(logits),
                              pk.cross_entropy_reference(logits, labels))
    out["fused_ce_grad"] = rel_err(
        jax.jit(jax.grad(lambda lg: ce(lg).sum()))(logits),
        jax.jit(jax.grad(lambda lg: pk.cross_entropy_reference(
            lg, labels).sum()))(logits))
    assert max(out["fused_ce"], out["fused_ce_grad"]) <= TOL["fused_ce"], out
    del logits

    # the flash call, through the dispatcher the model calls.
    q, k, v = (jax.random.normal(next(key), (batch, heads, seq, hd), dt)
               for _ in range(3))
    if attention._pallas_friendly(q, k, v):
        def flash(q, k, v):
            return attention.multihead_attention_kernel(q, k, v,
                                                        causal=True)

        def ref(q, k, v):
            return attention.dot_product_attention(q, k, v, causal=True)

        def att_loss(f):
            return lambda q, k, v: f(q, k, v).astype(jnp.float32).sum()

        _compiled_not_interpreted(opts, flash, q, k, v)
        out["flash"] = rel_err(jax.jit(flash)(q, k, v),
                               jax.jit(ref)(q, k, v))
        got = jax.jit(jax.grad(att_loss(flash), (0, 1, 2)))(q, k, v)
        want = jax.jit(jax.grad(att_loss(ref), (0, 1, 2)))(q, k, v)
        out["flash_grad"] = max(rel_err(g, w) for g, w in zip(got, want))
        assert out["flash"] <= TOL["flash"], out
        assert out["flash_grad"] <= TOL["flash_grad"], out
    else:
        # Not on this config's path (the model takes the reference
        # attention at this seq/head_dim on any backend).
        out["flash"] = "not on this config's path"

    # paged KV gather (bit-exact) and fused paged attention, at the
    # serve phase's pool: bf16 and int8, q_len 1 and a speculative 4.
    bs, lanes, c = 16, opts.slots, opts.cache_len
    n_blk = -(-c // bs)
    nb = 1 + lanes * n_blk
    kp, vp = (jax.random.normal(next(key), (nb, bs, kvh * hd), dt)
              for _ in range(2))       # rows as the cache stores them
    table = (1 + jax.random.permutation(next(key), lanes * n_blk)
             ).reshape(lanes, n_blk).astype(jnp.int32)

    def gather(p, t):
        return pk.paged_kv_gather(p, t, c, use_pallas=True)

    _compiled_not_interpreted(opts, gather, kp, table)
    out["paged_kv_gather_exact"] = bool(jnp.array_equal(
        jax.jit(gather)(kp, table),
        pk.paged_kv_gather_reference(kp, table, c)))
    assert out["paged_kv_gather_exact"], out
    k8 = jnp.clip(jnp.round(kp.astype(jnp.float32) * 40), -127, 127
                  ).astype(jnp.int8)
    v8 = jnp.clip(jnp.round(vp.astype(jnp.float32) * 40), -127, 127
                  ).astype(jnp.int8)
    ks, vs = (jnp.abs(jax.random.normal(next(key), (nb, bs, kvh),
                                        jnp.float32)) / 40 + 1e-3
              for _ in range(2))
    for q_len in (1, 4):
        qq = jax.random.normal(next(key), (lanes, q_len, heads, hd), dt)
        lens = jax.random.randint(next(key), (lanes,), 1, c - q_len)
        for name, pools, scales in (
                ("bf16", (kp, vp), {}),
                ("int8", (k8, v8), {"k_scales": ks, "v_scales": vs})):
            def attn(qq, kk, vv, **kw):
                return pk.paged_attention(qq, kk, vv, table, lens,
                                          cache_len=c, use_pallas=True,
                                          **kw)

            _compiled_not_interpreted(opts, attn, qq, *pools)
            err = rel_err(
                jax.jit(attn)(qq, *pools, **scales),
                jax.jit(lambda qq, kk, vv, **kw:
                        pk.paged_attention_reference(
                            qq, kk, vv, table, lens, cache_len=c, **kw))(
                    qq, *pools, **scales))
            out[f"paged_attention_{name}_q{q_len}"] = err
            assert err <= TOL["paged_attention"], out
    return {k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in out.items()}


# ── train ───────────────────────────────────────────────────────────────


def _platform_args(opts, devices: int = 1) -> list:
    if opts.rehearse_cpu:
        return ["--platform", "cpu", "--cpu-devices", str(devices)]
    return ["--platform", "tpu"]


def phase_train(opts, entry, ckpt_dir: str, log_path: str,
                compiles: Compiles) -> dict:
    from tensorflow_train_distributed_tpu import launch
    from tensorflow_train_distributed_tpu.training.checkpoint import (
        COMMIT_MARKER,
    )

    t0 = time.time()
    rc = launch.main([
        "--config", opts.config, *_platform_args(opts),
        "--steps", str(opts.steps), "--log-every", "1",
        "--seed", str(opts.seed), "--checkpoint-dir", ckpt_dir,
        "--jsonl-log", log_path])
    if rc != 0:
        raise RuntimeError(f"launch.main returned {rc}")
    # The trainer and its callbacks reference each other, so the train
    # state (params + Adam, several GB of HBM) outlives launch.main
    # until the cycle collector runs (seen on the chip: 4.5 GB still in
    # use after the phase).  Free it before the engine needs the room.
    gc.collect()
    with open(log_path) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    losses = [r["loss"] for r in recs]
    if len(losses) != opts.steps or not all(map(math.isfinite, losses)):
        raise AssertionError(f"want {opts.steps} finite losses: {losses}")
    marker = os.path.join(ckpt_dir, str(opts.steps), COMMIT_MARKER)
    if not os.path.exists(marker):
        raise AssertionError(f"no commit marker at {marker}")
    ts = [r["ts"] for r in recs]
    late = [s for t, s in compiles.events if ts[1] < t <= ts[-1]]
    if late:
        raise AssertionError(f"{len(late)} compilations after step 2")
    steps_s = [round(b - a, 4) for a, b in zip(ts, ts[1:])]
    batch = entry["global_batch_size"]
    seq = entry.get("dataset_kwargs", {}).get("seq_len", 0)
    return {"config": opts.config, "steps": len(losses),
            "mosaic_calls_in_step": train_step_kernels(opts, entry),
            "losses": [round(x, 4) for x in losses],
            "step_s_after_first": steps_s,
            "tokens_per_s_smoke": round(
                batch * seq / float(np.median(steps_s)), 1),
            "checkpoint": marker.replace(ckpt_dir, "<ckpt>"),
            "wall_s": round(time.time() - t0, 1)}


def train_step_kernels(opts, entry) -> int:
    """Kernel presence in the train step itself: lower (not compile) the
    step of a Trainer built the way ``launch.run`` builds it and count
    its Mosaic calls — rms_norm, flash and the fused CE must be there,
    forward and backward."""
    from tensorflow_train_distributed_tpu import launch
    from tensorflow_train_distributed_tpu.data.datasets import get_dataset
    from tensorflow_train_distributed_tpu.runtime.mesh import build_mesh
    from tensorflow_train_distributed_tpu.training import (
        Policy, Trainer, TrainerConfig,
    )

    args = launch.build_parser().parse_args(
        ["--config", opts.config, "--steps", str(opts.steps)])
    optimizer, lr_schedule = launch._make_optimizer(args, entry)
    trainer = Trainer(
        entry["task_factory"](), optimizer,
        build_mesh(devices=jax.devices()[:1]), lr_schedule=lr_schedule,
        policy=Policy.from_name(args.precision),
        config=TrainerConfig(seed=opts.seed))
    source = get_dataset(entry["dataset"],
                         **launch._dataset_kwargs(entry, args))
    batch = jax.tree.map(
        lambda x: np.stack([x] * entry["global_batch_size"]), source[0])
    n = trainer.lower_train_step(batch).as_text().count("tpu_custom_call")
    # fwd+bwd of: 2 norms per (scanned) layer + the final norm, the
    # flash call (1 fwd, 2 bwd kernels), the fused CE.
    if n < 11 and not opts.rehearse_cpu:
        raise AssertionError(f"train step lowers {n} Mosaic calls, want "
                             ">= 11 (a kernel took its reference path)")
    return n


# ── serve ───────────────────────────────────────────────────────────────


def _http(port: int, path: str, body: dict | None = None):
    """(status, text) of a GET, or of a JSON POST when ``body`` is given."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        if body is None:
            conn.request("GET", path)
        else:
            conn.request("POST", path, json.dumps(body),
                         {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read().decode()
    finally:
        conn.close()


def _greedy_parity(cfg, params, prompt, served) -> str:
    """``served`` (prompt + generated, from the gateway) against
    ``models.generate.generate`` on the same params: equal, or first
    differing where the reference's own logits are a bf16 near-tie."""
    from tensorflow_train_distributed_tpu.models.generate import (
        cast_floating, generate,
    )
    from tensorflow_train_distributed_tpu.models.llama import LlamaModel

    n_new = len(served) - len(prompt)
    want = np.asarray(generate(
        cfg, params, jnp.asarray([prompt], jnp.int32), n_new))[0].tolist()
    if served == want:
        return "equal"
    i = next(j for j, (a, b) in enumerate(zip(served, want)) if a != b)
    if i < len(prompt):
        raise AssertionError("the served prompt itself differs")
    logits = LlamaModel(cfg).apply(
        {"params": cast_floating(params, cfg.dtype)},
        jnp.asarray([want[:i]], jnp.int32))[0, -1].astype(jnp.float32)
    gap = float(abs(logits[want[i]] - logits[served[i]]) / logits.std())
    if gap > TOL["greedy_tie_std"]:
        raise AssertionError(
            f"greedy output leaves the reference at token {i} where the "
            f"reference's logits differ by {gap:.3f} std (no tie)")
    return f"equal up to a near-tie at token {i} (gap {gap:.4f} std)"


def phase_serve(opts, ckpt_dir: str) -> dict:
    import serve_http
    from sample import resolve_decoder_task

    from tensorflow_train_distributed_tpu.training.checkpoint import (
        CheckpointManager,
    )

    args = serve_http.build_parser().parse_args([
        "--config", opts.config, "--checkpoint-dir", ckpt_dir,
        "--slots", str(opts.slots), "--chunk", str(opts.chunk),
        "--cache-len", str(opts.cache_len), "--host", "127.0.0.1",
        "--port", "0", "--watchdog-timeout", "0"])
    _, cfg, is_moe = resolve_decoder_task(args.config, "serving")
    t0 = time.time()
    gw = serve_http.build_gateway(args, cfg, is_moe, [])
    eng = gw.engine
    out = {"fused_attn": eng._fused_attn,
           "kv_pool_gib": round(eng._kv_pool_bytes / 2**30, 3)}
    want_fused = not opts.rehearse_cpu
    if eng._fused_attn != want_fused:
        raise AssertionError(f"serving defaults are not all on: {out}")
    gw.start()
    try:
        # Prompt lengths 16-512 over three of the engine's buckets and
        # max_new 32-64 at the default cache of 2048, scaled down with
        # the rehearsal's cache; the second request streams.
        scale = opts.cache_len / 2048
        lens = [max(1, int(n * scale)) for n in (16, 125, 261, 512)]
        news = [max(4, int(n * scale)) for n in (32, 48, 64, 32)]
        rng = np.random.default_rng(opts.seed)
        reqs = [(rng.integers(3, cfg.vocab_size, n).tolist(), m)
                for n, m in zip(lens, news)]
        answers = []
        for i, (prompt, max_new) in enumerate(reqs):
            stream = i == 1
            status, body = _http(gw.port, "/v1/generate", {
                "prompt": prompt, "max_new": max_new, "stream": stream})
            if status != 200:
                raise AssertionError(f"request {i}: HTTP {status} {body}")
            if stream:
                chunks = [json.loads(ln) for ln in body.splitlines()]
                if chunks[-1] != {"done": True}:
                    raise AssertionError(f"stream ended {chunks[-1]}")
                got = sum(len(ch.get("tokens", [])) for ch in chunks)
                tokens = None
            else:
                tokens = json.loads(body)["tokens"]
                if tokens[:len(prompt)] != prompt:
                    raise AssertionError(f"request {i}: prompt not echoed")
                got = len(tokens) - len(prompt)
            if got != max_new:
                raise AssertionError(
                    f"request {i}: {got} tokens, asked for {max_new}")
            answers.append(tokens)
        status, body = _http(gw.port, "/healthz")
        health = json.loads(body)
        if status != 200 or health["status"] != "ok":
            raise AssertionError(f"/healthz {status} {health}")
        status, body = _http(gw.port, "/metrics")
        counted = sum(
            float(ln.split()[-1]) for ln in body.splitlines()
            if ln.startswith("ttd_gateway_tokens_generated_total"))
        if status != 200 or counted != sum(news):
            raise AssertionError(
                f"/metrics counts {counted} tokens, decoded {sum(news)}")
        mgr = CheckpointManager(ckpt_dir, async_save=False)
        params = mgr.restore_params()
        mgr.close()
        out["greedy_vs_generate"] = _greedy_parity(
            cfg, params, reqs[0][0], answers[0])
        del params
    finally:
        drained = gw.drain(timeout=120)
    if not drained:
        raise AssertionError("gateway did not drain")
    out.update(requests=len(reqs), prompt_lens=lens, max_new=news,
               streamed=1, tokens_counted=int(counted), drained=drained,
               wall_s=round(time.time() - t0, 1))
    return out


# ── four chips: the mesh path and its one-chip comparison ───────────────


def phase_mesh(opts) -> dict:
    """The same job, seed and global batch on a data=2,tensor=2 mesh
    over all four devices and on a mesh of one of them, through
    ``launch.run`` (its ``devices=`` argument is the ``build_mesh``
    seam; no program option)."""
    from tensorflow_train_distributed_tpu import launch

    parser = launch.build_parser()
    common = ["--config", opts.config, *_platform_args(opts, 4),
              "--steps", str(opts.mesh_steps), "--log-every", "1",
              "--seed", str(opts.seed)]
    devs = jax.devices()
    r4 = launch.run(parser.parse_args(
        common + ["--strategy", "dp_tp", "--mesh", "data=2,tensor=2"]))
    holders = sorted({d.id for leaf in jax.tree.leaves(r4.state.params)
                      for d in leaf.sharding.device_set})
    split = sum(1 for leaf in jax.tree.leaves(r4.state.params)
                if not leaf.sharding.is_fully_replicated)
    in_use = {d.id: hbm(d)["bytes_in_use"] for d in devs}
    out = {"mesh": {k: v for k, v in r4.mesh.shape.items() if v > 1},
           "param_devices": holders, "param_leaves_sharded": split,
           "bytes_in_use": in_use,
           "losses_mesh": [round(x, 4) for x in r4.history["loss"]]}
    if holders != sorted(d.id for d in devs) or len(holders) != 4:
        raise AssertionError(f"params live on {holders}, not 4 devices")
    if not split:
        raise AssertionError("no parameter is sharded over the mesh")
    if not opts.rehearse_cpu and not all(in_use.values()):
        raise AssertionError(f"a device holds nothing: {in_use}")
    loss4 = list(r4.history["loss"])
    # Free the four-chip state before one chip takes the whole job (the
    # trainer's reference cycle keeps it until a collection).
    del r4
    gc.collect()

    r1 = launch.run(parser.parse_args(common + ["--strategy", "dp"]),
                    devices=devs[:1])
    loss1 = list(r1.history["loss"])
    del r1
    diffs = [abs(a - b) for a, b in zip(loss4, loss1)]
    out.update(losses_one_chip=[round(x, 4) for x in loss1],
               max_abs_diff=round(max(diffs), 5),
               tolerance=TOL["mesh_loss_abs"])
    if (len(loss4) != opts.mesh_steps or len(loss1) != opts.mesh_steps
            or not all(map(math.isfinite, loss4 + loss1))):
        raise AssertionError(f"want {opts.mesh_steps} finite losses each")
    if max(diffs) > TOL["mesh_loss_abs"]:
        raise AssertionError(f"losses disagree: {out}")
    return out


# ── driver ──────────────────────────────────────────────────────────────


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4 = run ONLY the mesh phase and its one-chip "
                        "comparison")
    p.add_argument("--config", default="llama_350m_lm")
    p.add_argument("--steps", type=int, default=6)
    p.add_argument("--mesh-steps", type=int, default=4)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--chunk", type=int, default=8)
    p.add_argument("--cache-len", type=int, default=2048)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="no-chip rehearsal on the CPU backend, kernels "
                        "interpreted; always exits non-zero")
    opts = p.parse_args(argv)

    from tensorflow_train_distributed_tpu.models import registry
    from tensorflow_train_distributed_tpu.runtime import compile_cache
    from tensorflow_train_distributed_tpu.runtime.mesh import force_platform

    # Before any backend exists.  Without --rehearse-cpu this is the
    # TPU or nothing: with no chip jax.devices() raises below, the exit
    # code is non-zero and no result line is printed.
    if opts.rehearse_cpu:
        force_platform("cpu", opts.chips)
    else:
        force_platform("tpu")
    cache_dir = compile_cache.place_compile_cache()
    compiles = Compiles()
    entry = registry.get_entry(opts.config)

    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    sink = open(os.path.join(
        REPO, "chiprun_out", f"chip_smoke_{opts.chips}chip.jsonl"), "a")
    # Params plus Adam state are several GB: a scratch directory of the
    # machine's own, not what the chip tool copies back; removed below.
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    ok = True
    try:
        # No accelerator (or an unknown one) raises straight out: exit
        # code non-zero and no result line at all.
        device = phase_device(opts)
        emit({"phase": "device", "ok": True, **device}, sink)
        ckpt = os.path.join(work, "ckpt")
        if opts.chips == 4:
            phases = [("mesh", lambda: phase_mesh(opts))]
        else:
            phases = [
                ("kernels", lambda: phase_kernels(opts, entry)),
                ("train", lambda: phase_train(
                    opts, entry, ckpt, os.path.join(work, "train.jsonl"),
                    compiles)),
                ("serve", lambda: phase_serve(opts, ckpt))]
        for name, fn in phases:
            mark, t0 = compiles.mark(), time.time()
            try:
                rec = {"phase": name, "ok": True, **fn()}
            except Exception as e:      # recorded; fails the run below
                traceback.print_exc()
                rec = {"phase": name, "ok": False,
                       "error": f"{type(e).__name__}: {e}"[:2000]}
                ok = False
            rec.update(compiles.since(mark),
                       phase_s=round(time.time() - t0, 1),
                       hbm=hbm(jax.devices()[0]))
            emit(rec, sink)
            if not ok:
                break
        emit({"phase": "summary", "cache_dir": cache_dir,
              **compiles.since((0, 0, 0))}, sink)
    finally:
        shutil.rmtree(work, ignore_errors=True)
        sink.close()
    ok = ok and not opts.rehearse_cpu      # phase_device vouched for "tpu"
    result = {"ok": ok, "device": {k: device[k] for k in
                                   ("platform", "kind", "count")}}
    if opts.rehearse_cpu:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
