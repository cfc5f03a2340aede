"""The program against the plain reference for the decoder with latent
attention of two kinds (``dots3-note-1chip``), at published widths on
the chip, in float32 at ``precision=highest`` (the chip's default
multiplies float32 in bf16 passes): whether the program IS the
reference there, which PERF.md section 2 reports; kept so that it can be
run again.

    chiprun --timeout 900 -- timeout 600 python3 benchmark/check_latents_f32.py
    python3 benchmark/check_latents_f32.py --tiny      # its CPU rehearsal

NOT the cell: float32 weights of the cell's nine layers and 16 held
experts do not fit a chip's 16 GB, so ``--layers`` (5: the dense layer,
one full and three window layers) of the file's nine run and ``HELD``
(4) of the router's 256 experts are held; every width, the router's 256
outputs and 8 experts a token are as published, the weights the cell's
own (``serve_latents.seeded_latents``).

One sequence: a prompt of ``--prompt`` tokens (past the window and past
``index_topk`` rows) through the batch-1 prefill model in pieces of
1024, ``_paged_insert`` into lane 1 of a two-lane grid, then
``--steps`` paged decode steps through the decode model (the kernels
compiled), teacher-forced; against ``dots3_note.logits_at`` over the
prompt's last piece and every decode step.

One JSON line of readings, then ``{"ok": ...}`` (agreement to ``--tol``
at every row).
"""

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness import serve_family, serve_latents, weights  # noqa: E402
from benchmark.references import dots3_note as reference  # noqa: E402
from tensorflow_train_distributed_tpu.models import moe  # noqa: E402
from tensorflow_train_distributed_tpu.serving import ServingEngine  # noqa: E402

SEED = 2 ** 31 + 4646
HELD = 4


def log(**kw):
    print(json.dumps(kw, default=float), flush=True)


def program_logits(cfg, params, seq, n_prompt, *, piece, cache_len, block):
    """Float32 logits of the ENGINE's programs over ``seq``: the prompt
    in ``piece``-token pieces on the batch-1 cache (the last piece's
    rows), the insert into lane 1 of a two-lane grid, then one paged
    decode step a token, teacher-forced."""
    eng = ServingEngine(cfg, params, slots=2, chunk=4, cache_len=cache_len,
                        kv_block_size=block, prefill_chunk=piece)
    variables = eng._variables

    @jax.jit
    def call(variables, cache_1, toks):
        logits, vs = eng._prefill_model.apply(
            dict(variables, cache=cache_1), toks, mutable=["cache"])
        return logits[0].astype(jnp.float32), vs["cache"]

    cache_1 = eng._fresh_cache(1)
    padded = np.zeros(-(-n_prompt // piece) * piece, np.int32)
    padded[:n_prompt] = seq[:n_prompt]
    for i in range(len(padded) // piece):
        last, cache_1 = call(variables, cache_1, jnp.asarray(
            padded[None, i * piece:(i + 1) * piece]))
    first = (len(padded) // piece - 1) * piece
    kv = eng._kv_claim(0, [int(t) for t in seq[:n_prompt]],
                       len(seq) - n_prompt)
    cache = eng._paged_insert(
        eng._fresh_cache(2, grid=True), cache_1, jnp.int32(1),
        eng._kv_table(kv), jnp.int32(0), jnp.int32(n_prompt))

    @jax.jit
    def decode(variables, cache, toks):
        def step(cache, t):
            logits, upd = eng._model.apply(
                dict(variables, cache=cache),
                jnp.stack([jnp.int32(3), t])[:, None],
                mutable=["cache", "moe_stats", "attn_stats"])
            return upd["cache"], logits[1, -1].astype(jnp.float32)
        return jax.lax.scan(step, cache, toks)

    _, dec = decode(variables, cache, jnp.asarray(seq[n_prompt:]))
    return (first, np.asarray(last)[:n_prompt - first], np.asarray(dec),
            bool(eng.fused_attn()))


def file_keys_of(cfg) -> dict:
    """The configuration-file keys the reference reads, for a program
    config at test size (the source's own names)."""
    sizes = [cfg.latent_sizes(i) for i in range(cfg.num_layers)]
    out = {
        "num_hidden_layers": cfg.num_layers, "rms_norm_eps": cfg.rms_epsilon,
        "apply_mla_qkv_lora_rescale": cfg.lora_rescale,
        "num_experts_per_tok": cfg.top_k, "norm_topk_prob": True,
        "routed_scaling_factor": cfg.routed_scaling,
        "index_n_heads": cfg.index_heads, "index_head_dim": cfg.index_dim,
        "index_topk": cfg.index_topk, "experts_offset": cfg.experts_offset,
        "layer_types": ["sliding_attention" if k.window is not None
                        else "full_attention" for k in sizes]}
    for k in sizes:
        pre = "" if k.window is None else "swa_"
        if k.window is not None:
            out["sliding_window_size"] = k.window
        out.update({
            pre + "num_attention_heads": k.num_heads,
            pre + "q_lora_rank": k.q_lora_rank,
            pre + "kv_lora_rank": k.kv_lora_rank,
            pre + "qk_nope_head_dim": k.qk_nope_dim,
            pre + "qk_rope_head_dim": k.qk_rope_dim,
            pre + "v_head_dim": k.v_head_dim, pre + "rope_theta": k.rope_base})
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--tiny", action="store_true",
                   help="the CPU rehearsal at test size")
    p.add_argument("--layers", type=int, default=5)
    p.add_argument("--prompt", type=int, default=3000)
    p.add_argument("--steps", type=int, default=48)
    p.add_argument("--tol", type=float, default=1e-3)
    args = p.parse_args(argv)

    if args.tiny:
        moe.GMM_INTERPRET = True
        os.environ.setdefault("TTD_FUSED_ATTN_INTERPRET", "1")
        base = moe.MOE_PRESETS["dots3_note_tiny"]
        cfg_file = None
        sizes = dict(piece=8, cache_len=96, block=4)
        n_prompt, steps = 37, 24
    else:
        with open(os.path.join(REPO, "benchmark", "configs",
                               "dots3-note-1chip.json")) as f:
            cfg_file = json.load(f)
        base = dataclasses.replace(
            serve_latents.latents_config(cfg_file), num_layers=args.layers,
            experts_held=HELD)
        sizes = dict(piece=1024, cache_len=4096, block=16)
        n_prompt, steps = args.prompt, args.steps
    seq = np.random.default_rng(46).integers(
        3, base.vocab_size, n_prompt + steps).astype(np.int32)
    cfg = dataclasses.replace(base, dtype=jnp.float32)
    t0 = time.monotonic()
    params = serve_latents.seeded_latents(weights.make_params(
        serve_family.moe_param_shapes(cfg), SEED, jnp.float32))
    with jax.default_matmul_precision("highest"):
        first, pre, dec, fused = program_logits(
            cfg, params, seq, n_prompt, **sizes)
    ours = np.concatenate([pre, dec])
    rows = list(range(first, n_prompt + steps))
    ref_cfg = (file_keys_of(cfg) if args.tiny
               else dict(cfg_file, num_hidden_layers=cfg.num_layers))
    want = np.asarray(reference.logits_at(
        params, ref_cfg, [int(t) for t in seq], rows))
    off = np.abs(ours - want)
    log(layers=cfg.num_layers, fused=fused, rows=len(rows),
        seconds=time.monotonic() - t0, max_abs=float(off.max()),
        mean_abs=float(off.mean()),
        max_abs_decode=float(off[len(pre):].max()),
        max_abs_piece=float(off[:len(pre)].max()),
        logit_std=float(want.std()), logit_max=float(np.abs(want).max()),
        same_first_choice=float(
            (ours.argmax(-1) == want.argmax(-1)).mean()))
    ok = float(off.max()) < args.tol
    log(ok=ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
