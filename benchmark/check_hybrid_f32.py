"""Float32 program against the plain reference for the decoder with
delta-rule linear-attention layers (``ling3-flash-1chip``), at published
widths on the chip: the comparison PERF.md section 2 reports, kept so
that it can be run again.

    chiprun --timeout 1500 -- python3 benchmark/check_hybrid_f32.py
    python3 benchmark/check_hybrid_f32.py --tiny        # its CPU rehearsal

NOT the cell: float32 weights of the cell's 128 held experts do not fit
a chip's 16 GB, so ``HELD`` (32) of the router's 512 are held; every
width, the router's 512 outputs, 8 groups and 8 experts a token are as
published, at the configuration file's 7 layers (``--layers``: fewer,
to tell a layer's own error from the router's).

1. logits: a prompt of 9,700 tokens through the model call of
   ``_prefill_piece`` in calls of 4, 4, 1, 1 pieces of 1024 (the last
   with 540 rows of padding), ``_paged_insert`` into a slot that held a
   longer request's state, 520 paged decode steps through the decode
   model (``delta_state_step`` compiled), teacher-forced; against
   ``ling_hybrid.logits_at`` over the prompt's last piece and every
   decode step.
2. the experts chosen: at those rows of the prompt, in each expert
   layer, the experts the program's router scores choose and those the
   reference's choose (``ling_hybrid.chosen`` on each side's scores);
   for the rows whose logits part most, the layers where the two sets
   differ and each disputed expert's choice value (score + bias) on
   both sides, beside the two sides' largest difference of score.
3. the engine whole on one slot: a long request, then the compared one
   into the same slot; its served tokens against the reference
   (``served_gaps``).

One JSON line a phase; the last is ``{"ok": ...}`` (float32 agreement to
5e-3 at every row, which the chip does not reach: PERF.md section 2).
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

from benchmark.harness import serve_family, serve_hybrid, weights  # noqa: E402
from benchmark.references import ling_hybrid as reference  # noqa: E402
from tensorflow_train_distributed_tpu.models import moe  # noqa: E402
from tensorflow_train_distributed_tpu.serving import ServingEngine  # noqa: E402

SEED = 2 ** 31 + 3939
HELD = 32


def log(**kw):
    print(json.dumps(kw, default=float), flush=True)


def reference_scores(params, cfg_file, tokens, rows, pad_to):
    """The reference's router scores [expert layers, len(rows), E]."""
    cfg = dict(reference._static(cfg_file))
    toks = np.zeros(pad_to, np.int32)
    toks[:len(tokens)] = tokens

    @jax.jit
    def run(params, toks, rows):
        x = reference._f32(jnp.take(params["token_embed"]["embedding"],
                                    toks, axis=0))
        positions = jnp.arange(toks.shape[0])
        out = []
        for i in range(cfg["num_hidden_layers"]):
            w = params[f"layer_{i}"]
            if i >= cfg["first_k_dense_replace"]:
                h = reference.attended(x, w, cfg, i, positions)[rows]
                out.append(jax.nn.sigmoid(reference._mm(
                    reference.rms_norm(h, w["mlp_norm"]["scale"],
                                       cfg["rms_norm_eps"]),
                    w["moe"]["router"]["kernel"])))
            x = reference.block(x, w, cfg, i, positions)
        return jnp.stack(out)

    return np.asarray(run(params, toks, np.asarray(rows, np.int32)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--tiny", action="store_true",
                   help="the CPU rehearsal at the test cell's size")
    p.add_argument("--layers", type=int, default=None)
    args = p.parse_args(argv)

    if args.tiny:
        moe.GMM_INTERPRET = True
        with open(os.path.join(
                REPO, "tests/benchmark/cells/ling-tiny.json")) as f:
            cfg_file = json.load(f)
    else:
        with open(os.path.join(
                REPO, "benchmark/configs/ling3-flash-1chip.json")) as f:
            cfg_file = json.load(f)
        prog = cfg_file["program"]
        cfg_file = dict(
            cfg_file, num_experts=HELD, dtype="float32",
            program=dict(prog, replace=dict(
                prog["replace"], experts_held=HELD, dtype=jnp.float32)))
    cfg = serve_hybrid.hybrid_config(cfg_file)
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
        cfg_file = dict(cfg_file, num_hidden_layers=args.layers)
    log(phase="config", layers=cfg.num_layers, held=cfg.experts_held,
        cell_holds=128, dtype=str(cfg.dtype), device=str(jax.devices()[0]))
    jax.config.update("jax_default_matmul_precision", "highest")
    t0 = time.time()
    params = serve_hybrid.seeded_decay(weights.make_params(
        serve_family.moe_param_shapes(cfg), SEED, jnp.float32), SEED)
    jax.block_until_ready(params)
    log(phase="weights", s=time.time() - t0,
        gb=sum(x.nbytes for x in jax.tree.leaves(params)) / 1e9)

    n_prompt, n_dec, piece = (77, 20, 8) if args.tiny else (9700, 520, 1024)
    cache_len, block, budget, last = ((128, 4, 32, 8) if args.tiny
                                      else (12288, 16, 4096, 1024))
    rng = np.random.default_rng(39)
    seq = rng.integers(3, cfg.vocab_size, n_prompt + n_dec).astype(np.int32)
    eng = ServingEngine(cfg, params, slots=2, chunk=8, cache_len=cache_len,
                        kv_block_size=block, prefill_chunk=piece,
                        prefill_budget=budget)
    log(phase="engine", fused=bool(eng._fused_attn),
        state_layers=eng._state_layers, piece_counts=eng._piece_counts,
        state_pool_bytes=eng.state_pool_bytes(),
        kv_pool_bytes=eng.kv_pool_bytes())
    # An argument, not closed over: as constants of the lowering the
    # weights would be copied on the host.
    variables = eng._variables

    def is_router(module, method):
        return method == "__call__" and module.name == "router"

    @jax.jit
    def call(variables, cache_1, toks, pad):
        cache_1 = jax.tree_util.tree_map_with_path(
            lambda p, leaf: jnp.full_like(leaf, pad)
            if eng._path_key(p)[-1] == "pad_rows" else leaf, cache_1)
        logits, vs = eng._prefill_model.apply(
            dict(variables, cache=cache_1), toks,
            mutable=["cache", "intermediates"],
            capture_intermediates=is_router)
        routed = [jax.nn.sigmoid(v["moe"]["router"]["__call__"][0][0])
                  for k, v in sorted(vs["intermediates"].items(),
                                     key=lambda kv: int(kv[0][6:]))
                  if "moe" in v]
        return vs["cache"], logits[0], jnp.stack(routed)

    def prefill(tokens, n, keep):
        cache_1 = eng._fresh_cache(1)
        n_pieces = -(-n // piece)
        padded = np.zeros(n_pieces * piece, np.int32)
        padded[:n] = tokens[:n]
        i, got, scores, calls = 0, [], [], []
        while i < n_pieces:
            k = 4 if n_pieces - i >= 4 else 1
            pad = max(0, (i + k) * piece - n)
            cache_1, lg, routed = call(
                variables, cache_1,
                jnp.asarray(padded[None, i * piece:(i + k) * piece]),
                jnp.int32(pad))
            if keep:
                got.append(np.asarray(lg))
                scores.append(np.asarray(routed))
            calls.append((k, pad))
            i += k
        if not keep:
            return cache_1, None, None, calls
        return (cache_1, np.concatenate(got)[:n],
                np.concatenate(scores, axis=1)[:, :n], calls)

    t0 = time.time()
    grid = eng._fresh_cache(2, grid=True)
    other = rng.integers(3, cfg.vocab_size, n_prompt + (
        9 if args.tiny else 300)).astype(np.int32)
    kv0 = eng._kv_claim(0, other.tolist(), 4)
    c_other, _, _, _ = prefill(other, len(other), False)
    grid = eng._paged_insert(grid, c_other, jnp.int32(1), eng._kv_table(kv0),
                             jnp.int32(0), jnp.int32(len(other)))
    eng._kv_release(kv0)
    grid = eng._reset_lanes(grid, jnp.asarray([False, True]))
    cache_1, pre, pre_scores, calls = prefill(seq, n_prompt, True)
    kv = eng._kv_claim(1, [int(t) for t in seq[:n_prompt]], n_dec)
    grid = eng._paged_insert(grid, cache_1, jnp.int32(1), eng._kv_table(kv),
                             jnp.int32(0), jnp.int32(n_prompt))

    @jax.jit
    def decode(variables, cache, toks):
        def step(cache, t):
            logits, upd = eng._model.apply(
                dict(variables, cache=cache),
                jnp.stack([jnp.int32(3), t])[:, None],
                mutable=["cache", "moe_stats", "attn_stats"])
            return upd["cache"], logits[1, -1]
        return jax.lax.scan(step, cache, toks)

    compiled = decode.lower(variables, grid,
                            jnp.asarray(seq[n_prompt:])).compile()
    lowered = compiled.as_text()
    log(phase="decode_program",
        state_kernel="delta_state_step" in lowered,
        latent_kernel="paged_latent_attention" in lowered)
    _, dec = compiled(variables, grid, jnp.asarray(seq[n_prompt:]))
    dec = np.asarray(dec)
    log(phase="program", s=time.time() - t0, calls=calls)
    del grid, cache_1, c_other

    # 1. the prompt's last piece and every decode step
    t0 = time.time()
    rows = list(range(n_prompt - last, n_prompt + n_dec))
    want = np.asarray(reference.logits_at(params, cfg_file, seq.tolist(),
                                          rows))
    ours = np.concatenate([pre, dec])[rows]
    diff = np.abs(ours - want)
    by_row = diff.max(axis=1)
    log(phase="logits", s=time.time() - t0, rows=len(rows),
        max_abs_logit=float(np.abs(want).max()),
        prefill_max_diff=float(by_row[:last].max()),
        prefill_median=float(np.median(by_row[:last])),
        decode_max_diff=float(by_row[last:].max()),
        decode_median=float(np.median(by_row[last:])),
        decode_last100_max_diff=float(by_row[-100:].max()),
        rows_over_0_1=int((by_row > 0.1).sum()),
        same_argmax=int((ours.argmax(-1) == want.argmax(-1)).sum()))

    # 2. which experts the two sides chose at the prompt's rows compared
    t0 = time.time()
    prompt_rows = rows[:last]
    padded = reference._pad(len(seq), 0, None, None)[0]
    theirs = reference_scores(params, cfg_file, seq, prompt_rows, padded)
    mine = pre_scores[:, prompt_rows]
    first = cfg_file["first_k_dense_replace"]
    picks = []
    for j in range(theirs.shape[0]):
        m = params[f"layer_{first + j}"]["moe"]
        picks.append([np.sort(np.asarray(reference.chosen(
            jnp.asarray(s[j]), m, cfg_file)), axis=-1)
            for s in (mine, theirs)])
    differs = np.stack([(a != b).any(axis=-1) for a, b in picks])  # [L, R]
    worst = np.argsort(by_row[:last])[::-1][:8]
    for r in worst:
        layers = []
        for j in np.nonzero(differs[:, r])[0]:
            bias = np.asarray(params[f"layer_{first + j}"]["moe"]["bias"],
                              np.float32)
            a, b = (set(side[r].tolist()) for side in picks[j])
            # each disputed expert's choice value (score + bias) on the
            # two sides: a near-tie that the sides resolve differently
            layers.append(dict(
                layer=int(first + j),
                program_only=[(e, float(mine[j, r, e] + bias[e]),
                               float(theirs[j, r, e] + bias[e]))
                              for e in sorted(a - b)],
                reference_only=[(e, float(mine[j, r, e] + bias[e]),
                                 float(theirs[j, r, e] + bias[e]))
                                for e in sorted(b - a)],
                score_diff=float(np.abs(mine[j, r] - theirs[j, r]).max())))
        log(phase="choice_at_row", row=int(prompt_rows[r]),
            logit_diff=float(by_row[r]), layers_that_differ=layers)
    log(phase="choices", s=time.time() - t0, rows=last,
        expert_layers=int(theirs.shape[0]),
        row_layers_that_differ=int(differs.sum()),
        rows_that_differ=int(differs.any(axis=0).sum()),
        rows_over_0_1=int((by_row[:last] > 0.1).sum()),
        rows_over_0_1_that_differ=int(
            (differs.any(axis=0) & (by_row[:last] > 0.1)).sum()),
        score_diff_median=float(np.median(np.abs(mine - theirs).max(-1))))
    ok = bool(by_row.max() < 5e-3)

    # 3. the engine whole: one slot, a long first request, then ours
    eng2 = ServingEngine(cfg, params, slots=1, chunk=8, cache_len=cache_len,
                         kv_block_size=block, prefill_chunk=piece,
                         prefill_budget=budget)
    first_id = eng2.submit(other[:n_prompt + (
        5 if args.tiny else 200)].tolist(), 24)
    ours_id = eng2.submit(seq[:n_prompt].tolist(), n_dec)
    t0 = time.time()
    out = eng2.run()
    served = out[ours_id][n_prompt:]
    log(phase="engine_run", s=time.time() - t0, served=len(served),
        first_served=len(out[first_id]))
    gaps = reference.served_gaps(
        params, cfg_file, seq[:n_prompt].tolist(), served,
        pad_to=None if args.tiny else 10752, rows_to=n_dec)
    log(phase="engine_vs_reference", served_gap_max=float(gaps.max()),
        served_gap_mean=float(gaps.mean()),
        not_first_choice=int((gaps > 0).sum()), of=len(gaps))
    print(json.dumps({"ok": bool(ok and gaps.max() < 5e-3)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
