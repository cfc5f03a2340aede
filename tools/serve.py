"""Batch-serve mixed-length requests through the continuous-batching engine.

The OFFLINE CLI face of ``serving.ServingEngine`` (slot-refill decode):
every request is collected up front, the engine runs to completion, the
process exits.  For ONLINE serving — accepting HTTP requests while the
engine decodes, with admission control, deadlines, streaming, and a
/metrics surface — use ``tools/serve_http.py`` (the
``tensorflow_train_distributed_tpu.server`` gateway); token output is
identical for the same requests.

Unlike ``tools/sample.py`` (one static batch, equal-length prompts),
requests here may have DIFFERENT prompt lengths and budgets — the
engine keeps ``--slots`` of them in flight and refills as they finish,
emitting each result as one JSONL line ``{"id", "prompt", "tokens"}``
(tokens = prompt + continuation, exactly generate()'s convention).

Requests come from repeated ``--prompt`` flags or ``--requests FILE``
(JSONL: ``{"prompt": [ids...], "max_new": N, "seed": S?}``).  Prompts
are token ids; this CLI does no text tokenization itself (transformers
+ tokenizers ARE installed in this image — load the checkpoint's
``tokenizer.json`` with ``tokenizers``/``transformers`` to turn text
into ids, e.g. ``AutoTokenizer.from_pretrained(hf_dir).encode(text)``).

Examples:
  python tools/serve.py --config llama_tiny_sft --checkpoint-dir /ck \\
      --prompt 1,2,3 --prompt 4,5,6,7,8 --max-new 32
  python tools/serve.py --config llama_tiny_sft --checkpoint-dir /ck \\
      --requests reqs.jsonl --slots 8 --temperature 0.8 --top-k 20
"""

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))  # repo root (the package)
sys.path.insert(0, _HERE)                   # tools/ (sample.py helper)

from sample import (  # noqa: E402 (tools/ sibling)
    _restore_params,
    apply_dispatch_arg,
    check_vocab_ids,
    load_decoder_params,
    parse_prompt_spec,
    resolve_decoder_task,
)


def _int_or_auto(v: str):
    """``--kv-pool-blocks`` values: an int, or the literal 'auto'
    (device-HBM autosizing — the engine solves the pool size and
    budget from the chip's reported memory)."""
    return v if v == "auto" else int(v)


def parse_spec_depth_arg(arg: str, fixed_k: int):
    """``--spec-depth`` → (speculative_k, spec_depths-or-None).

    '' keeps today's fixed ``--speculative-k``; 'fixed:K' pins K
    (bitwise the same engine); 'adaptive' uses the default bucket set
    (0, 2, 4, 8); 'adaptive:K1,K2,...' sets the buckets.  Shared by
    serve/serve_http/bench so every launcher parses the policy
    identically."""
    if not arg:
        return fixed_k, None
    if arg.startswith("fixed:"):
        return int(arg.split(":", 1)[1]), None
    if arg == "adaptive":
        return fixed_k, (0, 2, 4, 8)
    if arg.startswith("adaptive:"):
        depths = tuple(int(x) for x in arg.split(":", 1)[1].split(","))
        return fixed_k, depths
    raise SystemExit(
        f"--spec-depth must be 'fixed:K', 'adaptive', or "
        f"'adaptive:K1,K2,...', got {arg!r}")


def add_engine_args(p) -> None:
    """Engine/model flag surface SHARED with tools/serve_http.py: one
    definition, so the offline CLI and the online gateway always load
    and configure the engine identically (the parity contract)."""
    p.add_argument("--config", required=True,
                   help="registry config name (a decoder-family preset)")
    src_grp = p.add_mutually_exclusive_group(required=True)
    src_grp.add_argument("--checkpoint-dir",
                         help="orbax checkpoint dir (params-only restore)")
    src_grp.add_argument("--init-from-hf",
                         help="local HuggingFace checkpoint (llama-family "
                              "or sparse-MoE) to serve directly")
    p.add_argument("--max-new", type=int, default=32,
                   help="default generation budget (per-request values "
                        "in JSONL / HTTP bodies override it)")
    p.add_argument("--prefix", default="",
                   metavar="IDS", help="comma-separated token ids of a "
                   "shared prompt prefix (system prompt): prefilled "
                   "ONCE, reused by every request whose prompt extends "
                   "it (engine.preload_prefix)")
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--chunk", type=int, default=8)
    p.add_argument("--cache-len", type=int, default=0,
                   help="0 -> config.max_positions")
    p.add_argument("--eos-id", type=int, default=None)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument("--quant", default="", choices=["", "int8"])
    p.add_argument("--kv-int8", action="store_true",
                   help="int8 KV cache (llama-family configs): cache "
                        "rows store 1 byte + a per-row f32 scale — "
                        "halves KV HBM in the paged pool and the "
                        "batch-1 prefill cache, the large-batch decode "
                        "bandwidth lever. The freed memory is worth "
                        "spending: grow --kv-pool-blocks (and --slots) "
                        "into it. Composes with --quant and "
                        "speculative serving (the draft's caches "
                        "quantize too)")
    p.add_argument("--speculative-draft-config", default=None,
                   help="enable speculative serving: registry config of "
                        "the DRAFT model (same vocab). Every slot keeps "
                        "its own acceptance length; greedy outputs stay "
                        "token-identical to plain serving, sampled ones "
                        "follow the same distribution (rejection rule)")
    p.add_argument("--speculative-draft-checkpoint", default=None,
                   help="orbax checkpoint dir for the draft's weights")
    p.add_argument("--speculative-k", type=int, default=4,
                   help="draft block length per round")
    p.add_argument("--spec-depth", default="",
                   help="draft-depth policy (needs the draft flags): "
                        "'fixed:K' pins depth K bitwise (same as "
                        "--speculative-k K); 'adaptive' precompiles "
                        "depth buckets {0,2,4,8} and a controller "
                        "picks per round from measured acceptance "
                        "(deepen when high, back off to plain decode "
                        "on collapse, hysteresis against thrash); "
                        "'adaptive:K1,K2,...' sets the bucket list. "
                        "TTD_NO_ADAPTIVE_SPEC=1 is the no-redeploy "
                        "kill switch back to the fixed depth")
    p.add_argument("--dispatch", default="", choices=["", "dense", "gmm"],
                   help="MoE expert-dispatch override (MoE configs "
                        "only). 'gmm' is DROPLESS: routing — and "
                        "therefore outputs — legitimately differs from "
                        "capacity-dropped 'dense', but serving regains "
                        "bucketed/chunked prefill and prefix caching "
                        "(dense compiles one prefill program per "
                        "distinct prompt length and refuses "
                        "--prefix). Default: the config's own setting")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   help="prefill prompts in fixed-size pieces of this "
                        "many tokens (ONE compiled program at any "
                        "prompt length) instead of the padded prompt "
                        "buckets; also the natural installment size "
                        "for --prefill-budget. Rejected for "
                        "dense-dispatch MoE (exact-length prefill)")
    p.add_argument("--prefill-budget", type=int, default=None,
                   help="tokens of staged prefill advanced per engine "
                        "step (decode-priority admission: a new "
                        "prompt's prefill interleaves with active "
                        "lanes' decode chunks instead of blocking "
                        "them). Default: one prefill piece per step; "
                        "a budget as large as the prompts admits a "
                        "whole prompt in one step; 0 is refused")
    p.add_argument("--kv-block-size", type=int, default=16,
                   help="paged KV cache: rows per physical block. "
                        "Smaller blocks = finer prefix sharing and "
                        "less tail waste per lane; larger blocks = "
                        "shorter block tables and coarser gathers. "
                        "Prefix sharing is block-granular, so shared "
                        "system prompts win most when their length is "
                        "a multiple of this")
    p.add_argument("--kv-pool-blocks", type=_int_or_auto, default=None,
                   help="paged KV cache: total physical blocks in the "
                        "pool (default: slots * ceil(cache_len / "
                        "block_size): every lane's whole context). "
                        "Admission is keyed on free blocks: "
                        "shrink to trade memory for queueing, grow to "
                        "serve more/longer shared prefixes warm. "
                        "'auto' solves the pool size AND "
                        "--hbm-budget-bytes exactly from the device's "
                        "reported memory (pool rows + prefill "
                        "transients + draft pools + --hbm-headroom), "
                        "so one binary lands correctly sized on any "
                        "chip; TTD_NO_HBM_AUTOSIZE=1 restores the "
                        "default heuristic")
    p.add_argument("--hbm-headroom", type=float, default=0.1,
                   help="fraction of device HBM the autosize solve "
                        "leaves free (weights, activations, XLA "
                        "scratch live outside the solved pools); only "
                        "meaningful with --kv-pool-blocks auto")
    p.add_argument("--hbm-budget-bytes", type=int, default=None,
                   help="declared HBM budget for the engine's memory "
                        "pools (memcheck): with TTD_MEMCHECK=1, the "
                        "allocation that would exceed it raises "
                        "MemoryBudgetError with the live set diffed "
                        "(instead of an opaque XLA OOM later), and "
                        "admission refuses requests whose projected "
                        "bytes cannot fit. Default: track-only — "
                        "ttd_engine_hbm_bytes gauges, no enforcement")
    p.add_argument("--platform", default="",
                   help="force a jax platform (e.g. 'cpu')")


def parse_prefix_arg(args, cfg):
    """--prefix ids, vocab-screened BEFORE any checkpoint load: the
    prefix becomes real context for every matching request — an
    out-of-vocab id would silently clamp in the embedding gather and
    corrupt every continuation; same screens as --prompt."""
    prefix_ids = (parse_prompt_spec(args.prefix, flag="--prefix")
                  if args.prefix else [])
    if prefix_ids:
        check_vocab_ids([prefix_ids], cfg.vocab_size)
    return prefix_ids


def maybe_dense_moe_hint(eng, lengths=None) -> None:
    """Startup hint for the dense-dispatch MoE compile storm: exact-
    length prefill compiles one XLA program per DISTINCT prompt length
    and disables prefix caching.  ``lengths``: the request lengths when
    known up front (serve.py) — the hint only fires when they vary;
    None (the online gateway: lengths unknowable at startup) always
    hints."""
    if not getattr(eng, "_exact_prefill", False):
        return
    if lengths is not None and len(set(lengths)) <= 1:
        return
    print("hint: serving a dense-dispatch MoE with varied prompt "
          "lengths compiles one prefill program PER DISTINCT length "
          "and cannot reuse prompt prefixes; pass --dispatch gmm "
          "(dropless — no capacity competition, so outputs "
          "legitimately differ from dense) to regain bucketed prefill "
          "and prefix caching, or pad prompts to a few fixed lengths "
          "host-side (MIGRATION.md §8)", file=sys.stderr)


def build_engine(args, cfg, is_moe, prefix_ids):
    """Load weights (+ optional draft), quantize, construct the engine,
    preload the prefix — shared by serve.py and serve_http.py.
    ValueErrors surface as the clean SystemExit CLI convention."""
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    cfg = apply_dispatch_arg(args, cfg, is_moe)
    if getattr(args, "kv_int8", False):
        import dataclasses

        if is_moe:
            raise SystemExit("--kv-int8 applies to llama-family "
                             "configs only (MoeConfig has no "
                             "kv_cache_int8 knob)")
        cfg = dataclasses.replace(cfg, kv_cache_int8=True)
    draft_cfg = draft_params = None
    if (args.speculative_draft_checkpoint
            and not args.speculative_draft_config):
        raise SystemExit("--speculative-draft-checkpoint needs "
                         "--speculative-draft-config")
    if args.speculative_draft_config:
        if not args.speculative_draft_checkpoint:
            raise SystemExit("--speculative-draft-checkpoint is required "
                             "with --speculative-draft-config")
        _, draft_cfg, draft_moe = resolve_decoder_task(
            args.speculative_draft_config, "speculative serving")
        if draft_moe:
            raise SystemExit("the draft config must be a llama-family "
                             "decoder")
        if getattr(args, "kv_int8", False):
            import dataclasses

            # Both caches ride the same bandwidth: --kv-int8 quantizes
            # the draft's KV alongside the target's (the --quant rule).
            draft_cfg = dataclasses.replace(draft_cfg,
                                            kv_cache_int8=True)
        draft_params = _restore_params(args.speculative_draft_checkpoint)

    cfg, params = load_decoder_params(args, cfg, is_moe)
    quant_scales = draft_quant_scales = None
    if args.quant == "int8":
        from tensorflow_train_distributed_tpu.models.quant import (
            quantize_params,
        )

        params, quant_scales = quantize_params(params)
        if draft_params is not None:
            # --quant quantizes BOTH models (decode is weight-HBM-bound
            # on both); each tree carries its own scales.
            draft_params, draft_quant_scales = quantize_params(
                draft_params)

    spec_k, spec_depths = parse_spec_depth_arg(
        getattr(args, "spec_depth", "") or "",
        getattr(args, "speculative_k", 4))
    if spec_depths is not None and draft_cfg is None:
        raise SystemExit("--spec-depth adaptive needs "
                         "--speculative-draft-config")
    try:
        eng = ServingEngine(
            cfg, params, slots=args.slots, chunk=args.chunk,
            cache_len=args.cache_len or None, eos_id=args.eos_id,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, quant_scales=quant_scales,
            draft_config=draft_cfg, draft_params=draft_params,
            draft_quant_scales=draft_quant_scales,
            speculative_k=(spec_k if draft_cfg is not None else 0),
            spec_depths=(spec_depths if draft_cfg is not None
                         else None),
            prefill_chunk=getattr(args, "prefill_chunk", None),
            prefill_budget=getattr(args, "prefill_budget", None),
            kv_block_size=getattr(args, "kv_block_size", 16),
            kv_pool_blocks=getattr(args, "kv_pool_blocks", None),
            hbm_budget_bytes=getattr(args, "hbm_budget_bytes", None),
            hbm_headroom=getattr(args, "hbm_headroom", 0.1))
        if prefix_ids:
            eng.preload_prefix(prefix_ids)
    except ValueError as e:
        raise SystemExit(str(e))
    return eng


def worker_engine_factory(spec: dict):
    """Subprocess-replica engine factory — the PRODUCTION one
    ``server.worker`` resolves as ``serve:worker_engine_factory``.
    ``spec`` is the launcher CLI's parsed flag namespace, serialized
    (``vars(args)`` — everything argparse produced is JSON-clean), so
    the worker replays the exact flag set the parent screened with:
    parent-side facades and worker-side engines are built from ONE
    flag surface and cannot drift."""
    args = argparse.Namespace(**spec)
    from tensorflow_train_distributed_tpu.runtime import compile_cache

    compile_cache.place_compile_cache()
    if getattr(args, "platform", ""):
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            force_platform,
        )

        force_platform(args.platform)
    _, cfg, is_moe = resolve_decoder_task(args.config, "serving")
    prefix_ids = parse_prefix_arg(args, cfg)
    eng = build_engine(args, cfg, is_moe, prefix_ids)
    # Warm before the HELLO: the decode program (and one prefill
    # shape) compiles now, inside the child, so the parent's
    # wait_ready covers the compile and the pool's hung-dispatch
    # watchdog never stares down a cold XLA compile.  Requests are
    # seeded independently — a warm pass changes no later output.
    eng.submit([1], 1)
    eng.run()
    return eng


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    add_engine_args(p)
    p.add_argument("--prompt", action="append", default=[],
                   metavar="IDS", help="comma-separated token ids; repeat "
                   "per request (lengths may differ — that is the point)")
    p.add_argument("--requests", default="",
                   help="JSONL file: {'prompt': [ids], 'max_new': N, "
                        "'seed': S?} per line")
    p.add_argument("--output", default="-",
                   help="output JSONL path ('-' = stdout)")
    args = p.parse_args(argv)

    from tensorflow_train_distributed_tpu.runtime import compile_cache

    compile_cache.place_compile_cache()
    if args.platform:
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            force_platform,
        )

        force_platform(args.platform)

    _, cfg, is_moe = resolve_decoder_task(args.config, "serving")

    reqs = [{"prompt": parse_prompt_spec(spec), "max_new": args.max_new}
            for spec in args.prompt]
    if args.requests:
        if not os.path.isfile(args.requests):
            raise SystemExit(f"no requests file at {args.requests}")
        with open(args.requests) as f:
            for i, line in enumerate(f):
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                    if not isinstance(rec.get("prompt"), list):
                        # A string would silently iterate characters.
                        raise ValueError("'prompt' must be a list of ids")
                    if not rec["prompt"]:
                        raise ValueError("empty prompt")
                    def _int(v, what):
                        # int() would silently truncate 1.9 -> 1 (and
                        # accept bools); demand real integers.
                        if not isinstance(v, int) or isinstance(v, bool):
                            raise ValueError(f"{what} must be an integer")
                        return v

                    rec = {"prompt": [_int(t, "token ids")
                                      for t in rec["prompt"]],
                           "max_new": _int(rec.get("max_new",
                                                   args.max_new),
                                           "max_new"),
                           **({"seed": _int(rec["seed"], "seed")}
                              if "seed" in rec else {})}
                except (json.JSONDecodeError, KeyError, TypeError,
                        ValueError, AttributeError) as e:
                    raise SystemExit(
                        f"{args.requests}:{i + 1}: bad request line "
                        f"({e})")
                reqs.append(rec)
    if not reqs:
        raise SystemExit("no requests (--prompt or --requests)")
    check_vocab_ids([r["prompt"] for r in reqs], cfg.vocab_size)
    prefix_ids = parse_prefix_arg(args, cfg)

    # Probe --output writability BEFORE serving (an unwritable path
    # must fail in milliseconds, not after minutes of decode) — append
    # mode, so an early failure later (bad checkpoint, OOM) does NOT
    # truncate a pre-existing results file.
    if args.output != "-":
        try:
            open(args.output, "a").close()
        except OSError as e:
            raise SystemExit(f"cannot write --output {args.output}: {e}")

    eng = build_engine(args, cfg, is_moe, prefix_ids)
    maybe_dense_moe_hint(eng, [len(r["prompt"]) for r in reqs])
    # Submit validation errors (oversized prompts, budget vs cache)
    # exit with the same clean SystemExit convention as every other
    # serve.py input error — and they happen BEFORE the truncating
    # open below, so a failed rerun never destroys a previous results
    # file.
    try:
        ids = [eng.submit(r["prompt"], r["max_new"],
                          seed=r.get("seed")) for r in reqs]
    except ValueError as e:
        raise SystemExit(str(e))
    out = eng.run()
    if args.speculative_draft_config:
        # Observable proof the speculative path actually engaged (and
        # the acceptance rate the draft is buying).  The rate divides
        # by SLOT-rounds × k (each active slot drafts k per round) —
        # engine rounds alone would inflate it by the slot count.
        s = eng.spec_stats
        rate = (s["drafted_accepted"] / (s["slot_rounds"]
                                         * args.speculative_k)
                if s["slot_rounds"] else 0.0)
        print(f"speculative: rounds={s['rounds']} "
              f"slot_rounds={s['slot_rounds']} "
              f"accepted={s['drafted_accepted']} "
              f"emitted={s['emitted']} "
              f"acceptance={rate:.3f}", file=sys.stderr)
    lines = [json.dumps({"id": rid, "prompt": r["prompt"],
                         "tokens": out[rid]}) + "\n"
             for rid, r in zip(ids, reqs)]
    if args.output == "-":
        sys.stdout.writelines(lines)
    else:
        # Results in hand before the sink is touched: a failure during
        # serving (OOM, interrupt) must never destroy a pre-existing
        # results file.  Write-temp-then-rename keeps the replacement
        # atomic too.
        tmp = args.output + ".tmp"
        with open(tmp, "w") as sink:
            sink.writelines(lines)
        os.replace(tmp, args.output)
    return 0


if __name__ == "__main__":
    sys.exit(main())
