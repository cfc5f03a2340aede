"""Sample from a trained (or HF-imported) decoder checkpoint via the CLI.

The inference face of the Llama family: load weights from an orbax
checkpoint dir (params-only partial restore — no optimizer state
materialized) or a local HuggingFace checkpoint, then run the KV-cache
``generate`` path (greedy / temperature / top-k / top-p).

Prompts are token ids: ``--prompt 1,15043,29892`` (comma-separated),
repeatable for a batch.  This CLI does no text tokenization itself —
transformers+tokenizers ARE installed in this image, so turn text into
ids with the checkpoint's own tokenizer (e.g.
``AutoTokenizer.from_pretrained(hf_dir).encode(text)``).

Examples:
  python tools/sample.py --config llama_tiny_sft --checkpoint-dir /ck \\
      --prompt 1,2,3 --max-new 32
  python tools/sample.py --config llama2_7b_sft --init-from-hf /hf \\
      --prompt 1,15043 --max-new 64 --temperature 0.8 --top-p 0.95
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _restore_params(checkpoint_dir: str):
    """Params-only orbax restore, with the isdir guard FIRST: orbax
    would create a typo'd directory as a side effect of opening it."""
    import os as _os

    from tensorflow_train_distributed_tpu.training.checkpoint import (
        CheckpointManager,
    )

    if not _os.path.isdir(checkpoint_dir):
        raise SystemExit(f"no checkpoint dir at {checkpoint_dir}")
    mgr = CheckpointManager(checkpoint_dir, async_save=False)
    params = mgr.restore_params()
    mgr.close()
    if params is None:
        raise SystemExit(f"no checkpoint under {checkpoint_dir}")
    return params


def load_decoder_params(args, cfg, is_moe):
    """Weights from --checkpoint-dir (orbax) or --init-from-hf (HF
    import with the registry config's layout) — shared by sample.py
    and serve.py.  Import validators exit with the clean CLI
    convention, not a traceback."""
    if getattr(args, "init_from_hf", None):
        from tensorflow_train_distributed_tpu.models import import_hf

        importer = (import_hf.import_moe if is_moe
                    else import_hf.import_llama)
        try:
            return importer(args.init_from_hf, cfg)
        except ValueError as e:
            raise SystemExit(str(e))
    return cfg, _restore_params(args.checkpoint_dir)


def resolve_decoder_task(config_name: str, verb: str):
    """Registry lookup + decoder-family guard (shared with serve.py).

    Returns ``(task, config, is_moe)`` or SystemExits with the CLI
    convention."""
    from tensorflow_train_distributed_tpu.models import registry
    from tensorflow_train_distributed_tpu.models.llama import CausalLmTask
    from tensorflow_train_distributed_tpu.models.moe import MoeLmTask

    task = registry.get_entry(config_name)["task_factory"]()
    if not isinstance(task, (CausalLmTask, MoeLmTask)):
        raise SystemExit(
            f"--config {config_name} is not a decoder LM; {verb} needs "
            "a llama- or moe-family config")
    return task, task.config, isinstance(task, MoeLmTask)


def parse_prompt_spec(spec: str, flag: str = "--prompt"):
    """One token-id list flag value -> list of ints (shared with
    serve.py, which also parses --prefix through it)."""
    try:
        return [int(t) for t in spec.split(",") if t]
    except ValueError:
        raise SystemExit(f"{flag} must be comma-separated ints, got "
                         f"{spec!r}")


def check_vocab_ids(rows, vocab_size: int) -> None:
    """Reject out-of-vocab prompt ids (shared with serve.py)."""
    bad = [t for r in rows for t in r if not 0 <= t < vocab_size]
    if bad:
        raise SystemExit(f"prompt ids outside vocab [0, {vocab_size}): "
                         f"{sorted(set(bad))[:8]}")


def apply_dispatch_arg(args, cfg, is_moe):
    """--dispatch override, applied to the config BEFORE weights load
    (dense and gmm share one parameter tree, so the override never
    invalidates a checkpoint) — shared with serve.py/serve_http.py."""
    if not getattr(args, "dispatch", ""):
        return cfg
    if not is_moe:
        raise SystemExit("--dispatch selects the MoE expert-dispatch "
                         "formulation; it does not apply to dense "
                         "decoder configs")
    import dataclasses

    return dataclasses.replace(cfg, dispatch=args.dispatch)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--config", required=True,
                   help="registry config name (a llama-family preset)")
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--checkpoint-dir",
                     help="orbax checkpoint dir (params-only restore)")
    src.add_argument("--init-from-hf",
                     help="local HuggingFace LlamaForCausalLM checkpoint")
    p.add_argument("--prompt", action="append", required=True,
                   metavar="IDS", help="comma-separated token ids; repeat "
                   "for a batch. Rows must be the SAME length (static "
                   "shapes, and the decode path has no pad masking — run "
                   "unequal prompts as separate batches)")
    p.add_argument("--max-new", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy")
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--quant", default="", choices=["", "int8"],
                   help="'int8': post-training weight-only quantization "
                        "(models.quant) before sampling — halves decode "
                        "weight HBM traffic vs bf16")
    p.add_argument("--lora-rank", type=int, default=0,
                   help="serve a LoRA checkpoint: the rank/alpha/targets "
                        "it was TRAINED with (adapters applied unmerged; "
                        "generate refuses adapter-bearing trees without "
                        "this). Composing with --quant requires merging "
                        "via tools/export_hf_checkpoint.py instead")
    p.add_argument("--lora-alpha", type=float, default=None,
                   help="default: the sidecar's, else 16.0")
    p.add_argument("--lora-targets", default=None,
                   help="default: the sidecar's, else query,value")
    p.add_argument("--speculative-draft-config", default=None,
                   help="enable speculative decoding: registry config of "
                        "the DRAFT model (same vocab; batch-1). Greedy "
                        "output is provably identical to the target's "
                        "own greedy decode; with --temperature the "
                        "rejection rule keeps the plain sampled law")
    p.add_argument("--speculative-draft-checkpoint", default=None,
                   help="orbax checkpoint dir for the draft's weights")
    p.add_argument("--speculative-k", type=int, default=4,
                   help="draft block length per round")
    p.add_argument("--dispatch", default="", choices=["", "dense", "gmm"],
                   help="MoE expert-dispatch override (MoE configs "
                        "only). 'gmm' is DROPLESS — routing, and "
                        "therefore outputs, legitimately differ from "
                        "capacity-dropped 'dense'. Default: the "
                        "config's own setting")
    p.add_argument("--platform", default="",
                   help="force a jax platform (e.g. 'cpu')")
    args = p.parse_args(argv)

    from tensorflow_train_distributed_tpu.runtime import compile_cache

    compile_cache.place_compile_cache()
    if args.platform:
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            force_platform,
        )

        force_platform(args.platform)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflow_train_distributed_tpu.models.generate import generate

    task, cfg, is_moe = resolve_decoder_task(args.config, "sampling")
    cfg = apply_dispatch_arg(args, cfg, is_moe)

    rows = [parse_prompt_spec(spec) for spec in args.prompt]
    if not rows or any(not r for r in rows):
        raise SystemExit("--prompt rows must be non-empty")
    if len({len(r) for r in rows}) != 1:
        raise SystemExit(
            "all --prompt rows must have equal length (static shapes, and "
            "the decode path has no pad masking — padding would condition "
            "on pad tokens as real context; run unequal prompts as "
            "separate invocations)")
    if args.temperature == 0 and (args.top_k is not None
                                  or args.top_p is not None):
        raise SystemExit(
            "--top-k/--top-p filter a sampling distribution; add "
            "--temperature > 0 (they have no effect on greedy argmax)")
    check_vocab_ids(rows, cfg.vocab_size)
    if args.max_new < 1:
        raise SystemExit(f"--max-new must be >= 1, got {args.max_new}")
    if len(rows[0]) + args.max_new > cfg.max_positions:
        raise SystemExit(
            f"prompt {len(rows[0])} + --max-new {args.max_new} exceeds the "
            f"config's max_positions={cfg.max_positions} (the KV cache)")
    prompt = np.asarray(rows, np.int32)

    cfg, params = load_decoder_params(args, cfg, is_moe)

    import dataclasses as _dc

    from tensorflow_train_distributed_tpu.models.lora import (
        LoraSpec, load_spec, validate_targets,
    )

    sidecar = (load_spec(args.checkpoint_dir)
               if args.checkpoint_dir else None)
    spec = None
    flags_given = (args.lora_alpha is not None
                   or args.lora_targets is not None)
    if is_moe and (flags_given or args.lora_rank or sidecar is not None):
        raise SystemExit(
            "--lora-* applies to llama-family configs only (and this "
            "checkpoint dir carries a lora_spec.json sidecar, which a "
            "MoE config cannot serve)" if sidecar is not None else
            "--lora-* applies to llama-family configs only")
    if flags_given and not args.lora_rank:
        raise SystemExit(
            "--lora-alpha/--lora-targets need --lora-rank too (a lone "
            "flag would be silently dropped in favor of the checkpoint's "
            "lora_spec.json)")
    if args.lora_rank:
        try:
            spec = LoraSpec(
                rank=args.lora_rank,
                alpha=(16.0 if args.lora_alpha is None
                       else args.lora_alpha),
                targets=validate_targets(
                    ("query,value" if args.lora_targets is None
                     else args.lora_targets).split(",")))
        except ValueError as e:
            raise SystemExit(str(e))
        if sidecar is not None and spec != sidecar:
            raise SystemExit(
                f"--lora-* flags {spec} disagree with the checkpoint's "
                f"persisted lora_spec.json {sidecar} — drop the flags "
                "(the sidecar is authoritative) or fix them")
    elif sidecar is not None:
        spec = sidecar  # self-describing checkpoint
    if spec is not None:
        cfg = _dc.replace(cfg, lora=spec)

    # Speculative flag validation BEFORE any quantization work: these
    # checks only read args, and a doomed invocation must not pay a
    # full-tree quantize first.
    draft_task = None
    if args.speculative_draft_config:
        if args.quant or spec is not None:
            raise SystemExit(
                "--speculative-draft-config does not compose with "
                "--quant or LoRA serving (merge first).  Sampling DOES "
                "compose: with --temperature the draft samples its "
                "proposals and acceptance uses the rejection rule, so "
                "outputs follow the same law as plain sampled decoding")
        if is_moe:
            raise SystemExit("speculative decoding needs a llama-family "
                             "TARGET --config")
        if prompt.shape[0] != 1:
            raise SystemExit("speculative decoding is batch-1: pass ONE "
                             "--prompt")
        if not args.speculative_draft_checkpoint:
            raise SystemExit("--speculative-draft-checkpoint is required "
                             "with --speculative-draft-config")
        from tensorflow_train_distributed_tpu.models import registry
        from tensorflow_train_distributed_tpu.models.llama import (
            CausalLmTask,
        )

        draft_task = registry.get_entry(
            args.speculative_draft_config)["task_factory"]()
        if not isinstance(draft_task, CausalLmTask):
            # One accurate message (moe drafts are NOT accepted, so the
            # generic llama-or-moe wording would mislead).
            raise SystemExit("the draft config must be a llama-family "
                             "decoder")

    quant_scales = None
    if args.quant:
        from tensorflow_train_distributed_tpu.models.quant import (
            quantize_params,
        )

        params, quant_scales = quantize_params(params)

    if draft_task is not None:
        from tensorflow_train_distributed_tpu.models.speculative import (
            generate_speculative,
        )

        draft_params = _restore_params(args.speculative_draft_checkpoint)
        try:
            toks, stats = generate_speculative(
                cfg, params, draft_task.config, draft_params,
                jnp.asarray(prompt), args.max_new,
                k=args.speculative_k, temperature=args.temperature,
                top_k=args.top_k, top_p=args.top_p, seed=args.seed)
        except ValueError as e:
            # The library's guards (vocab match, k >= 1, the
            # prompt+max_new+k+1 cache budget on BOTH models, LoRA
            # leaves) — surface them as the clean CLI error every other
            # bad input gets.
            raise SystemExit(str(e))
        out = np.asarray(toks)
        print(json.dumps({"speculative_stats": stats}), flush=True)
    else:
        rng = (jax.random.key(args.seed)
               if args.temperature > 0 else None)
        out = np.asarray(generate(
            cfg, params, prompt, args.max_new,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, rng=rng, quant_scales=quant_scales))
    for row_in, row_out in zip(rows, out):
        print(json.dumps({
            "prompt": row_in,
            "completion": [int(t) for t in row_out[len(row_in):]],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
