"""Runner kind ``serve_latents``: ``serve_family``'s run for a
configuration whose latent-attention layers are of TWO KINDS
(``dots3_note``): full layers at the plain keys' sizes, over the rows a
learned indexer picks, and sliding-window layers at the ``swa_`` keys'
(their own heads, ranks, key size and rotary base) with no indexer; a
per-head gate on both, both latents rescaled, sigmoid-routed experts
beside a shared one, of which a chip's share is held here.

What the file says and ``serve_share.share_config`` cannot take: the
pattern as ``layer_types`` (as published: the first
``num_hidden_layers`` entries run), a window layer's sizes under
``swa_*`` keys, ``sliding_window_size``, the two gate types,
``apply_mla_qkv_lora_rescale`` and a ``rope_scaling`` of null;
``n_routed_experts`` counts the experts held HERE, of the router's
published width (``changed.n_routed_experts.source``).
``latents_config`` builds the program's ``MoeConfig`` from the file's
``program`` (a preset and its replacements) and cross-checks every
published key against it, each layer's kind against what
``MoeConfig.latent_sizes`` resolves for it.

Importing this module registers the family (``program.family``:
``"moe_latents"``) in ``serve_family.FAMILIES``.  The run is
``serve_share.run``: ``serve_family.run`` (set-up, warm-up, ramp,
window, drain and the check against
``benchmark/references/<reference>.py`` are not copied) with the pool
offered in the order ``mix_seed`` drew it under every ``--seed``
(``"order": "pool"``, the one value that runner takes).

Two names are lent to that run, as ``serve_sink.run`` lends one and for
the same reason (``serve_family.run`` takes no argument for them; a
``benchmark`` PR gives it one: PERF.md section 7):

- ``serve.build``, so that the engine's pools are logged by kind beside
  the ``warm`` line's ``kv_pool_bytes`` (``{"phase": "pools", ...}``:
  ``latent_pool_bytes``, ``index_pool_bytes``, ``latent_ring_bytes``); a
  program whose engine cannot say them by kind logs none;
- ``weights.make_params``.  ``weights._fill`` draws every ``kernel`` at
  std ``1 / sqrt(rows)``, which keeps a unit-variance input at unit
  variance.  A RESCALED latent is no unit-variance input: its variance
  is ``d_model / rank`` (5 and 10 in a full layer, 5 and 5 in a window
  layer), so queries and keys made from it by such kernels are
  ``sqrt(5)`` and ``sqrt(10)`` wide and a score's spread is 7.1 times
  (full) and 5 times (window) that of the same layer unrescaled: ~6
  where a trained layer's is ~1, a softmax that is an argmax, and a
  bf16 program's logits then part from the float32 reference's by more
  than a token's choice is worth (my chip runs, PR 46: 96% of served
  tokens not the reference's first choice, ``served_gap_mean`` 1.25-1.61
  beside the ``fp8w`` control's 2.53; five layers of the program in
  float32 at the chip's default precision 0.17 in the mean from the
  reference with the rescale, 0.0046 without: PERF.md section 6).  A
  trained model's up-projections take the latent as it is;
  ``seeded_latents`` refills the three kernels that read a rescaled
  latent (``q_b`` and ``index_q`` the query's, ``kv_b`` the key and
  value's) at std ``1 / sqrt(rows x d_model / rows) = 1 / sqrt(d_model)``:
  the variance-keeping rule for the input they really get.  The rescale
  stays in the program and in the reference (a latent left unscaled is
  then a layer at 1 / sqrt(5) of its scores and values: the planted
  fault ``nokvscale``); the reference reads the same arrays.
"""

from __future__ import annotations

import dataclasses

import jax

from benchmark.harness import serve, serve_family, serve_share, weights

#: source key -> MoeConfig field, compared after building (the plain
#: latent keys are the FULL layers' and the model's).
_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "intermediate_size": "dense_ffn_size",
    "moe_intermediate_size": "ffn_size",
    "n_routed_experts": "experts_held",
    "num_experts_per_tok": "top_k",
    "first_k_dense_replace": "dense_layers",
    "max_position_embeddings": "max_positions",
    "rope_theta": "rope_base",
    "rms_norm_eps": "rms_epsilon",
    "routed_scaling_factor": "routed_scaling",
    "norm_topk_prob": "norm_topk_prob",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim",
    "attention_bias": "qkv_bias",
    "index_n_heads": "index_heads",
    "index_head_dim": "index_dim",
    "index_topk": "index_topk",
    "moe_layer_freq": "moe_every",
    "apply_mla_qkv_lora_rescale": "lora_rescale",
}
#: What the file must say for the program's block to be the source's
#: (the program has no option for anything else).
_FIXED = {
    "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "hidden_act": "silu", "tie_word_embeddings": False,
    "rope_scaling": None, "attention_gate_type": "headwise",
    "swa_attention_gate_type": "headwise",
}
#: The fields a program needs for this family at all.
_FIELDS = ("attn_period", "attn_lead", "attn_gate", "lora_rescale",
           "experts_held")
#: LatentSizes field -> the source's key, under ``swa_`` for a window
#: layer.
_KIND_KEYS = {
    "num_heads": "num_attention_heads", "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_dim": "qk_nope_head_dim",
    "qk_rope_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "rope_base": "rope_theta",
}


def kind_of(cfg_file: dict, layer: int) -> dict:
    """Layer ``layer`` as the file states it, in the program's terms
    (``models.moe.LatentSizes``'s fields): a full layer's sizes under
    the plain keys with the indexer's, a window layer's under ``swa_``
    with the window and no choice."""
    name = cfg_file["layer_types"][layer]
    if name not in ("full_attention", "sliding_attention"):
        raise ValueError(f"layer_types[{layer}] = {name!r}: the program "
                         f"runs full_attention and sliding_attention")
    window = name == "sliding_attention"
    pre = "swa_" if window else ""
    out = {field: cfg_file[pre + key] for field, key in _KIND_KEYS.items()}
    out["rope_base"] = float(out["rope_base"])
    out.update(
        window=cfg_file["sliding_window_size"] if window else None,
        rope_scaling=None, index_heads=cfg_file["index_n_heads"],
        index_dim=cfg_file["index_head_dim"],
        index_topk=0 if window else cfg_file["index_topk"])
    return out


def latents_config(cfg_file: dict):
    """The program's ``MoeConfig`` for a file that states one chip's
    share of a ``dots3_note`` deployment, every size and every layer of
    the pattern cross-checked."""
    from tensorflow_train_distributed_tpu.models import moe

    prog = cfg_file["program"]
    # A program from before this family (the parent commit of the PR
    # that brought it) says so and stops, before any weight is made.
    have = {f.name for f in dataclasses.fields(moe.MoeConfig)}
    lacks = sorted((set(prog["replace"]) | set(_FIELDS)) - have)
    if prog["preset"] not in moe.MOE_PRESETS or lacks:
        raise ValueError(
            f"this program cannot run the configuration: it has no preset "
            f"{prog['preset']!r}" + (
                f" and no MoeConfig field {', '.join(lacks)}" if lacks
                else ""))
    cfg = dataclasses.replace(moe.MOE_PRESETS[prog["preset"]],
                              **prog["replace"])
    for key, field in _KEYS.items():
        if key not in cfg_file:
            raise KeyError(f"configuration file lacks {key!r}")
        got, want = getattr(cfg, field), cfg_file[key]
        if got != want:
            raise ValueError(
                f"configuration file says {key}={want!r} but the program "
                f"would run {field}={got!r}")
    for key, want in _FIXED.items():
        if cfg_file.get(key, KeyError) != want:
            raise ValueError(
                f"the program's block has {key}={want!r}; the "
                f"configuration file says {cfg_file.get(key)!r}")
    published = cfg_file["changed"]["n_routed_experts"]["source"]
    if cfg.num_experts != published:
        raise ValueError(
            f"the source routes over {published} experts but the program "
            f"would run a router of num_experts={cfg.num_experts}")
    if cfg.experts_offset != cfg_file.get("experts_offset", 0):
        raise ValueError(
            f"configuration file says experts_offset="
            f"{cfg_file.get('experts_offset', 0)} but the program would "
            f"run experts_offset={cfg.experts_offset}")
    for pre in ("", "swa_"):
        if cfg_file[pre + "num_key_value_heads"] != (
                cfg_file[pre + "num_attention_heads"]):
            raise ValueError("latent attention has as many key heads as "
                             "query heads")
    if cfg.router != "sigmoid" or cfg.dispatch != "gmm" or (
            cfg.n_group != 1):
        raise ValueError("noaux_tc with one group is the program's "
                         "sigmoid router under dropless dispatch")
    if (cfg.shared_expert_size or 0) != (
            cfg_file["n_shared_experts"] * cfg_file["moe_intermediate_size"]):
        raise ValueError("shared expert width differs from "
                         "n_shared_experts x moe_intermediate_size")
    if not cfg.attn_period or not cfg.kv_lora_rank or not cfg.attn_gate:
        raise ValueError("the file states a pattern of gated latent "
                         "layers; the program would run something else")
    # The pattern, entry by entry over every layer the file lists (the
    # published 46, of which the first ``num_hidden_layers`` run).
    n = len(cfg_file["layer_types"])
    if n < cfg.num_layers:
        raise ValueError(f"layer_types states {n} layers; the program "
                         f"would run {cfg.num_layers}")
    for i in range(n):
        got = dataclasses.asdict(cfg.latent_sizes(i))
        want = kind_of(cfg_file, i)
        if cfg.attn_kind(i).kind != "latent" or got != want:
            raise ValueError(
                f"configuration file says layer {i} is {want!r} but the "
                f"program would run {got!r}")
    return cfg


serve_family.FAMILIES.setdefault(
    "moe_latents", (latents_config, serve_family.moe_param_shapes))


#: The kernels that read a rescaled latent (module docstring).
RESCALED_INPUT = ("q_b", "kv_b", "index_q")


def seeded_latents(params):
    """``params`` with every ``attention/{q_b,kv_b,index_q}/kernel``
    times ``sqrt(rows / d_model)`` (std ``1 / sqrt(d_model)`` in place of
    ``1 / sqrt(rows)``), in the leaves' own type and place, a leaf at a
    time (the tree is 9 GB: nothing copies it whole)."""
    d_model = params["token_embed"]["embedding"].shape[1]

    def refill(path, leaf):
        names = tuple(getattr(p, "key", "") for p in path)
        if names[-3:-1] not in [("attention", n) for n in RESCALED_INPUT]:
            return leaf
        return (leaf * (leaf.shape[0] / d_model) ** 0.5).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(refill, params)


def run(ctx: dict) -> dict:
    build_theirs, make_theirs = serve.build, weights.make_params

    def build(*args, **kwargs):
        engine, driver = build_theirs(*args, **kwargs)
        parts = getattr(engine, "kv_pool_parts", None)
        if parts is not None:
            ctx["log"](phase="pools", kv_pool_bytes=engine.kv_pool_bytes(),
                       **parts())
        return engine, driver

    def make_params(shapes, seed, dtype):
        return seeded_latents(make_theirs(shapes, seed, dtype))

    serve.build, weights.make_params = build, make_params
    try:
        return serve_share.run(ctx)
    finally:
        serve.build, weights.make_params = build_theirs, make_theirs
