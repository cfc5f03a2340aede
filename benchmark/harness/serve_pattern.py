"""Runner kind ``serve_pattern``: ``serve_family``'s run for a
configuration whose LAYERS DIFFER BY A PATTERN (``laguna``): full and
sliding-window attention layers side by side, each kind with its own
query heads and rotary rule, a per-head output gate, sigmoid-routed
experts of which a chip's share is held here.

What the file says and ``serve_family.moe_config`` cannot take: the
per-layer lists (``layer_types``, ``num_attention_heads_per_layer``,
``gating_types``, ``mlp_layer_types``, as published: the first
``num_hidden_layers`` entries run) and ``rope_parameters`` by kind of
layer; ``num_experts`` counts the experts held HERE, of the router's
published width (``changed.num_experts.source``).  ``pattern_config``
builds the program's ``MoeConfig`` from the file's ``program`` (a preset
and its replacements) and cross-checks every published key against it,
the pattern entry by entry against the period the program would run.

Importing this module registers the family (``program.family``:
``"moe_pattern"``) in ``serve_family.FAMILIES``; the run is
``serve_family.run``: set-up, warm-up, ramp, window, drain and the
check against ``benchmark/references/<reference>.py`` are not copied.
"""

from __future__ import annotations

import dataclasses

from benchmark.harness import serve_family

#: source key -> MoeConfig field, compared after building.
_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "dense_ffn_size",
    "moe_intermediate_size": "ffn_size",
    "shared_expert_intermediate_size": "shared_expert_size",
    "num_experts": "experts_held",
    "num_experts_per_tok": "top_k",
    "max_position_embeddings": "max_positions",
    "rms_norm_eps": "rms_epsilon",
    "moe_routed_scaling_factor": "routed_scaling",
    "norm_topk_prob": "norm_topk_prob",
    "attention_bias": "qkv_bias",
    "decoder_sparse_step": "moe_every",
}
#: What the file must say for the program's block to be the source's
#: (the program has no option for anything else).
_FIXED = {
    "tie_word_embeddings": False,
    "moe_apply_router_weight_on_input": False,
    "moe_router_logit_softcapping": 0,
    "gating": "per-head",
}
#: The fields a program needs for this family at all.
_FIELDS = ("attn_period", "attn_gate", "head_dim", "experts_held")
_LISTS = ("layer_types", "mlp_layer_types", "gating_types",
          "num_attention_heads_per_layer")


def kind_of(cfg_file: dict, layer: int) -> tuple:
    """Layer ``layer`` as the file states it, in the program's terms:
    ``(query heads, window or None, rope_base, rotary share, scaling
    tuple or None)`` (``models.moe.AttnKind``'s fields, in order)."""
    name = cfg_file["layer_types"][layer]
    rule = cfg_file["rope_parameters"][name]
    window = {"full_attention": None,
              "sliding_attention": cfg_file["sliding_window"]}[name]
    scaling = None
    if rule["rope_type"] == "yarn":
        scaling = ("yarn", float(rule["factor"]), float(rule["beta_fast"]),
                   float(rule["beta_slow"]),
                   int(rule["original_max_position_embeddings"]),
                   float(rule["attention_factor"]))
    elif rule["rope_type"] != "default":
        raise ValueError(f"the program rotates by 'default' or 'yarn'; "
                         f"the configuration file says {rule!r}")
    return (cfg_file["num_attention_heads_per_layer"][layer], window,
            float(rule["rope_theta"]),
            float(rule["partial_rotary_factor"]), scaling)


def pattern_config(cfg_file: dict):
    """The program's ``MoeConfig`` for a file that states one chip's
    share of a ``laguna`` deployment, every size and every layer of the
    pattern cross-checked."""
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
    published = cfg_file["changed"]["num_experts"]["source"]
    if cfg.num_experts != published:
        raise ValueError(
            f"the source routes over {published} experts but the program "
            f"would run a router of num_experts={cfg.num_experts}")
    if cfg.experts_offset != cfg_file.get("experts_offset", 0):
        raise ValueError(
            f"configuration file says experts_offset="
            f"{cfg_file.get('experts_offset', 0)} but the program would "
            f"run experts_offset={cfg.experts_offset}")
    if cfg.router != "sigmoid" or cfg.dispatch != "gmm":
        raise ValueError("the file's router is the program's sigmoid "
                         "router under dropless dispatch")
    if not cfg.attn_period or cfg.kv_lora_rank:
        raise ValueError("the program would run one kind of attention "
                         "layer; the configuration file states a pattern")
    # The pattern, entry by entry over every layer the file lists (the
    # published 48, of which the first ``num_hidden_layers`` run).
    n = len(cfg_file["layer_types"])
    if n < cfg.num_layers or any(len(cfg_file[k]) != n for k in _LISTS):
        raise ValueError(
            f"the per-layer lists state {[len(cfg_file[k]) for k in _LISTS]}"
            f" layers; the program would run {cfg.num_layers}")
    for i in range(n):
        got = dataclasses.astuple(cfg.attn_kind(i))
        want = kind_of(cfg_file, i)
        if got != want:
            raise ValueError(
                f"configuration file says layer {i} is {want!r} (heads, "
                f"window, rope_theta, rotary share, scaling) but the "
                f"program would run {got!r}")
        gated = {"per_head": True, None: False}[cfg_file["gating_types"][i]]
        if gated != cfg.attn_gate:
            raise ValueError(
                f"configuration file says gating_types[{i}]="
                f"{cfg_file['gating_types'][i]!r} but the program would "
                f"run attn_gate={cfg.attn_gate}")
        dense = i < cfg.dense_layers
        if (cfg_file["mlp_layer_types"][i] == "dense") != dense or (
                (i in cfg_file["mlp_only_layers"]) != dense):
            raise ValueError(
                f"configuration file says mlp_layer_types[{i}]="
                f"{cfg_file['mlp_layer_types'][i]!r} (mlp_only_layers "
                f"{cfg_file['mlp_only_layers']}) but the program would run "
                f"dense_layers={cfg.dense_layers}")
    return cfg


serve_family.FAMILIES.setdefault(
    "moe_pattern", (pattern_config, serve_family.moe_param_shapes))


#: The run is the family runner's, whole.
run = serve_family.run
