"""Runner kind ``serve_sink``: ``serve_family``'s run for a
configuration whose full and sliding-window layers differ in their KV
HEADS, whose key and value heads differ in SIZE, and whose window
layers carry a learned SINK in the softmax (``mimo_v2``): sigmoid-routed
experts with no shared one, of which a chip's share is held here.

What the file says and ``serve_pattern.pattern_config`` cannot take:
the pattern as ``hybrid_layer_pattern`` (0 full, 1 window; no period
from layer 0) and ``moe_layer_freq``, a window layer's sizes under
``swa_*`` keys, ``v_head_dim`` beside ``head_dim``,
``attention_value_scale``, the two sink flags, ``routed_scaling_factor``
and ``n_shared_experts`` null; ``n_routed_experts`` counts the experts
held HERE, of the router's published width
(``changed.n_routed_experts.source``).  ``sink_config`` builds the
program's ``MoeConfig`` from the file's ``program`` (a preset and its
replacements) and cross-checks every published key against it, the
pattern entry by entry against the lead and period the program would
run.

Importing this module registers the family (``program.family``:
``"moe_sink"``) in ``serve_family.FAMILIES``.  The run is
``serve_share.run``: ``serve_family.run`` (set-up, warm-up, ramp,
window, drain and the check against
``benchmark/references/<reference>.py`` are not copied) with the pool
offered in the order ``mix_seed`` drew it under every ``--seed``
(``"order": "pool"``, the one value that runner takes).  A window of
this traffic finishes ~50 of the pool's 64 requests and is bound by
prefill, so a pool shuffled by the seed chose the work and the reading
followed it: 7.0% over six seeds against the 5% a new cell may spread
(my chip runs, PR 41; PERF.md section 6), as ``longctx-mixed`` found at
21%.

One name is lent to that run, as ``serve_hybrid.run`` lends it and for
the same reason (``serve_family.run`` takes no argument for it; a
``benchmark`` PR gives it one: PERF.md section 7):
``weights.make_params``.  ``weights._fill`` draws every ``bias`` about
zero (std 0.02), and a sink of logit 0 beside a window's 128 scores of
unit spread takes 0.5% of a row's mass: leaving the sink out then
scales a window layer's output by 1.005 and no limit of ``correct``
sees it (my chip runs, PR 41: ``served_gap_mean`` 0.00031, inside the
sound runs').  ``seeded_sinks`` refills each window layer's
``sink/bias`` normal about ``SINK_LOGIT[0]`` with std ``SINK_LOGIT[1]``
from the seed: at 3 +- 1 a sink takes 14% of a row's mass in the mean
and up to three fifths in a head, what a trained sink is there to do
(a head that wants no key gives its mass to it), so that a kernel whose
running softmax does not start from the sink parts from the reference
by more than a window one key short does.  The issue's own figure, std
1 about 0, takes 0.8% in the mean and would not be seen either (the
same arithmetic at the cell's widths: leaving it out moves a window
layer's output by 1.1%, a window of 127 for 128 by 9.1%).  The
reference reads the same arrays.
"""

from __future__ import annotations

import dataclasses

import jax

from benchmark.harness import serve_family, serve_share, weights

#: source key -> MoeConfig field, compared after building.
_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "v_head_dim": "v_head_dim",
    "swa_head_dim": "head_dim",
    "swa_v_head_dim": "v_head_dim",
    "attention_value_scale": "value_scale",
    "intermediate_size": "dense_ffn_size",
    "moe_intermediate_size": "ffn_size",
    "n_routed_experts": "experts_held",
    "num_experts_per_tok": "top_k",
    "max_position_embeddings": "max_positions",
    "layernorm_epsilon": "rms_epsilon",
    "norm_topk_prob": "norm_topk_prob",
    "attention_bias": "qkv_bias",
    "n_group": "n_group",
    "topk_group": "topk_group",
    "rope_theta": "rope_base",
    "n_shared_experts": "shared_expert_size",
}
#: What the file must say for the program's block to be the source's
#: (the program has no option for anything else).
_FIXED = {
    "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "hidden_act": "silu", "tie_word_embeddings": False,
    "hybrid_block_size": None, "routed_scaling_factor": None,
    "rope_scaling": {"rope_type": "default", "type": "default"},
}
#: The fields a program needs for this family at all.
_FIELDS = ("attn_period", "attn_lead", "head_dim", "v_head_dim",
           "value_scale", "experts_held")
_LISTS = ("hybrid_layer_pattern", "moe_layer_freq")


def kind_of(cfg_file: dict, layer: int) -> tuple:
    """Layer ``layer`` as the file states it, in the program's terms:
    ``(query heads, window or None, rope_base, rotary share, scaling
    (None), KV heads, sink)`` (``models.moe.KvKind``'s fields, in
    order, the KV heads said outright)."""
    if cfg_file["hybrid_layer_pattern"][layer] not in (0, 1):
        raise ValueError("hybrid_layer_pattern holds 0 (full) and 1 "
                         "(window)")
    share = float(cfg_file["partial_rotary_factor"])
    if cfg_file["hybrid_layer_pattern"][layer]:
        return (cfg_file["swa_num_attention_heads"],
                cfg_file["sliding_window"],
                float(cfg_file["swa_rope_theta"]), share, None,
                cfg_file["swa_num_key_value_heads"],
                cfg_file["add_swa_attention_sink_bias"])
    return (cfg_file["num_attention_heads"], None,
            float(cfg_file["rope_theta"]), share, None,
            cfg_file["num_key_value_heads"],
            cfg_file["add_full_attention_sink_bias"])


def sink_config(cfg_file: dict):
    """The program's ``MoeConfig`` for a file that states one chip's
    share of a ``mimo_v2`` deployment, every size and every layer of the
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
    if cfg.routed_scaling != 1.0:
        raise ValueError(
            f"routed_scaling_factor is null (gates unscaled) but the "
            f"program would run routed_scaling={cfg.routed_scaling}")
    if cfg_file["sliding_window_size"] != cfg_file["sliding_window"] or (
            cfg_file["attention_chunk_size"] != cfg_file["sliding_window"]):
        raise ValueError("sliding_window, sliding_window_size and "
                         "attention_chunk_size say one window")
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
    if cfg.router != "sigmoid" or cfg.dispatch != "gmm":
        raise ValueError("noaux_tc is the program's sigmoid router under "
                         "dropless dispatch")
    if not cfg.attn_period or cfg.kv_lora_rank or cfg.attn_gate:
        raise ValueError("the file states a pattern of ungated MHA/GQA "
                         "layers; the program would run something else")
    # The pattern, entry by entry over every layer the file lists (the
    # published 48, of which the first ``num_hidden_layers`` run).
    n = len(cfg_file["hybrid_layer_pattern"])
    if n < cfg.num_layers or any(len(cfg_file[k]) != n for k in _LISTS):
        raise ValueError(
            f"the per-layer lists state {[len(cfg_file[k]) for k in _LISTS]}"
            f" layers; the program would run {cfg.num_layers}")
    for i in range(n):
        kind = cfg.attn_kind(i)
        got = dataclasses.astuple(kind)
        got = got[:5] + (got[5] or cfg.num_kv_heads,) + got[6:]
        want = kind_of(cfg_file, i)
        if kind.kind != "softmax" or got != want:
            raise ValueError(
                f"configuration file says layer {i} is {want!r} (heads, "
                f"window, rope_theta, rotary share, scaling, KV heads, "
                f"sink) but the program would run {got!r}")
        dense = i < cfg.dense_layers
        if (cfg_file["moe_layer_freq"][i] == 0) != dense or (
                cfg.moe_every != 1):
            raise ValueError(
                f"configuration file says moe_layer_freq[{i}]="
                f"{cfg_file['moe_layer_freq'][i]} but the program would "
                f"run dense_layers={cfg.dense_layers}, "
                f"moe_every={cfg.moe_every}")
    return cfg


serve_family.FAMILIES.setdefault(
    "moe_sink", (sink_config, serve_family.moe_param_shapes))


#: The sink logits' seeded mean and std (module docstring).
SINK_LOGIT = (3.0, 1.0)


def seeded_sinks(params, seed: int):
    """``params`` with every ``sink/bias`` refilled normal about
    ``SINK_LOGIT`` from the seed, in the leaves' own type and place (a
    tree without such leaves comes back as it is)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    names = [tuple(getattr(p, "key", "") for p in path)[-2:]
             for path, _ in flat]
    if ("sink", "bias") not in names:
        return params
    seed = int(seed)
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32),
        0x51C)
    # The few small leaves alone are made anew; every other leaf is
    # handed on as it is.
    keys = jax.random.split(key, len(flat))
    mean, std = SINK_LOGIT
    out = [(mean + std * jax.random.normal(k, leaf.shape)
            ).astype(leaf.dtype) if name == ("sink", "bias") else leaf
           for name, (_, leaf), k in zip(names, flat, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)


def run(ctx: dict) -> dict:
    theirs = weights.make_params

    def make_params(shapes, seed, dtype):
        return seeded_sinks(theirs(shapes, seed, dtype), seed)

    weights.make_params = make_params
    try:
        return serve_share.run(ctx)
    finally:
        weights.make_params = theirs
