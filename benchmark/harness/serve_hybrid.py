"""Runner kind ``serve_hybrid``: ``serve_family``'s run for a
configuration whose layers are of TWO KINDS OF STATE (``bailing_hybrid``):
delta-rule linear-attention layers, each a recurrent state a lane and no
rows, beside latent-attention layers of one row a token, a per-head
output gate on both, group-limited sigmoid-routed experts of which a
chip's share is held here.

What the file says and ``serve_family.moe_config`` cannot take:
``layer_group_size`` (layer ``i`` is latent where ``(i + 1) % group ==
0``, linear otherwise), the linear layers' keys
(``short_conv_kernel_size``, ``kda_lower_bound``, ``kda_safe_gate``,
``num_kv_heads_for_linear_attn`` ...), ``q_lora_rank: null``;
``num_experts`` counts the experts held HERE, of the router's published
width (``changed.num_experts.source``).  ``hybrid_config`` builds the
program's ``MoeConfig`` from the file's ``program`` (a preset and its
replacements) and cross-checks every published key against it, the
kind of every layer against the period the program would run.

Importing this module registers the family (``program.family``:
``"moe_hybrid"``) in ``serve_family.FAMILIES``; the run is
``serve_family.run``: set-up, warm-up, ramp, window, drain and the
check against ``benchmark/references/<reference>.py`` are not copied.
Two names are lent to it for the run, each because ``serve_family.run``
takes no argument for it (a ``benchmark`` PR gives it them: PERF.md
section 7):

- ``weights.make_params``: ``weights._fill`` draws a ``bias`` near zero
  and a ``kernel`` at unit scale, so a linear layer's log decay ``-5 x
  sigmoid(x Wf + b)`` would lie in (-4.4, -0.6), every step's decay
  under 0.55, and the layer would forget in two tokens: no fault in the
  state's handling and no chunk-to-chunk path of the scan could then be
  seen by ``correct``.  ``seeded_decay`` refills each linear layer's
  ``decay/bias`` uniform on (-9, -4) and its ``a_log`` with zeros, from
  the seed: decays between ~0.55 and ~0.9999 a step, median ~0.99, the
  range the layer's published initialiser aims at.  The reference reads
  the same arrays.
- ``serve.warm``: its ``info``, which the ``warm`` line prints, gains
  ``state_pool_bytes`` beside ``kv_pool_bytes`` (rows and state apart).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from benchmark.harness import serve, serve_family, weights

#: source key -> MoeConfig field, compared after building.
_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "head_dim": "head_dim",
    "intermediate_size": "dense_ffn_size",
    "moe_intermediate_size": "ffn_size",
    "moe_shared_expert_intermediate_size": "shared_expert_size",
    "num_experts": "experts_held",
    "num_experts_per_tok": "top_k",
    "first_k_dense_replace": "dense_layers",
    "max_position_embeddings": "max_positions",
    "rms_norm_eps": "rms_epsilon",
    "routed_scaling_factor": "routed_scaling",
    "norm_topk_prob": "norm_topk_prob",
    "n_group": "n_group",
    "topk_group": "topk_group",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim",
    "short_conv_kernel_size": "linear_conv",
    "kda_lower_bound": "linear_decay_floor",
    "use_qkv_bias": "qkv_bias",
}
#: What the file must say for the program's block to be the source's
#: (the program has no option for anything else).
_FIXED = {
    "topk_method": "noaux_tc", "score_function": "sigmoid",
    "scoring_func": "sigmoid", "hidden_act": "silu",
    "tie_word_embeddings": False, "rope_scaling": None,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "kda_safe_gate": True, "linear_silu": True,
    "no_kda_lora": True, "use_kda_lora": False,
    "num_kv_heads_for_linear_attn": 0, "use_qk_norm": True,
    "use_bias": False, "moe_router_enable_expert_bias": True,
    "scale_router_input": False, "use_nGPT": False, "value_norm": False,
    "up_proj_norm": False, "use_mla_nope": False,
}
#: The fields a program needs for this family at all.
_FIELDS = ("attn_period", "attn_gate", "head_dim", "experts_held",
           "linear_conv", "linear_decay_floor")


def kind_of(cfg_file: dict, layer: int) -> str:
    """Layer ``layer`` as the file states it: ``"latent"`` where
    ``(layer + 1) % layer_group_size == 0``, else ``"linear"``."""
    return ("latent" if (layer + 1) % cfg_file["layer_group_size"] == 0
            else "linear")


def hybrid_config(cfg_file: dict):
    """The program's ``MoeConfig`` for a file that states one chip's
    share of a ``bailing_hybrid`` deployment, every size and every
    layer's kind cross-checked."""
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
        raise ValueError("noaux_tc is the program's sigmoid router under "
                         "dropless dispatch")
    if cfg_file["num_key_value_heads"] != cfg_file["num_attention_heads"]:
        raise ValueError("as many key heads as query heads in both kinds "
                         "of layer")
    if cfg_file["qk_head_dim"] != cfg.qk_nope_dim + cfg.qk_rope_dim or (
            cfg_file["rotary_dim"] != cfg.qk_rope_dim):
        raise ValueError("qk_head_dim is nope + rope and rotary_dim the "
                         "rope part")
    if not cfg.attn_gate or not cfg.attn_period:
        raise ValueError("the file states a pattern of gated layers; the "
                         "program would run one ungated kind")
    # The pattern over every PUBLISHED layer (the swiglu limit lists are
    # as long as the source is deep), of which the first
    # ``num_hidden_layers`` run; no clamp on a layer that runs.
    deep = cfg_file["changed"]["num_hidden_layers"]["source"]
    for name in ("expert_swiglu_limit_list",
                 "share_expert_swiglu_limit_list"):
        if len(cfg_file[name]) != deep or any(
                cfg_file[name][:cfg.num_layers]):
            raise ValueError(
                f"{name} states {len(cfg_file[name])} layers, "
                f"{cfg_file[name][:cfg.num_layers]} on those that run; the "
                f"program's SwiGLUs have no clamp")
    for i in range(deep):
        kind = cfg.attn_kind(i)
        if kind.kind != kind_of(cfg_file, i) or (
                kind.num_heads != cfg_file["num_attention_heads"]):
            raise ValueError(
                f"configuration file says layer {i} is "
                f"{kind_of(cfg_file, i)} with "
                f"{cfg_file['num_attention_heads']} heads but the program "
                f"would run {kind!r}")
        if kind.kind == "latent" and (
                kind.rope_base != float(cfg_file["rope_theta"])
                or kind.rope_scaling is not None):
            raise ValueError(
                f"configuration file says rope_theta="
                f"{cfg_file['rope_theta']} unscaled but layer {i} would "
                f"run {kind!r}")
    return cfg


serve_family.FAMILIES.setdefault(
    "moe_hybrid", (hybrid_config, serve_family.moe_param_shapes))


#: The decay's seeded range (module docstring).
DECAY_BIAS = (-9.0, -4.0)


def seeded_decay(params, seed: int):
    """``params`` with every linear layer's ``decay/bias`` refilled
    uniform on ``DECAY_BIAS`` from the seed and its ``a_log`` with
    zeros, in the leaves' own type and place (a tree without such
    leaves comes back as it is)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    names = [tuple(getattr(p, "key", "") for p in path)[-2:]
             for path, _ in flat]
    if ("decay", "bias") not in names:
        return params
    seed = int(seed)
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32),
        0xDECA)

    # The few small leaves alone are made anew; every other leaf is
    # handed on as it is (a jit over the whole tree would copy 10 GB).
    keys = jax.random.split(key, len(flat))
    out = []
    for name, (_, leaf), k in zip(names, flat, keys):
        if name == ("decay", "bias"):
            leaf = jax.random.uniform(
                k, leaf.shape, jnp.float32, *DECAY_BIAS).astype(leaf.dtype)
        elif name == ("a_log", "bias"):
            leaf = jnp.zeros_like(leaf)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def run(ctx: dict) -> dict:
    theirs = weights.make_params, serve.warm

    def make_params(shapes, seed, dtype):
        return seeded_decay(theirs[0](shapes, seed, dtype), seed)

    def warm(engine, *args, **kwargs):
        info = theirs[1](engine, *args, **kwargs)
        info["state_pool_bytes"] = engine.state_pool_bytes()
        return info

    weights.make_params, serve.warm = make_params, warm
    try:
        return serve_family.run(ctx)
    finally:
        weights.make_params, serve.warm = theirs
