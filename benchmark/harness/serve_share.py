"""Runner kind ``serve_share``: ``serve_family``'s run for a
configuration that is ONE CHIP'S SHARE of an expert-parallel
deployment of a latent-attention, routed-expert decoder with a learned
selection of the rows attention sees (``deepseek_v32``).

What the file says and ``serve_family.moe_config`` cannot take
(``benchmark/README.md``): ``n_routed_experts`` counts the experts
held HERE, of the router's published width
(``changed.n_routed_experts.source``); the router chooses within the
best ``topk_group`` of ``n_group`` groups; the rotary frequencies are
YaRN's; an indexer (``index_n_heads`` x ``index_head_dim``) chooses
``index_topk`` rows a query.  ``share_config`` builds the program's
``MoeConfig`` from the file's ``program`` (a preset and its
replacements) and cross-checks every published key against it, as
``moe_config`` does for the whole-model files.

Importing this module registers the family (``program.family``:
``"moe_share"``) in ``serve_family.FAMILIES``; the run is
``serve_family.run``: set-up, warm-up, ramp, window, drain and the
check against ``benchmark/references/<reference>.py`` are not copied.

One traffic key is this kind's own and has one value, ``"order":
"pool"``: the pool is offered in the order ``mix_seed`` drew it, under
every ``--seed`` (``PoolOrder``).  ``loadgen.Schedule`` lets the seed
shuffle the pool, which changes no amount of work where a window spans
the pool many times; a window of requests as long as these holds ~20
of the pool's 64, so there the shuffle chose the work and the reading
followed it.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.harness import loadgen, serve_family

#: source key -> MoeConfig field, beyond ``serve_family._MOE_KEYS``
#: (whose ``n_routed_experts`` is the router's width there and the
#: experts held here).
_SHARE_KEYS = dict(
    {k: v for k, v in serve_family._MOE_KEYS.items()
     if k != "n_routed_experts"},
    n_routed_experts="experts_held", n_group="n_group",
    topk_group="topk_group", index_n_heads="index_heads",
    index_head_dim="index_dim", index_topk="index_topk",
    moe_layer_freq="moe_every")
#: What the file must say for the program's block to be the source's.
_SHARE_FIXED = {
    "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "hidden_act": "silu", "tie_word_embeddings": False,
    "num_nextn_predict_layers": 0,
}


def rope_scaling_of(cfg_file: dict):
    """The file's ``rope_scaling`` as ``layers.apply_rope`` takes it."""
    rs = cfg_file["rope_scaling"]
    if rs is None:
        return None
    if rs.get("type") != "yarn" or rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError(
            f"the program's rotary scaling is YaRN with mscale == "
            f"mscale_all_dim (cos and sin unscaled); the configuration "
            f"file says {rs!r}")
    return ("yarn", float(rs["factor"]), float(rs["beta_fast"]),
            float(rs["beta_slow"]),
            int(rs["original_max_position_embeddings"]))


def share_config(cfg_file: dict):
    """The program's ``MoeConfig`` for a file that states one chip's
    share of a ``deepseek_v32`` deployment, every size cross-checked."""
    from tensorflow_train_distributed_tpu.models import moe

    prog = cfg_file["program"]
    # A program from before this family (the parent commit of the PR
    # that brought it) says so and stops, before any weight is made.
    lacks = sorted(set(prog["replace"]) - {
        f.name for f in dataclasses.fields(moe.MoeConfig)})
    if prog["preset"] not in moe.MOE_PRESETS or lacks:
        raise ValueError(
            f"this program cannot run the configuration: it has no preset "
            f"{prog['preset']!r}" + (
                f" and no MoeConfig field {', '.join(lacks)}" if lacks
                else ""))
    cfg = dataclasses.replace(moe.MOE_PRESETS[prog["preset"]],
                              **prog["replace"])
    for key, field in _SHARE_KEYS.items():
        if key not in cfg_file:
            raise KeyError(f"configuration file lacks {key!r}")
        got, want = getattr(cfg, field), cfg_file[key]
        if got != want:
            raise ValueError(
                f"configuration file says {key}={want!r} but the program "
                f"would run {field}={got!r}")
    for key, want in _SHARE_FIXED.items():
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
    if cfg.rope_scaling != rope_scaling_of(cfg_file):
        raise ValueError(
            f"configuration file says rope_scaling="
            f"{cfg_file['rope_scaling']!r} but the program would run "
            f"rope_scaling={cfg.rope_scaling!r}")
    if cfg_file["num_key_value_heads"] != cfg_file["num_attention_heads"]:
        raise ValueError("latent attention has as many key heads as "
                         "query heads")
    if cfg.router != "sigmoid" or cfg.dispatch != "gmm":
        raise ValueError("noaux_tc is the program's sigmoid router under "
                         "dropless dispatch")
    if (cfg.shared_expert_size or 0) != (
            cfg_file["n_shared_experts"] * cfg_file["moe_intermediate_size"]):
        raise ValueError("shared expert width differs from "
                         "n_shared_experts x moe_intermediate_size")
    return cfg


serve_family.FAMILIES.setdefault(
    "moe_share", (share_config, serve_family.moe_param_shapes))


class PoolOrder(loadgen.Schedule):
    """A closed loop's schedule whose request ``i`` has the lengths of
    the pool's entry ``i`` as ``mix_seed`` drew it, whatever the seed:
    every seed offers the same sizes in the same order, and draws the
    token ids (and, in the runner, the weights) alone."""

    def __init__(self, traffic: dict, seed: int, seconds: float,
                 vocab_size: int):
        super().__init__(traffic, seed, seconds, vocab_size)
        if self.loop != "closed":
            raise ValueError('"order": "pool" is a closed loop\'s key')
        # Schedule's own draw of the pool, not shuffled afterwards.
        mix = np.random.default_rng(int(traffic["mix_seed"]))
        pool = int(traffic["pool"])
        self._prompts = loadgen.draw_lengths(traffic["prompt_len"], pool,
                                             mix)
        self._outputs = loadgen.draw_lengths(traffic["output_len"], pool,
                                             mix)


def run(ctx: dict) -> dict:
    # Stated in the file, so that whoever reads the traffic sees it; no
    # other order is run here.
    if ctx["traffic"].get("order") != "pool":
        raise ValueError(
            f'a serve_share traffic file says "order": "pool"; this one '
            f'says {ctx["traffic"].get("order")!r}')
    # ``serve_family.run`` builds its schedule as ``loadgen.Schedule``
    # and takes no other, so the name is lent for the run (a
    # ``benchmark`` PR gives ``run`` the argument: PERF.md section 7).
    theirs, loadgen.Schedule = loadgen.Schedule, PoolOrder
    try:
        return serve_family.run(ctx)
    finally:
        loadgen.Schedule = theirs
