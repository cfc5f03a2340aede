"""The seam between a configuration file and the program under test.

A configuration file states the model in the source's own key names.
Its ``program`` object says how the program is told to build that
model: a preset of the program's and the fields replaced on it
(``dataclasses.replace``), registered under the configuration's name
with ``models.registry.register`` where a job needs a registry entry.
No program file is edited and no program option is added.

``llama_config`` cross-checks every size the program will run against
the file's own keys, so the file is the configuration *as it is run*.
"""

from __future__ import annotations

import dataclasses

# source key -> LlamaConfig field, compared after building.
_KEYS = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "ffn_size",
    "max_position_embeddings": "max_positions",
    "rope_theta": "rope_base",
    "rms_norm_eps": "rms_epsilon",
    "sliding_window": "sliding_window",
    "attention_bias": "qkv_bias",
}


def llama_config(cfg_file: dict):
    from tensorflow_train_distributed_tpu.models import llama

    prog = cfg_file["program"]
    if prog["family"] != "llama":
        raise ValueError(f"unknown program family {prog['family']!r}")
    cfg = dataclasses.replace(llama.LLAMA_PRESETS[prog["preset"]],
                              **prog.get("replace", {}))
    for key, field in _KEYS.items():
        want = cfg_file.get(key, None if key == "sliding_window"
                            else False if key == "attention_bias"
                            else KeyError)
        if want is KeyError:
            raise KeyError(f"configuration file lacks {key!r}")
        got = getattr(cfg, field)
        if key == "num_key_value_heads" and got is None:
            got = cfg.num_heads
        if got != want:
            raise ValueError(
                f"configuration file says {key}={want!r} but the program "
                f"would run {field}={got!r}")
    if cfg_file.get("hidden_act", "silu") != cfg.mlp_activation:
        raise ValueError("hidden_act differs from the program's activation")
    return cfg


def param_shapes(cfg):
    """Plain nested dicts of ``ShapeDtypeStruct`` for the model's
    ``params`` (nothing is allocated)."""
    import jax
    import jax.numpy as jnp

    from tensorflow_train_distributed_tpu.models import llama

    from benchmark.harness import weights

    model = llama.LlamaModel(cfg)
    boxed = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    return weights.plain_shapes(boxed)["params"]
