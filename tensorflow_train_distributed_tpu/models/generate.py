"""Autoregressive generation with a KV cache for the decoder family.

The reference is a training harness — its SFT config (SURVEY.md §2.1
config[4]) produces a model users then sample from elsewhere; here the
framework closes that loop natively.  TPU-first shape discipline: one
jitted function, static prompt/output lengths, ``lax.scan`` over decode
steps (no per-token dispatch), cache buffers donated between steps by XLA.

Two phases inside one jit:
- prefill: the whole prompt in a single call (``decode=True`` attention
  appends all prompt positions to the cache at once, causal via the index
  mask);
- step: ``lax.scan`` over single-token calls, greedy or temperature
  sampling.  Only the greedy-vs-sampling *branch* is static; the
  temperature value is traced, so a temperature sweep reuses one compile.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from tensorflow_train_distributed_tpu.models.llama import (
    LlamaConfig,
    LlamaModel,
)
from tensorflow_train_distributed_tpu.models.quant import (
    maybe_quant_variables,
    quantized_inference,
)
from tensorflow_train_distributed_tpu.runtime.lint.registry import (
    compile_site,
)


def _decode_model(config, cache_len: int, slot_decode: bool = False,
                  paged_kv_blocks: int = 0, kv_block_size: int = 0,
                  ring_blocks: int = 0, query_block: int = 0):
    """The decode-mode model for a decoder-family config: LlamaModel for
    LlamaConfig, MoeLmModel for MoeConfig (Mixtral-style) — one generate
    path serves every decoder family.  ``slot_decode`` selects the
    per-slot cache-index mode (serving.ServingEngine), and
    ``paged_kv_blocks``/``kv_block_size`` its paged-pool variant (the
    engine's block-table cache; ``ring_blocks`` the blocks of a window
    layer's ring a lane, for a config that has window layers;
    ``query_block`` the queries its batch-1 prefill model's attention
    walks at a time: the engine's ``prefill_chunk``); this is the ONE
    family-dispatch point, shared by generate and the engine."""
    from tensorflow_train_distributed_tpu.models.moe import (
        MoeConfig,
        MoeLmModel,
    )

    cls = MoeLmModel if isinstance(config, MoeConfig) else LlamaModel
    return cls(config, decode=True, cache_len=cache_len,
               slot_decode=slot_decode,
               paged_kv_blocks=paged_kv_blocks,
               kv_block_size=kv_block_size, query_block=query_block,
               # a field of the family whose layers may differ by kind
               **({"ring_blocks": ring_blocks} if ring_blocks else {}))


def cast_floating(params, dtype):
    """Cast floating leaves to ``dtype`` (inference precision).

    Reads ``.dtype`` directly — ``jnp.asarray`` would round-trip every
    leaf through the device just to inspect it (26 GB of H2D at 7B).
    int8 kernels, ints, and non-array leaves pass through untouched.
    """
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        params)


def has_lora_leaves(params) -> bool:
    """Whether a param tree carries unmerged LoRA adapters."""
    return any(
        getattr(p[-1], "key", None) in ("lora_a", "lora_b")
        for p, _ in jax.tree_util.tree_flatten_with_path(params)[0])


def validate_sampling(temperature, top_k, top_p) -> None:
    """Shared sampling-knob validation (generate + serving engine)."""
    if temperature < 0:
        raise ValueError(
            f"temperature must be >= 0, got {temperature} (negative "
            "values invert the distribution)")
    if temperature == 0.0 and (top_k is not None or top_p is not None):
        raise ValueError(
            "top_k/top_p filter a sampling distribution; set "
            "temperature > 0 (greedy argmax is unaffected by them)")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")


def filter_logits(logits, *, temperature, top_k=None, top_p=None):
    """Temperature scale + top-k + nucleus filters over f32 ``logits``
    [..., V] — the sampling-distribution shaping shared by ``generate``
    and the serving engine (``top_k`` static: it sets the lax.top_k
    shape; ``temperature``/``top_p`` traced).  Filters compose k first,
    then p (the HF convention)."""
    logits = logits / temperature
    if top_k is not None and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        # Nucleus: keep the smallest prefix (by descending prob)
        # whose mass reaches p; the first token always survives.
        sorted_desc = -jnp.sort(-logits, axis=-1)
        cum = jnp.cumsum(jax.nn.softmax(sorted_desc), axis=-1)
        keep = cum - jax.nn.softmax(sorted_desc) <= top_p
        cutoff = jnp.min(
            jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
            keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def generate(config: LlamaConfig, params, prompt: jax.Array,
             max_new_tokens: int, *, temperature: float = 0.0,
             top_k: Optional[int] = None, top_p: Optional[float] = None,
             rng: Optional[jax.Array] = None,
             cast_params: bool = True,
             quant_scales=None) -> jax.Array:
    """Sample ``max_new_tokens`` continuations of ``prompt`` [B, S].

    ``temperature`` 0 → greedy argmax; > 0 → categorical sampling with
    ``rng`` (required).  ``top_k`` keeps only the k highest logits;
    ``top_p`` keeps the smallest nucleus of tokens whose probability mass
    reaches p (Holtzman et al.) — both filters apply after the
    temperature scale, compose (k first, then p — the HF convention), and
    require ``temperature > 0``.  Returns [B, S + max_new_tokens] ids.
    Prompt + new tokens must fit ``config.max_positions`` (the cache size).

    ``cast_params``: cast floating params to ``config.dtype`` before
    inference — a trained state carries f32 masters (26 GB at 7B), which
    inference neither needs nor fits on one chip; the compute path runs in
    ``config.dtype`` either way.  No-op for f32 configs.

    ``quant_scales``: the scale tree from ``models.quant.quantize_params``
    — pass it together with the int8 ``params`` that call returned and
    every Dense runs the fused weight-only-int8 path (decode weight
    traffic halves vs bf16).  int8 kernels are untouched by
    ``cast_params``.
    """
    b, prompt_len = prompt.shape
    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, got "
                         f"{max_new_tokens}")
    if max_new_tokens == 0:
        return prompt
    if prompt_len + max_new_tokens > config.max_positions:
        raise ValueError(
            f"prompt {prompt_len} + {max_new_tokens} new tokens exceeds "
            f"max_positions={config.max_positions} (the KV cache size)")
    validate_sampling(temperature, top_k, top_p)
    greedy = temperature == 0.0
    if not greedy and rng is None:
        raise ValueError("temperature sampling needs rng=")
    if rng is None:
        rng = jax.random.key(0)  # unused under greedy; keeps shapes static
    from tensorflow_train_distributed_tpu.models.lora import spec_of

    if spec_of(config) is not None and quant_scales is not None:
        raise ValueError(
            "int8 serving of a LoRA model needs the adapters folded in "
            "first: params = models.lora.merge_lora(params, spec), then "
            "quantize the merged tree with a lora=None config")
    if spec_of(config) is not None and has_lora_leaves(params):
        # Targets/rank must agree with the adapters actually present —
        # flax silently ignores unread leaves, so a narrower serving
        # spec would silently drop part of the fine-tune.
        from tensorflow_train_distributed_tpu.models.lora import (
            check_spec_matches,
        )

        check_spec_matches(params, spec_of(config))
    if spec_of(config) is None and has_lora_leaves(params):
        # flax apply would silently IGNORE the extra adapter leaves and
        # serve the un-adapted base — the fine-tuning vanishing without
        # a trace is the worst possible failure mode here.
        raise ValueError(
            "params carry unmerged LoRA adapters but config.lora is not "
            "set: either serve with the training config (lora=LoraSpec) "
            "or fold them in first via models.lora.merge_lora")
    from tensorflow_train_distributed_tpu.models.quant import (
        check_quant_pairing,
    )

    check_quant_pairing(params, quant_scales)
    if cast_params:
        params = cast_floating(params, config.dtype)
    # top_k is static (it sets the lax.top_k shape); top_p is a TRACED
    # scalar so a sampling sweep over p reuses one compiled graph.
    return _generate(config, max_new_tokens, greedy, top_k,
                     top_p is not None, params, prompt,
                     jnp.float32(temperature),
                     jnp.float32(1.0 if top_p is None else top_p), rng,
                     quant_scales)


@compile_site(buckets="exact (offline batch API: one compile per "
                      "prompt/output shape is the documented contract "
                      "— the serving engine is the bucketed path)",
              donates=(), statics=(),
              static_names=("config", "max_new_tokens", "greedy",
                            "top_k", "use_top_p"),
              max_compiles=None)
@partial(jax.jit, static_argnames=("config", "max_new_tokens", "greedy",
                                   "top_k", "use_top_p"))
def _generate(config: LlamaConfig, max_new_tokens: int, greedy: bool,
              top_k, use_top_p, params, prompt, temperature, top_p, rng,
              quant_scales=None):
    # Cache sized to the request, not max_positions: a 30-token generation
    # from a 4k-context config must not allocate (or attend over) 4k
    # cache rows per layer.
    model = _decode_model(config,
                          cache_len=prompt.shape[1] + max_new_tokens)

    def pick(logits, step_rng):
        logits = logits.astype(jnp.float32)
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(prompt.dtype)
        logits = filter_logits(logits, temperature=temperature,
                               top_k=top_k,
                               top_p=top_p if use_top_p else None)
        return jax.random.categorical(
            step_rng, logits, axis=-1).astype(prompt.dtype)

    base_vars = maybe_quant_variables(params, quant_scales)

    def infer_ctx():
        # LoRA configs serve unmerged adapters through the same
        # interceptor the training task uses; otherwise the (free when
        # inactive) int8 interceptor.  The two do not compose — generate
        # rejects that pairing up front.
        from tensorflow_train_distributed_tpu.models.lora import (
            maybe_lora_scope, spec_of,
        )

        return maybe_lora_scope(spec_of(config),
                                fallback=quantized_inference)

    # Prefill: whole prompt at once; next token comes from the last logit.
    with infer_ctx():
        logits, variables = model.apply(
            base_vars, prompt, mutable=["cache"])
    rngs = jax.random.split(rng, max_new_tokens)
    first = pick(logits[:, -1], rngs[0])

    def step(carry, step_rng):
        cache, tok = carry
        with infer_ctx():
            logits, updated = model.apply(
                dict(base_vars, cache=cache), tok[:, None],
                mutable=["cache"])
        nxt = pick(logits[:, -1], step_rng)
        return (updated["cache"], nxt), tok

    # first is token 1 of n; n-1 scan steps sample the rest.  toks collects
    # each step's *input* token, so toks = tokens 1..n-1 and `last` is n.
    (_, last), toks = jax.lax.scan(
        step, (variables["cache"], first), rngs[1:])
    out = jnp.moveaxis(toks, 0, 1)
    return jnp.concatenate([prompt, out, last[:, None]], axis=1)
