"""The prefill pieces' latent-attention kernel's share of the matrix
unit's peak where only SOME layers run it: ``prefix_flash_roofline.
longctx``'s reading (its file beside this one: the joined executions of
``_prefill_piece``, ``costs_flash_latent.call_flops`` over the pairs
the queries see and the rows up-projected, the ``tpu_custom_call``s
named ``prefix_flash_latent``) with the operations counted for the
layers that run the kernel, the ``full_attention`` ones among those
run (``costs_latents.full_layers``: three of nine), and not for every
layer of the file: a window layer's piece walks in XLA and has no such
call.  The log line is that reader's own (``prefix_flash_roofline.
longctx``).  Layer: kernels / program roofline.  Moves
``serve_tokens_per_s``."""

import importlib.util
import os

from benchmark.harness import costs_latents

_spec = importlib.util.spec_from_file_location(
    "bench_layer_metric_prefix_flash_roofline_longctx",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "prefix_flash_roofline.longctx.py"))
_longctx = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_longctx)


def read(ctx):
    cfg = ctx["config"]
    if "layer_types" not in cfg:
        return None
    return _longctx.read(dict(ctx, config=dict(
        cfg, num_hidden_layers=costs_latents.full_layers(cfg))))
