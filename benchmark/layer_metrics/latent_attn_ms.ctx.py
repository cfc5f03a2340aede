"""Device time a decode step spends in the absorbed latent-attention
kernel: the ``tpu_custom_call`` events named ``paged_latent_attention``
(the Pallas call's own ``name=``) inside the executions of
``_decode_chunk``, all layers.  Layer: kernels / program roofline.
Moves ``serve_tokens_per_s``."""

from benchmark.harness import scope_table


def read(ctx):
    table = scope_table.decode_table(ctx)
    return table and table["kernel_ms"].get(scope_table.LATENT_KERNEL)
