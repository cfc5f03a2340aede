"""The main-path kernels compile for a TPU v5e — without one.

The TPU compiler is installed here and compiles for a chip that is
*described*, not attached (``jax.experimental.topologies``).  Interpret
mode cannot see what it refuses: a slice off the tiling, too much VMEM,
a Mosaic kernel GSPMD cannot partition.  Each case hands one kernel real
widths with ``use_pallas=True`` and asserts the compiled program holds a
``tpu_custom_call`` — so a quiet pure-jax path cannot pass either.

A compile that passes here is NOT a chip run: nothing executes, so it
says nothing about numerics or time (``chip_smoke.py`` does, on the
chip).  Kernels, and ONE whole program: the decode program of the
benchmark's ``qwen25-7b-1chip`` (three seconds), for the names the
device trace is read by.  Other whole-program compiles (the train step,
the prefill pieces) are rehearsed from scratch scripts before a chip
call, not in tier-1.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from tensorflow_train_distributed_tpu.ops import attention, pallas_kernels as pk
from tensorflow_train_distributed_tpu.runtime.mesh import AXES

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def v5e():
    """A described v5e 2x2; skipped only where it cannot be described."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    return topo.devices


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """An entry compiled for a described chip is written to the
    persistent cache but cannot be read back without one (the next run
    warns and recompiles), so these compiles keep the cache out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _rms(grad):
    x = ((4, 2048, 1024), BF16)
    scale = ((1024,), jnp.float32)

    def fwd(x, s):
        return pk.rms_norm(x, s, use_pallas=True)

    def bwd(x, s):
        return jax.grad(lambda x, s: fwd(x, s).astype(jnp.float32).sum(),
                        argnums=(0, 1))(x, s)

    return (bwd if grad else fwd), (x, scale)


def _ce(vocab, grad):
    logits = ((2048, vocab), jnp.float32)
    labels = ((2048,), jnp.int32)

    def fwd(lg, lb):
        return pk.fused_cross_entropy(lg, lb, use_pallas=True)

    def bwd(lg, lb):
        return jax.grad(lambda lg: fwd(lg, lb).sum())(lg)

    return (bwd if grad else fwd), (logits, labels)


def _flash(grad):
    # llama_350m's training attention: [B, H, S, D] causal.  On the CPU
    # backend the dispatcher would take its reference branch, so the
    # test steers it (monkeypatched default_backend) — see the case.
    qkv = ((4, 16, 2048, 64), BF16)

    def fwd(q, k, v):
        return attention.multihead_attention_kernel(q, k, v, causal=True)

    def bwd(q, k, v):
        return jax.grad(
            lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    return (bwd if grad else fwd), (qkv, qkv, qkv)


def _paged(heads, kvh, hd, bs, q_len, int8, gather=False):
    lanes, cache_len = 8, 2048
    n_blk = cache_len // bs
    nb = 1 + lanes * n_blk
    pool = ((nb, bs, kvh * hd), jnp.int8 if int8 else BF16)
    table = ((lanes, n_blk), jnp.int32)
    if gather:
        return (lambda p, t: pk.paged_kv_gather(p, t, cache_len,
                                                use_pallas=True),
                (pool, table))
    q = ((lanes, q_len, heads, hd), BF16)
    lengths = ((lanes,), jnp.int32)
    if not int8:
        return (lambda q, k, v, t, n: pk.paged_attention(
            q, k, v, t, n, cache_len=cache_len, use_pallas=True),
            (q, pool, pool, table, lengths))
    scales = ((nb, bs, kvh), jnp.float32)
    return (lambda q, k, v, t, n, ks, vs: pk.paged_attention(
        q, k, v, t, n, k_scales=ks, v_scales=vs, cache_len=cache_len,
        use_pallas=True),
        (q, pool, pool, table, lengths, scales, scales))


def _paged_ring(heads, q_len):
    """A sliding-window layer's decode attention at Laguna-S-2.1's
    published sizes: ``heads`` query heads (72, or a full layer's 48)
    over 8 KV heads of 128, a window of 512 over a ring of 33 blocks of
    16 rows a lane (the cell's engine's at ``q_len`` 1, a block more
    than the window's at 3), a context of 17,408 rows."""
    lanes, kvh, hd, bs, ring = 8, 8, 128, 16, 33
    pool = ((1 + lanes * ring, bs, kvh * hd), BF16)
    return (lambda q, k, v, t, n: pk.paged_attention(
        q, k, v, t, n, cache_len=17408, window=512, use_pallas=True),
        (((lanes, q_len, heads, hd), BF16), pool, pool,
         ((lanes, ring), jnp.int32), ((lanes,), jnp.int32)))


def _paged_sink(window):
    """MiMo-V2.5's decode attention at its published sizes, as the
    cell's engine calls it: 64 query heads of 192 over values of 128;
    a full layer's 4 KV heads (16 queries a head, rows of 768 + 512
    values) over a context of 26,624 rows, or a window layer's 8 over
    a ring of 9 blocks of 16 rows with a sink logit a head."""
    lanes, heads, hd, vd, bs, cache_len = 8, 64, 192, 128, 16, 26624
    kvh, n_blk = (8, 9) if window else (4, cache_len // bs)
    nb = 1 + lanes * n_blk
    shapes = (((lanes, 1, heads, hd), BF16), ((nb, bs, kvh * hd), BF16),
              ((nb, bs, kvh * vd), BF16), ((lanes, n_blk), jnp.int32),
              ((lanes,), jnp.int32))
    if not window:
        return (lambda q, k, v, t, n: pk.paged_attention(
            q, k, v, t, n, cache_len=cache_len, use_pallas=True), shapes)
    return (lambda q, k, v, t, n, s: pk.paged_attention(
        q, k, v, t, n, cache_len=cache_len, window=window, sink_logits=s,
        use_pallas=True), shapes + (((heads,), jnp.float32),))


def _paged_latent(q_len):
    """GLM-4.7-Flash's absorbed decode kernel at its published sizes:
    20 heads over rows of 512 + 64 values stored 640 wide, blocks of
    16."""
    lanes, cache_len, bs, heads, row, rank = 8, 2048, 16, 20, 640, 512
    n_blk = cache_len // bs
    return (lambda q, p, t, n: pk.paged_latent_attention(
        q, p, t, n, value_dim=rank, scale=256 ** -0.5,
        cache_len=cache_len, use_pallas=True),
        (((lanes, q_len, heads, row), BF16),
         ((1 + lanes * n_blk, bs, row), BF16),
         ((lanes, n_blk), jnp.int32), ((lanes,), jnp.int32)))


def _paged_latent_ring(q_len=1):
    """dots3-note-prev's window layers' absorbed decode kernel at their
    published sizes: 64 heads over rows of 1024 + 64 values stored
    1,152 wide (nine lane tiles), a value of 1024, a window of 513 in a
    ring of 34 blocks of 16 a lane (one more under a speculative round's
    ``q_len`` of 3), a context of 16,384."""
    lanes, cache_len, bs, heads, row, rank = 32, 16384, 16, 64, 1152, 1024
    ring = 34 + (q_len > 1)
    return (lambda q, p, t, n: pk.paged_latent_attention(
        q, p, t, n, value_dim=rank, scale=256 ** -0.5,
        cache_len=cache_len, window=513, use_pallas=True),
        (((lanes, q_len, heads, row), BF16),
         ((1 + lanes * ring, bs, row), BF16),
         ((lanes, ring), jnp.int32), ((lanes,), jnp.int32)))


def _paged_latent_chosen(q_len):
    """dots3-note-prev's full layers' absorbed decode kernel over the
    2,048 rows a lane's indexer chose, the widest scores a step of the
    walk holds: 128 heads (384 query rows under a speculative round's
    ``q_len`` of 3) over rows of 512 + 64 values stored 640 wide."""
    lanes, cache_len, bs, heads, row, rank = 32, 2048, 16, 128, 640, 512
    n_blk = cache_len // bs
    return (lambda q, p, t, n: pk.paged_latent_attention(
        q, p, t, n, value_dim=rank, scale=192 ** -0.5,
        cache_len=cache_len, use_pallas=True),
        (((lanes, q_len, heads, row), BF16),
         ((1 + lanes * n_blk, bs, row), BF16),
         ((lanes, n_blk), jnp.int32), ((lanes,), jnp.int32)))


def _paged_index(q_len):
    """DeepSeek-V3.2's index scores at their published sizes through a
    lane's table: 64 heads of 128 against one key of 128 a row, blocks
    of 16, a cache of 32768 rows."""
    lanes, cache_len, bs, heads, dim = 4, 32768, 16, 64, 128
    n_blk = cache_len // bs
    return (lambda q, w, p, t, n: pk.paged_index_scores(
        q, w, p, t, n, cache_len=cache_len, use_pallas=True),
        (((lanes, q_len, heads, dim), BF16),
         ((lanes, q_len, heads), jnp.float32),
         ((1 + lanes * n_blk, bs, dim), BF16),
         ((lanes, n_blk), jnp.int32), ((lanes,), jnp.int32)))


def _delta_step():
    """Ling-3.0-flash's state step at the cell's slot grid: 64 lanes of
    32 heads, each a 128 x 128 float32 state read and written in place
    (2 MB a lane in, 2 MB out, twice for the double buffers)."""
    lanes, heads, d = 64, 32, 128
    row = ((lanes, heads, d), jnp.float32)
    return (lambda s, q, k, v, g, b: pk.delta_state_step(
        s, q, k, v, g, b, use_pallas=True),
        (((lanes, heads, d, d), jnp.float32), row, row, row, row,
         ((lanes, heads), jnp.float32)))


# (heads, kv_heads, head_dim, block_size): llama_350m's layout at the
# engine's default block size, qwen25_7b's GQA layout, a full layer of
# Laguna-S-2.1 (6 queries a KV head), and the widest rows a preset
# ships, where a step of the walk holds fewest (``pk._paged_fold``):
# llama2_7b's, gemma_7b's and llama2_13b's MHA rows of 4,096 to 5,120
# keys and as many values.
_LAYOUTS = {"h16kv16d64": (16, 16, 64, 16), "h28kv4d128": (28, 4, 128, 32),
            "h48kv8d128": (48, 8, 128, 16), "h32kv32d128": (32, 32, 128, 16),
            "h16kv16d256": (16, 16, 256, 16),
            "h40kv40d128": (40, 40, 128, 16)}

CASES = {
    "rms_norm-fwd": lambda: _rms(False),
    "rms_norm-grad": lambda: _rms(True),
    "fused_ce-v32000-fwd": lambda: _ce(32_000, False),
    "fused_ce-v32000-grad": lambda: _ce(32_000, True),
    "fused_ce-v152064-fwd": lambda: _ce(152_064, False),
    "fused_ce-v152064-grad": lambda: _ce(152_064, True),
    "flash-fwd": lambda: _flash(False),
    "flash-grad": lambda: _flash(True),
}
CASES["delta_state_step-l64h32d128"] = _delta_step
CASES["paged_attn-h64kv4k192v128"] = lambda: _paged_sink(None)
CASES["paged_ring-h64kv8k192v128-w128-sink"] = lambda: _paged_sink(128)
CASES["paged_latent_ring-h64r1088-w513"] = _paged_latent_ring
CASES["paged_latent_ring-h64r1088-w513-q3"] = lambda: _paged_latent_ring(3)
for _q in (1, 3):
    for _h in (48, 72):
        CASES[f"paged_ring-h{_h}kv8d128-w512-q{_q}"] = (
            lambda h=_h, q=_q: _paged_ring(h, q))
    CASES[f"paged_latent-h20r576-q{_q}"] = lambda q=_q: _paged_latent(q)
    CASES[f"paged_latent-h128r576-chosen2048-q{_q}"] = (
        lambda q=_q: _paged_latent_chosen(q))
    CASES[f"paged_index-h64d128-q{_q}"] = lambda q=_q: _paged_index(q)
for _name, (_h, _kvh, _hd, _bs) in _LAYOUTS.items():
    CASES[f"paged_gather-{_name}"] = (
        lambda a=(_h, _kvh, _hd, _bs): _paged(*a, 1, False, gather=True))
    for _kv in ("bf16", "int8"):
        for _q in (1, 3):
            CASES[f"paged_attn-{_name}-{_kv}-q{_q}"] = (
                lambda a=(_h, _kvh, _hd, _bs), q=_q, i8=(_kv == "int8"):
                _paged(*a, q, i8))


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, v5e, monkeypatch):
    # The flash dispatcher asks default_backend(); everything else is
    # steered by use_pallas=True.  Steering lives here, in the test.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, shapes = CASES[case]()
    one_chip = SingleDeviceSharding(v5e[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _prefix_dense():
    """A 1024-token prefill piece of qwen25_7b over its batch-1 cache of
    4096 rows: 28 heads of 128 on 4 KV heads, repeated a tile at a
    time."""
    heads, kvh, hd, cache_len = 28, 4, 128, 4096
    kv = ((1, cache_len, kvh, hd), BF16)

    def fn(q, k, v, start):
        return attention.prefix_attention(
            q, (k, v), start,
            lambda rows: [jnp.repeat(r, heads // kvh, axis=2).transpose(
                0, 2, 1, 3) for r in rows])

    return fn, (((1, heads, 1024, hd), BF16), kv, kv,
                ((1,), jnp.int32)), (heads, 1024, cache_len)


def _prefix_latent():
    """A 1024-token piece of GLM-4.7-Flash over its cache of 8192 rows
    of 512 + 64 values stored 640 wide: 20 heads of 192 + 64 key and
    256 value dims, up-projected a tile at a time."""
    heads, rank, nope, rope, vd, cache_len = 20, 512, 192, 64, 256, 8192

    def fn(q, rows, w, start):
        def kv_of(rows):
            kv = jnp.einsum("btc,chd->bthd", rows[..., :rank], w)
            k_r = jnp.broadcast_to(rows[..., None, rank:rank + rope],
                                   (*rows.shape[:2], heads, rope))
            k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
            return [t.transpose(0, 2, 1, 3) for t in (k, kv[..., nope:])]

        return attention.prefix_attention(q, rows, start, kv_of)

    return fn, (((1, heads, 1024, nope + rope), BF16),
                ((1, cache_len, 640), BF16),
                ((rank, heads, nope + vd), BF16),
                ((1,), jnp.int32)), (heads, 1024, cache_len)


@pytest.mark.parametrize("case", [_prefix_dense, _prefix_latent],
                         ids=["qwen25_7b-1024x4096", "glm47-1024x8192"])
def test_prefix_attention_compiles_to_a_loop_with_tile_sized_scores(
        case, v5e):
    """The prefill pieces' attention at both cells' widths: one program
    with a loop in it (a traced trip count, no signature a prompt
    length) whose temporaries are below the float32 scores of the whole
    cache, [heads, q, cache_len], that the masked expression holds: no
    value of the program has a query's scores over every row."""
    fn, shapes, (heads, q_len, cache_len) = case()
    one_chip = SingleDeviceSharding(v5e[0])
    compiled = jax.jit(fn).lower(*(
        jax.ShapeDtypeStruct(s, d, sharding=one_chip)
        for s, d in shapes)).compile()
    text = compiled.as_text()
    assert " while(" in text
    assert (compiled.memory_analysis().temp_size_in_bytes
            < heads * q_len * cache_len * 4)
    assert f"{q_len},{cache_len}]" not in text
    assert f"{heads},{q_len},{attention.PREFIX_TILE}]" in text


def test_delta_scan_compiles_to_a_loop_over_chunks(v5e):
    """``delta_rule_scan`` at ``ling3-flash-1chip``'s largest call (4096
    rows of 32 heads of 128, bf16 operands): one loop over the call's
    256 chunks of 16 rows whose carry is the float32 state, and what it
    makes for all chunks at once stays under 1.5 GB."""
    one_chip = SingleDeviceSharding(v5e[0])
    t, h, d = 4096, 32, 128
    rows = ((1, t, h, d), BF16)
    shapes = (rows, rows, rows, ((1, t, h, d), jnp.float32),
              ((1, t, h), jnp.float32), ((1, h, d, d), jnp.float32))
    compiled = jax.jit(attention.delta_rule_scan).lower(*(
        jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
        for s, dt in shapes)).compile()
    text = compiled.as_text()
    assert " while(" in text and f"f32[1,{h},{d},{d}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize("case", ["rms_norm-grad", "fused_ce-v32000-grad",
                                  "flash-grad"])
def test_kernel_partitions_over_a_2x2_mesh(case, v5e, monkeypatch):
    """GSPMD refuses to partition a Mosaic kernel ("wrap the call in a
    shard_map"): under a mesh the kernels run per shard
    (``pallas_kernels.per_shard``).  data=2 × tensor=2 over the four
    described chips, operands sharded the way the trainer shards them."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, shapes = CASES[case]()
    mesh = Mesh(np.asarray(v5e).reshape(1, 2, 1, 1, 1, 2), AXES)
    heads_dim = 1 if case.startswith("flash") else None
    args = []
    for s, d in shapes:
        spec = (pk.activation_spec(mesh, s, heads_dim=heads_dim)
                if len(s) > 1 else P(None))
        args.append(jax.ShapeDtypeStruct(
            s, d, sharding=NamedSharding(mesh, spec)))
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _benchmark_config(name):
    """``benchmark/configs/<name>.json``: a cell's configuration as it
    is run."""
    import json

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


_EXPERT_CONFIGS = ("glm47-flash-1chip", "deepseek-v32exp-1chip",
                   "laguna-s21-1chip", "ling3-flash-1chip")


@pytest.mark.parametrize("rows_of", ["step", "piece"])
@pytest.mark.parametrize("config", _EXPERT_CONFIGS)
def test_routed_rows_go_to_expert_order_and_back_in_one_pass_each(
        config, rows_of, v5e):
    """``_routed_ffn_rows`` at each expert configuration of the
    benchmark (choices, width, experts held and their width from
    ``benchmark/configs/``), over a decode step's rows (the engine's
    slots) and a 1024-token prefill piece's.  The TPU compiler takes
    the tiles ``_gmm_tiling`` chose (a slice that outgrows VMEM is
    refused here, before any chip call) and the program holds its three
    ``gmm`` kernels.  The float32 rows they return are never laid out
    again by token (no ``reshape`` or ``copy`` to ``[tokens, k, d]`` or
    ``[k, tokens, d]``: at Laguna's piece 126 MB read and 201 written
    when the pairs lay token-major), no ``select`` pass follows the
    sort's gather (its indices are promised in bounds), and the un-sort
    and gate-combine is the one kernel named ``moe_combine``."""
    import dataclasses
    import re

    from tensorflow_train_distributed_tpu.models import moe

    cfg_file = _benchmark_config(config)
    cfg = dataclasses.replace(
        moe.MOE_PRESETS[cfg_file["program"]["preset"]],
        **cfg_file["program"]["replace"])
    t = cfg_file["engine"]["slots"] if rows_of == "step" else 1024
    k, d, f, experts = cfg.top_k, cfg.d_model, cfg.ffn_size, cfg.num_experts
    held = cfg.experts_held or experts
    m_pad = -(-t * k // 128) * 128
    one_chip = SingleDeviceSharding(v5e[0])

    def rows(flat, top_e, gate_w, wi_gate, wi_up, wo):
        return moe._routed_ffn_rows(
            flat, top_e, gate_w, experts, wi_gate, wi_up, wo, dtype=BF16,
            interpret=False,
            group_offset=None if cfg.experts_held is None
            else cfg.experts_offset)

    text = jax.jit(rows).lower(*(
        jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
            ((t, d), BF16), ((t, k), jnp.int32), ((t, k), jnp.float32),
            ((held, d, f), BF16), ((held, d, f), BF16),
            ((held, f, d), BF16)))).compile().as_text()
    made = re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = (\w+\[[\d,]*\])\S* ([\w\-]+)\(",
        text, re.M)
    assert (f"f32[{m_pad},{d}]", "custom-call") in made     # gmm's rows
    relaid = {f"f32[{t},{k},{d}]", f"f32[{k},{t},{d}]"}
    assert not [m for m in made
                if m[0] in relaid and m[1] in ("reshape", "copy")]
    assert (f"bf16[{m_pad},{d}]", "select") not in made
    kernels = [name.split(".")[0] for name in _kernels(text)]
    assert kernels.count("gmm") == 3 and "moe_combine" in kernels


@pytest.fixture(scope="module")
def decode_program(v5e):
    """The benchmark's decode program (``qwen25-7b-1chip``: 32 lanes,
    256 blocks of 16 a lane, 28/4 heads of 128, 12 layers), compiled
    once for the described chip."""
    import dataclasses

    import flax.linen as nn

    from tensorflow_train_distributed_tpu.models import llama
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        cfg_file = _benchmark_config("qwen25-7b-1chip")
        cfg = dataclasses.replace(
            llama.LLAMA_PRESETS[cfg_file["program"]["preset"]],
            **cfg_file["program"]["replace"])
        one_chip = SingleDeviceSharding(v5e[0])

        def described(tree, dtype=None):
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, dtype or s.dtype,
                                               sharding=one_chip), tree)

        params = described(nn.meta.unbox(jax.eval_shape(
            lambda: llama.LlamaModel(cfg).init(
                jax.random.key(0),
                jnp.zeros((1, 8), jnp.int32))))["params"], BF16)
        kw = {k: v for k, v in cfg_file["engine"].items()
              if k != "max_queue"}
        eng = ServingEngine(cfg, params, cast_params=False, **kw)
        assert eng.fused_attn
        assert (eng.slots, eng._kv_nblk_lane, eng.kv_block_size) == (
            32, 256, 16)
        cache = described(eng._cache_struct(eng.slots, grid=True))
        lanes = {d: jax.ShapeDtypeStruct((eng.slots,), d, sharding=one_chip)
                 for d in (jnp.int32, jnp.uint32)}
        program = ServingEngine._decode_chunk
        while not hasattr(program, "lower"):   # past the compile sanitizer
            program = program.__wrapped__
        return program.lower(eng, eng._variables, cache, lanes[jnp.int32],
                             lanes[jnp.uint32],
                             lanes[jnp.int32]).compile()
    finally:
        monkeypatch.undo()


def _kernels(text):
    """Names of a compiled program's Mosaic kernels.  A device event is
    named by its instruction, without the metadata: the name has to say
    which kernel it is."""
    import re

    return re.findall(
        r'^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*'
        r'custom_call_target="tpu_custom_call"', text, re.M)


def test_decode_program_carries_its_scope_names(decode_program):
    """The benchmark's decode program, at its real width, compiled for
    the described chip: every region of the device-scope contract is in
    the operations' metadata (``benchmark/harness/scopes.py`` reads a
    trace by it), and the attention kernel is still a
    ``tpu_custom_call`` that the method it is called from names
    (``paged_attn_roofline.decode`` finds its events so: the kernel
    takes no ``name=`` of its own, which would replace that name)."""
    import re

    text = decode_program.as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("embed", "norm", "attn/qkv", "attn/out", "mlp", "head",
                  "sample", "kv_pool/write"):
        assert any(f"/{scope}/" in name for name in op_names), scope
    kernels = _kernels(text)
    assert any("_paged_decode_step" in name for name in kernels), kernels
    assert any(name.startswith("rms_norm_fwd") for name in kernels)


def test_decode_program_holds_one_attention_kernel_and_no_more_memory(
        decode_program):
    """The length-bounded walk is ONE ``tpu_custom_call`` in the layer
    scan's body, named by the method that calls it (a second one would
    be counted into ``paged_attn_roofline.decode``'s mean time a call),
    and the program moves no pool about: with the pools carried through
    the layer scan, written in place and stored as the kernel reads
    them, the temporaries are 0.33 GiB beside 10.24 GiB of arguments
    (compile, PR 29; 4.37 GiB before it, two of them whole copies of
    the pools), and 12 layers fit a 15.75 GiB chip with 5 GiB to
    spare."""
    paged = [k for k in _kernels(decode_program.as_text())
             if "_paged_decode_step" in k]
    assert len(paged) == 1, paged
    mem = decode_program.memory_analysis()
    gib = 1 << 30
    assert mem.temp_size_in_bytes <= 0.5 * gib, mem.temp_size_in_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            <= 10.8 * gib), mem


def test_decode_program_returns_its_cache_in_the_buffers_it_was_given(
        decode_program):
    """The donated ``cache`` argument IS the returned cache: every leaf
    of it, both pools among them, is an input-output alias of the
    compiled program, so a chunk writes its rows into the pools where
    they lie and allocates no second pool for its result."""
    import re

    text = decode_program.as_text()
    header = text[:text.index("entry_computation_layout")]
    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    layout = text[text.index("entry_computation_layout={(") + 27:]
    params = re.findall(r"(\w+\[[\d,]*\])\{[^}]*\}",
                        layout[:layout.index(")->")])
    pools = [i for i, p in enumerate(params)
             if p == "bf16[12,8193,16,512]"]
    assert len(pools) == 2, params                  # key_pool, value_pool
    assert set(pools) <= aliased, (pools, aliased)
    mem = decode_program.memory_analysis()
    pool_bytes = 2 * 12 * 8193 * 16 * 512 * 2
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    # cache leaves: two pools, the block tables, the indices
    assert len(aliased) == 4, header


# -- the prefill pieces' walk as one kernel ---------------------------------

# heads, kv_heads, key head, value head, window, sink, cache rows: the
# three families whose pieces walk plain K/V rows, at the cells' caches.
_FLASH_KINDS = {
    "mimo-full-h64kv4-k192v128": (64, 4, 192, 128, None, False, 26112),
    "mimo-window128-h64kv8-k192v128-sink": (64, 8, 192, 128, 128, True,
                                            26112),
    "laguna-full-h48kv8-d128": (48, 8, 128, 128, None, False, 17408),
    "laguna-window512-h72kv8-d128": (72, 8, 128, 128, 512, False, 17408),
    "qwen-h28kv4-d128": (28, 4, 128, 128, None, False, 4096),
}


@pytest.mark.parametrize("q_len", [1024, 4096])
@pytest.mark.parametrize("kind", sorted(_FLASH_KINDS))
def test_prefix_flash_attention_compiles_for_v5e(kind, q_len, v5e):
    """A call of one piece and of four at the published shapes: ONE
    ``tpu_custom_call``, named ``prefix_flash_attention`` (the trace
    finds it by that, and the ``_paged_decode_step`` readers do not),
    and no loop round it (each query block walks its own tiles inside
    the kernel's grid)."""
    heads, kvh, hd, vd, window, sink, cache_len = _FLASH_KINDS[kind]
    shapes = [((1, heads, q_len, hd), BF16), ((1, cache_len, kvh, hd), BF16),
              ((1, cache_len, kvh, vd), BF16), ((1,), jnp.int32)]
    if sink:
        shapes.append(((heads,), jnp.float32))

    def fn(q, k, v, start, sinks=None):
        return pk.prefix_flash_attention(q, k, v, start, window=window,
                                         sink_logits=sinks)

    one_chip = SingleDeviceSharding(v5e[0])
    text = jax.jit(fn).lower(*(
        jax.ShapeDtypeStruct(s, d, sharding=one_chip)
        for s, d in shapes)).compile().as_text()
    kernels = _kernels(text)
    assert len(kernels) == 1 and kernels[0].startswith(
        "prefix_flash_attention"), kernels
    assert "_paged_decode_step" not in kernels[0]
    assert " while(" not in text


@pytest.mark.parametrize("family, moved", [
    ("glm_lite_tiny", False), ("deepseek_v32_tiny", False),
    ("ling_tiny", False), ("laguna_tiny", True)])
def test_a_latent_familys_piece_program_never_asks_for_the_kernel(
        family, moved, monkeypatch):
    """A piece program lowered with the kernel's rule saying yes to
    every walk it is asked about is, for the families whose caches
    hold latent rows, the text it is with the rule saying no: their
    walk (``LatentAttention._linear_step``) is ``prefix_attention``'s
    and asks nothing.  A family of plain K/V rows moves."""
    from benchmark.harness import weights
    from tensorflow_train_distributed_tpu.models import moe
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    cfg = moe.MOE_PRESETS[family]
    boxed = jax.eval_shape(lambda: moe.MoeLmModel(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    params = weights.make_params(weights.plain_shapes(boxed)["params"], 7,
                                 jnp.float32)
    program = ServingEngine._prefill_piece
    while not hasattr(program, "lower"):       # past the compile sanitizer
        program = program.__wrapped__

    def lowered(engages):
        monkeypatch.setattr(pk, "prefix_flash_engages",
                            lambda q_len, k, v: engages and q_len > 1)
        monkeypatch.setattr(pk, "fused_attn_interpret", lambda: True)
        monkeypatch.setattr(pk, "PREFIX_FLASH_BLOCK_Q", 8)
        monkeypatch.setattr(pk, "PREFIX_FLASH_TILE", 16)
        eng = ServingEngine(cfg, params, slots=2, chunk=2, cache_len=64,
                            kv_block_size=8, prefill_chunk=16)
        return program.lower(
            eng, eng._variables, eng._cache_struct(1),
            jax.ShapeDtypeStruct((1, 16), jnp.int32), jnp.int32(3),
            jnp.uint32(0), jnp.int32(0)).as_text()

    assert (lowered(True) != lowered(False)) is moved


# -- the same walk over latent rows -----------------------------------------

# heads, nope, rope, value head, keep, cache rows, queries of a call: the
# three latent families at their cells' caches and calls (one piece, and
# the most pieces a step's budget makes a call of).
_LATENT_KINDS = {
    "deepseek-h128-k128r64v128-keep": (128, 128, 64, 128, True, 32768,
                                       (1024,)),
    "glm-h20-k192r64v256": (20, 192, 64, 256, False, 9216, (1024, 2048)),
    "ling-h32-k128r64v128": (32, 128, 64, 128, False, 19456, (1024, 4096)),
}


@pytest.mark.parametrize("kind, q_len", [
    (k, q) for k in sorted(_LATENT_KINDS) for q in _LATENT_KINDS[k][-1]])
def test_prefix_flash_latent_compiles_for_v5e(kind, q_len, v5e):
    """At the published shapes (a latent of 512 in rows stored 640
    wide): ONE ``tpu_custom_call``, named ``prefix_flash_latent`` (the
    trace tells it from ``prefix_flash_attention`` by that), no loop
    round it, and its blocks inside the fast memory it asks for (a
    VMEM overrun is the compiler's error).  GLM's shape compiles too,
    though the rule leaves it to the XLA walk."""
    heads, nope, rope, vd, keep, cache_len, _ = _LATENT_KINDS[kind]
    shapes = [((1, heads, q_len, nope), BF16), ((1, heads, q_len, rope), BF16),
              ((1, cache_len, 640), BF16), ((512, heads, nope + vd), BF16),
              ((1,), jnp.int32)]
    if keep:
        shapes.append(((1, q_len, cache_len), jnp.bool_))

    def fn(q_nope, q_rope, rows, kv_b, start, keep=None):
        return pk.prefix_flash_latent(
            q_nope, q_rope, rows, kv_b, start, keep=keep,
            softmax_scale=(nope + rope) ** -0.5)

    one_chip = SingleDeviceSharding(v5e[0])
    text = jax.jit(fn).lower(*(
        jax.ShapeDtypeStruct(s, d, sharding=one_chip)
        for s, d in shapes)).compile().as_text()
    kernels = _kernels(text)
    assert len(kernels) == 1 and kernels[0].startswith(
        "prefix_flash_latent"), kernels
    assert not kernels[0].startswith("prefix_flash_attention")
    assert " while(" not in text
