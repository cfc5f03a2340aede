"""The plain reference against the program at a tiny size on the CPU:
logits with and without the window and the q/k/v bias, its parts by
hand, and the gap it reads for served tokens; the seeded weights it is
given; and the operation names it is reported beside."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import trace, weights
from benchmark.references import decoder
from cellkit import CASES, tiny_setup


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_logits_match_the_programs_forward(case):
    llama, cfg, file_cfg, params = tiny_setup(case)
    tokens = np.random.default_rng(0).integers(3, 256, 40)
    with jax.default_matmul_precision("highest"):
        want = llama.LlamaModel(cfg).apply(
            {"params": params}, jnp.asarray(tokens)[None])[0]
    got = decoder.logits_at(params, file_cfg, tokens, np.arange(40))
    # float32 on both sides, the same mathematics in another order
    np.testing.assert_allclose(got, want, atol=2e-4)
    if CASES[case]["sliding_window"]:
        # the window must matter at this length, or the case is idle
        full = decoder.logits_at(params, dict(file_cfg, sliding_window=None),
                                 tokens, np.arange(40))
        assert float(jnp.abs(full - got).max()) > 1e-2


def test_rms_norm_and_rope_by_hand():
    x = jnp.asarray([[3.0, 4.0, 0.0, 0.0]])
    # mean square 6.25 -> x / 2.5, times the scale
    np.testing.assert_allclose(
        decoder.rms_norm(x, jnp.asarray([1.0, 2.0, 1.0, 1.0]), 0.0),
        [[1.2, 3.2, 0.0, 0.0]], rtol=1e-6)
    # head_dim 2: one frequency of 1; position p rotates (1, 0) by p rad
    q = jnp.asarray([[[1.0, 0.0]], [[1.0, 0.0]]])
    out = decoder.rope(q, jnp.asarray([0, 1]), 10000.0)
    np.testing.assert_allclose(out[0, 0], [1.0, 0.0], atol=1e-6)
    np.testing.assert_allclose(out[1, 0], [np.cos(1.0), np.sin(1.0)],
                               rtol=1e-6)


def test_attention_masks_the_future_and_what_left_the_window():
    s, hd = 6, 4
    q = jnp.zeros((s, 2, hd))              # zero scores: uniform weights
    k = jnp.zeros((s, 1, hd))
    v = jnp.arange(s, dtype=jnp.float32)[:, None, None] * jnp.ones((1, 1, hd))
    full = decoder.attention(q, k, v)
    # query i averages values 0..i; both query heads read the one KV head
    np.testing.assert_allclose(full[:, 0, 0], [0, 0.5, 1, 1.5, 2, 2.5],
                               rtol=1e-6)
    np.testing.assert_allclose(full[:, 1, 0], full[:, 0, 0])
    win = decoder.attention(q, k, v, window=2)
    # ... with a window of 2, values i-1 and i only
    np.testing.assert_allclose(win[:, 0, 0], [0, 0.5, 1.5, 2.5, 3.5, 4.5],
                               rtol=1e-6)


def test_served_gaps_are_zero_for_the_references_own_choice():
    _, _, file_cfg, params = tiny_setup("bias")
    prompt = list(np.random.default_rng(3).integers(3, 256, 12))
    served = []
    for _ in range(5):                     # greedy by the reference
        lg = decoder.logits_at(params, file_cfg, prompt + served,
                               [len(prompt) + len(served) - 1])
        served.append(int(jnp.argmax(lg[0])))
    gaps = decoder.served_gaps(params, file_cfg, prompt, served,
                               pad_to=512, rows_to=8)
    assert gaps.shape == (5,) and float(np.abs(gaps).max()) < 1e-5
    wrong = list(served)
    wrong[2] = (wrong[2] + 1) % 256
    gaps = decoder.served_gaps(params, file_cfg, prompt, wrong)
    assert gaps[2] > 1e-3 and float(np.abs(gaps[:2]).max()) < 1e-5


def test_weights_are_a_function_of_the_seed_alone():
    _, cfg, _, params = tiny_setup("plain")
    from benchmark.harness import program

    again = weights.make_params(program.param_shapes(cfg), 2 ** 33 + 7,
                                jnp.float32)
    other = weights.make_params(program.param_shapes(cfg), 2 ** 33 + 8,
                                jnp.float32)
    same = jax.tree.map(lambda a, b: bool((a == b).all()), params, again)
    assert all(jax.tree.leaves(same))
    diff = [bool((a != b).any()) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(other))
        if bool((a != 1).any())]       # norm scales are all ones
    assert all(diff)
    head = params["lm_head"]["kernel"]
    assert float(head.std()) == pytest.approx(1 / 8, rel=0.05)
    assert float(params["final_norm"]["scale"].min()) == 1.0


def test_long_hlo_lines_are_shortened_for_the_breakdown():
    line = ("%fusion.139 = bf16[32,18944]{1,0:T(8,128)(2,1)S(1)} fusion("
            "bf16[32,3584]{1,0} %pallas_call.3), kind=kOutput")
    assert trace.short_op_name(line) == "%fusion.139 fusion bf16[32,18944]"
    call = ("%attention._paged_decode_step.9 = bf16[32,28,128]{2,1,0} "
            "custom-call(s32[32,256]{1,0} %copy-done.1), "
            "custom_call_target=\"tpu_custom_call\"")
    assert trace.short_op_name(call) == \
        "%attention._paged_decode_step.9 custom-call bf16[32,28,128]"
    assert trace.short_op_name("%fusion.1") == "%fusion.1"
    assert trace.short_op_name("$serving.py:1 x") == "$serving.py:1 x"
