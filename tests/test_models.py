"""Model zoo tests: every reference config family trains on the CPU mesh.

Tiny variants exercise the full code path (attention, BN, scan, remat);
param-count checks pin the full-size architectures without compiling them.
"""

import pytest

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensorflow_train_distributed_tpu.data import (
    DataConfig, HostDataLoader, get_dataset,
)
from tensorflow_train_distributed_tpu.models import registry
from tensorflow_train_distributed_tpu.models.bert import BERT_PRESETS, BertEncoder
from tensorflow_train_distributed_tpu.models.lenet import LeNet
from tensorflow_train_distributed_tpu.models.llama import (
    LLAMA_PRESETS, LlamaModel,
)
from tensorflow_train_distributed_tpu.models.resnet import (
    RESNET_PRESETS, ResNet,
)
from tensorflow_train_distributed_tpu.models.transformer import (
    TRANSFORMER_PRESETS, Seq2SeqTransformer,
)
from tensorflow_train_distributed_tpu.training import Trainer, TrainerConfig
from tensorflow_train_distributed_tpu.training.callbacks import History


def _param_count(model, *args):
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), *args))
    return sum(np.prod(x.shape) for x in jax.tree.leaves(shapes))


class TestArchitectures:
    def test_lenet_param_count(self):
        # Classic LeNet-5 on 28x28: 61,706 params.
        n = _param_count(LeNet(), jnp.zeros((1, 28, 28, 1)))
        assert n == 61_706

    def test_resnet50_param_count(self):
        n = _param_count(ResNet(RESNET_PRESETS["resnet50"]),
                         jnp.zeros((1, 224, 224, 3)))
        assert abs(n - 25.56e6) < 0.1e6, n  # ResNet-50: ~25.56M

    def test_bert_base_param_count(self):
        n = _param_count(BertEncoder(BERT_PRESETS["bert_base"]),
                         jnp.zeros((1, 16), jnp.int32))
        assert abs(n - 110e6) < 3e6, n  # BERT-base: ~110M

    def test_transformer_big_param_count(self):
        n = _param_count(
            Seq2SeqTransformer(TRANSFORMER_PRESETS["transformer_big"]),
            jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 8), jnp.int32))
        assert abs(n - 210e6) < 15e6, n  # Transformer-big: ~210M

    def test_llama2_7b_param_count(self):
        n = _param_count(LlamaModel(LLAMA_PRESETS["llama2_7b"]),
                         jnp.zeros((1, 8), jnp.int32))
        assert abs(n - 6.74e9) < 0.1e9, n  # Llama-2-7B: 6.74B

    def test_qwen_presets_carry_checkpoint_norm_epsilon(self):
        """Qwen checkpoints use rms_norm_eps=1e-6; a preset left at the
        family default 1e-5 imports into silently-different logits on
        the config=task_cfg CLI route (ADVICE round 5)."""
        from tensorflow_train_distributed_tpu.models.moe import (
            MOE_PRESETS,
        )

        assert LLAMA_PRESETS["qwen25_7b"].rms_epsilon == 1e-6
        assert MOE_PRESETS["qwen15_moe_a27b"].rms_epsilon == 1e-6

    def test_llama_scan_matches_loop_params(self):
        loop_cfg = LLAMA_PRESETS["llama_tiny"]
        scan_cfg = LLAMA_PRESETS["llama_tiny_scan"]
        n_loop = _param_count(LlamaModel(loop_cfg),
                              jnp.zeros((1, 8), jnp.int32))
        n_scan = _param_count(LlamaModel(scan_cfg),
                              jnp.zeros((1, 8), jnp.int32))
        assert n_loop == n_scan

    @pytest.mark.parametrize("policy", ["dots", "no_ffn"])
    def test_llama_remat_policy_matches_full(self, policy):
        """'dots'/'no_ffn' remat save more, recompute less — same math:
        loss AND gradients must match full remat exactly."""
        import dataclasses

        import jax
        import numpy as np

        from tensorflow_train_distributed_tpu.models.llama import (
            CausalLmTask,
        )

        rng = np.random.default_rng(0)
        batch = {
            "tokens": rng.integers(0, 256, (2, 32)).astype(np.int32),
            "targets": rng.integers(0, 256, (2, 32)).astype(np.int32),
        }

        def loss_and_grad(pol):
            cfg = dataclasses.replace(LLAMA_PRESETS["llama_tiny_scan"],
                                      remat_policy=pol)
            task = CausalLmTask(cfg)
            variables = task.init_variables(jax.random.key(0), batch)

            def loss(params):
                value, _ = task.loss_fn(params, {}, batch,
                                        jax.random.key(1), True)
                return value

            return jax.value_and_grad(loss)(variables["params"])

        (l_full, g_full) = loss_and_grad("full")
        (l_p, g_p) = loss_and_grad(policy)
        np.testing.assert_allclose(float(l_full), float(l_p), rtol=1e-6)
        # Gradients: recompute-vs-saved changes f32 reassociation, so
        # element-wise rounding differs; bound the relative tree error.
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                a, b, rtol=5e-3, atol=1e-5),
            g_full, g_p)

    def test_llama_remat_policy_unknown_rejected(self):
        import dataclasses

        import pytest as _pytest

        from tensorflow_train_distributed_tpu.models.llama import (
            _checkpoint_policy,
        )

        cfg = dataclasses.replace(LLAMA_PRESETS["llama_tiny_scan"],
                                  remat_policy="nope")
        with _pytest.raises(ValueError, match="remat_policy"):
            _checkpoint_policy(cfg)


def _train_config(name, steps=12, mesh=None, **overrides):
    entry = registry.get_entry(name)
    entry.update(overrides)
    ds = get_dataset(entry["dataset"], num_examples=256,
                     **entry["dataset_kwargs"])
    loader = HostDataLoader(
        ds, DataConfig(global_batch_size=entry["global_batch_size"]))
    trainer = Trainer(
        entry["task_factory"](),
        optax.adam(entry["learning_rate"]),
        mesh,
        config=TrainerConfig(log_every=4),
        callbacks=[hist := History()],
    )
    state = trainer.fit(iter(loader), steps=steps)
    return state, hist


@pytest.mark.slow  # full fit loops per config family
class TestTraining:
    def test_mnist_lenet_converges(self, mesh8):
        state, hist = _train_config("mnist", steps=30, mesh=mesh8,
                                    global_batch_size=64)
        assert hist.history["loss"][-1] < hist.history["loss"][0] * 0.5
        assert hist.history["accuracy"][-1] > 0.5

    def test_resnet_tiny_trains_with_bn(self, mesh8):
        state, hist = _train_config("resnet_tiny", steps=8, mesh=mesh8,
                                    global_batch_size=16)
        # batch_stats updated (BN running means move off zero).
        bn_means = [np.asarray(x) for path, x in
                    jax.tree_util.tree_leaves_with_path(
                        state.model_state["batch_stats"])
                    if path[-1].key == "mean"]
        assert any(np.abs(m).max() > 0 for m in bn_means)
        assert hist.history["loss"][-1] < hist.history["loss"][0]

    def test_resnet_space_to_depth_equivalence(self):
        """The s2d stem is the SAME function: transforming a trained 7x7
        stem kernel with stem_kernel_to_s2d and feeding s2d input must
        reproduce the baseline logits exactly (MLPerf s2d trick)."""
        import dataclasses

        import flax.linen as nn
        import jax.numpy as jnp

        from tensorflow_train_distributed_tpu.models import resnet

        cfg = dataclasses.replace(resnet.RESNET_PRESETS["resnet_tiny"],
                                  space_to_depth=False)
        cfg_s2d = dataclasses.replace(cfg, space_to_depth=True)
        model, model_s2d = resnet.ResNet(cfg), resnet.ResNet(cfg_s2d)
        x = jax.random.normal(jax.random.key(0), (2, 32, 32, 3),
                              jnp.float32)
        variables = nn.unbox(model.init(jax.random.key(1), x, train=False))
        params = variables["params"]
        params_s2d = jax.tree.map(lambda p: p, params)
        params_s2d["stem_conv"] = {
            "kernel": resnet.stem_kernel_to_s2d(
                params["stem_conv"]["kernel"])
        }
        ref = model.apply({"params": params, **{
            k: v for k, v in variables.items() if k != "params"}}, x,
            train=False)
        out = model_s2d.apply({"params": params_s2d, **{
            k: v for k, v in variables.items() if k != "params"}},
            resnet.space_to_depth(x), train=False)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=1e-5)

    def test_resnet_s2d_dataset_layout_matches_model(self):
        """Host-side dataset s2d must equal the model's on-the-fly s2d."""
        from tensorflow_train_distributed_tpu.data.datasets import (
            SyntheticImageNet,
        )
        from tensorflow_train_distributed_tpu.models import resnet

        raw = SyntheticImageNet(num_examples=4, image_size=32, seed=3)
        s2d = SyntheticImageNet(num_examples=4, image_size=32, seed=3,
                                space_to_depth=True)
        img = raw[1]["image"][None]
        np.testing.assert_array_equal(
            np.asarray(resnet.space_to_depth(img))[0], s2d[1]["image"])

    def test_bert_tiny_mlm_trains(self, mesh8):
        state, hist = _train_config("bert_tiny_mlm", steps=12, mesh=mesh8)
        assert hist.history["loss"][-1] < hist.history["loss"][0]
        assert "mlm_accuracy" in hist.history

    def test_bert_mlm_val_metrics_drive_early_stopping(self, mesh8):
        """BERT MLM eval parity: held-out val_loss + val_mlm_accuracy flow
        through fit's eval loop and drive EarlyStopping — the [SPEC]
        'samples/sec + loss match' metric pair, closed end-to-end."""
        import optax

        from tensorflow_train_distributed_tpu.data import (
            DataConfig, HostDataLoader, get_dataset, train_val_split,
        )
        from tensorflow_train_distributed_tpu.models import bert
        from tensorflow_train_distributed_tpu.training import (
            EarlyStopping, History, Trainer, TrainerConfig,
        )

        src = get_dataset("mlm", num_examples=512, vocab_size=256,
                          seq_len=64)
        train_src, val_src = train_val_split(src, 0.25)
        loader = HostDataLoader(
            train_src, DataConfig(global_batch_size=32, seed=0))
        # min_delta=0.5: only the initial steep descent counts as
        # improvement, so the stop fires deterministically mid-run.
        es = EarlyStopping(monitor="val_loss", patience=2, min_delta=0.5)
        trainer = Trainer(
            bert.make_task(bert.BERT_PRESETS["bert_tiny"]),
            optax.adam(2e-3), mesh8,
            config=TrainerConfig(log_every=5),
            callbacks=[hist := History(), es])
        state = trainer.fit(
            loader, steps=300,
            eval_batches=lambda: HostDataLoader(
                val_src, DataConfig(global_batch_size=32, seed=1,
                                    num_epochs=1)),
            eval_every=10, eval_steps=4)
        assert "val_loss" in hist.history
        assert "val_mlm_accuracy" in hist.history
        # Learned on the held-out split (the stop fires only after the
        # steep descent, so the total drop exceeds min_delta).
        assert (hist.history["val_loss"][-1]
                < hist.history["val_loss"][0] - 0.5)
        # EarlyStopping actually stopped the run on the val_loss plateau,
        # and its best tracked the qualifying (>min_delta) improvements.
        assert int(state.step) < 300
        assert es.best < hist.history["val_loss"][0] - 0.5

    def test_transformer_tiny_wmt_trains(self, mesh8):
        state, hist = _train_config("transformer_tiny_wmt", steps=12,
                                    mesh=mesh8)
        assert hist.history["loss"][-1] < hist.history["loss"][0]

    def test_llama_tiny_trains_2d_mesh(self, mesh_2d):
        state, hist = _train_config("llama_tiny_sft", steps=12, mesh=mesh_2d)
        assert hist.history["loss"][-1] < hist.history["loss"][0]

    def test_llama_scan_remat_trains_and_shards(self, mesh_2d):
        from tensorflow_train_distributed_tpu.models import llama

        entry = registry.get_entry("llama_tiny_sft")
        ds = get_dataset("lm", num_examples=64, vocab_size=256, seq_len=32)
        loader = HostDataLoader(ds, DataConfig(global_batch_size=16))
        task = llama.make_task(llama.LLAMA_PRESETS["llama_tiny_scan"])
        trainer = Trainer(task, optax.adam(1e-3), mesh_2d,
                          config=TrainerConfig(log_every=4),
                          callbacks=[hist := History()])
        state = trainer.fit(iter(loader), steps=8)
        # Scanned stack: params carry leading layer axis.
        stack = state.params["layers"]["stack"]["block"]
        gate = stack["mlp"]["wi_gate"]["kernel"]
        assert gate.shape[0] == 2  # num_layers
        # mlp dim sharded over tensor axis on the 2x4 mesh.
        assert gate.addressable_shards[0].data.shape[-1] == gate.shape[-1] // 4
        assert hist.history["loss"][-1] < hist.history["loss"][0]


class TestLlama7bMemoryBudget:
    """SURVEY §7 calls the 7B memory layout make-or-break; validate it AOT
    (eval_shape + sharding arithmetic, no chips) against the v5e 16-GiB
    HBM budget."""

    V5E_HBM = 16 * 2**30

    def _plan(self, mesh):
        import numpy as np

        from tensorflow_train_distributed_tpu.models import llama
        from tensorflow_train_distributed_tpu.training import (
            plan_state_memory,
        )

        task = llama.make_task(llama.LLAMA_PRESETS["llama2_7b"])
        batch = {"tokens": np.zeros((8, 4096), np.int32),
                 "targets": np.zeros((8, 4096), np.int32)}
        return plan_state_memory(task, batch, optax.adamw(1e-5), mesh)

    def test_fsdp_tp_fits_v5e8_and_v5e16(self):
        from tensorflow_train_distributed_tpu.runtime.compat import (
            abstract_mesh,
        )

        from tensorflow_train_distributed_tpu.runtime.mesh import (
            AXES, MeshConfig, build_mesh,
        )

        plan8 = self._plan(build_mesh(MeshConfig(data=1, fsdp=2, tensor=4)))
        # ~26 GB params+opt (7B × 12 bytes: f32 master + adam mu/nu),
        # sharded 8-ways with a small replicated floor (norm scales).
        assert plan8["total_bytes"] > 70 * 2**30
        assert plan8["per_device_bytes"] < self.V5E_HBM
        assert plan8["replicated_bytes"] < 2**30
        # v5e-16 (fsdp=4 × tensor=4) — AbstractMesh: no 16 devices needed.
        sizes = dict.fromkeys(AXES, 1)
        sizes.update(fsdp=4, tensor=4)
        mesh16 = abstract_mesh(tuple(sizes[a] for a in AXES), AXES)
        plan16 = self._plan(mesh16)
        assert plan16["per_device_bytes"] < self.V5E_HBM / 2
        assert plan16["per_device_bytes"] < plan8["per_device_bytes"]

    def test_dp_tp_exceeds_v5e_documenting_fsdp_default(self):
        """Pure dp×tp replicates params+opt over data: 7B with adam needs
        ~19 GiB/device at tensor=4 regardless of the data size — that is
        WHY the llama2_7b_sft registry config defaults to fsdp_tp."""
        from tensorflow_train_distributed_tpu.models import registry
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            MeshConfig, build_mesh,
        )

        plan = self._plan(build_mesh(MeshConfig(data=2, tensor=4)))
        assert plan["per_device_bytes"] > self.V5E_HBM
        assert registry.get_entry("llama2_7b_sft")["strategy"] == "fsdp_tp"


class TestActivationMemoryModel:
    """training.memory: the calibrated activation estimate — pinned to the
    three OOM points measured on a real v5e chip (July 2026)."""

    V5E_BUDGET = 15.75 * 2**30

    def _estimate(self, preset, batch, seq, remat):
        from tensorflow_train_distributed_tpu.models import llama
        from tensorflow_train_distributed_tpu.training.memory import (
            STATE_BYTES_PER_PARAM, decoder_activation_bytes,
        )

        cfg = llama.LLAMA_PRESETS[preset]
        model = llama.LlamaModel(cfg)
        import numpy as np

        abstract = jax.eval_shape(
            lambda: model.init(jax.random.key(0),
                               np.zeros((1, seq), np.int32)))
        n_params = sum(
            x.size for x in jax.tree_util.tree_leaves(abstract["params"]))
        state = n_params * STATE_BYTES_PER_PARAM
        act = decoder_activation_bytes(
            cfg.num_layers, cfg.d_model, batch, seq, remat=remat)
        return state + act

    def test_measured_point_125m_b8_noremat_fits(self):
        # Measured: runs at 31.8k tok/s on the chip.
        est = self._estimate("llama_125m", 8, 2048, remat=False)
        assert est <= self.V5E_BUDGET

    def test_measured_point_125m_b16_noremat_refused(self):
        # Measured: OOM, 26.4 GiB requested.  The estimate must refuse
        # the budget (that's the guard's job) and stay in the measured
        # point's calibration band — not so low it green-lights a compile
        # that cannot fit.
        est = self._estimate("llama_125m", 16, 2048, remat=False)
        assert est > self.V5E_BUDGET
        assert est > 0.7 * 26.4 * 2**30

    def test_no_ffn_policy_sits_between_remat_and_no_remat(self):
        from tensorflow_train_distributed_tpu.training.memory import (
            decoder_activation_bytes,
        )

        kw = dict(num_layers=12, d_model=768, batch=16, seq=2048)
        no_remat = decoder_activation_bytes(remat=False, **kw)
        no_ffn = decoder_activation_bytes(remat=False, ffn_size=2048,
                                          save_ffn_hiddens=False, **kw)
        remat = decoder_activation_bytes(remat=True, **kw)
        assert remat < no_ffn < no_remat

    def test_measured_point_1b_noremat_state_refused(self):
        # Measured: llama_1b state alone exceeds the chip.
        est = self._estimate("llama_1b", 16, 2048, remat=False)
        assert est > 17 * 2**30

    def test_plan_train_memory_7b_v5e16(self):
        """The combined planner: 7B fsdp4xtp4 fits v5e-16 at small batch
        with remat, and refuses the large-batch config."""
        import numpy as np
        import optax

        from tensorflow_train_distributed_tpu.runtime.compat import (
            abstract_mesh,
        )

        from tensorflow_train_distributed_tpu.models import llama
        from tensorflow_train_distributed_tpu.runtime.mesh import AXES
        from tensorflow_train_distributed_tpu.training import (
            plan_train_memory,
        )

        sizes = dict.fromkeys(AXES, 1)
        sizes.update(fsdp=4, tensor=4)
        mesh16 = abstract_mesh(tuple(sizes[a] for a in AXES), AXES)
        task = llama.make_task(llama.LLAMA_PRESETS["llama2_7b"])

        def plan(batch):
            b = {"tokens": np.zeros((batch, 4096), np.int32),
                 "targets": np.zeros((batch, 4096), np.int32)}
            return plan_train_memory(task, b, optax.adamw(1e-5), mesh16,
                                     device_kind="TPU v5e")

        small = plan(4)
        assert small["fits"], small
        assert small["activation_bytes_per_device"] > 0
        big = plan(64)
        assert not big["fits"], big
        assert (big["step_bytes_per_device"]
                > small["step_bytes_per_device"])


@pytest.mark.slow  # full 7B SPMD compile
class TestLlama7bAotCompile:
    """Compile-level 7B proof: the REAL llama2_7b
    train step AOT-lowers and runs the full XLA SPMD partitioning
    pipeline over an fsdp x tp mesh with nothing materialized — the
    collective structure is asserted from the compiled HLO."""

    def test_7b_partitions_on_8dev_fsdp_tp(self, mesh8):
        import numpy as np
        import optax

        from tensorflow_train_distributed_tpu.models import llama
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            MeshConfig, build_mesh,
        )
        from tensorflow_train_distributed_tpu.training import (
            Policy, Trainer, TrainerConfig,
        )

        mesh = build_mesh(MeshConfig(fsdp=2, tensor=4))
        task = llama.CausalLmTask(llama.LLAMA_PRESETS["llama2_7b"])
        trainer = Trainer(
            task, optax.adamw(1e-4, b1=0.9, b2=0.95, weight_decay=0.1),
            mesh, policy=Policy.from_name("mixed_bfloat16"),
            config=TrainerConfig(log_every=1_000_000))
        batch = {"tokens": np.zeros((8, 4096), np.int32),
                 "targets": np.zeros((8, 4096), np.int32)}
        compiled = trainer.lower_train_step(batch).compile()
        txt = compiled.as_text()
        # fsdp: params all-gather before use; grads reduced across fsdp.
        # tp: activation all-reduce (Megatron row/col pattern).
        assert txt.count("all-gather") > 0
        assert txt.count("all-reduce") > 0
        # State never materializes unsharded: per-device argument bytes
        # are ~1/8 of the ~84 GB f32+moments state.
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes < 15 * 2**30


class TestRegistry:
    def test_all_reference_configs_present(self):
        names = registry.available()
        # The five reference configs (BASELINE.json) all have entries.
        for required in ("mnist", "resnet50_imagenet", "bert_base_mlm",
                         "transformer_big_wmt", "llama2_7b_sft"):
            assert required in names, required

    def test_unknown_config_raises(self):
        with pytest.raises(ValueError, match="Unknown config"):
            registry.get_entry("alexnet")


class TestEncoderRemat:
    """remat=True is a pure memory/speed trade: params, forward, and
    grads must be bit-identical (nn.remat is a transparent lift, so
    trained/HF checkpoints load unchanged)."""

    def test_bert_remat_parity(self):
        import dataclasses

        from tensorflow_train_distributed_tpu.models import bert

        cfg0 = bert.BERT_PRESETS["bert_tiny"]
        cfg1 = dataclasses.replace(cfg0, remat=True)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg0.vocab_size, (2, 16)).astype(np.int32)
        p0 = bert.BertEncoder(cfg0).init(jax.random.key(0), ids)["params"]
        p1 = bert.BertEncoder(cfg1).init(jax.random.key(0), ids)["params"]
        assert (jax.tree_util.tree_structure(p0)
                == jax.tree_util.tree_structure(p1))
        o0 = bert.BertEncoder(cfg0).apply({"params": p0}, ids)
        o1 = bert.BertEncoder(cfg1).apply({"params": p0}, ids)
        np.testing.assert_allclose(np.asarray(o0), np.asarray(o1),
                                   atol=1e-6)
        g = lambda cfg: jax.grad(  # noqa: E731
            lambda p: bert.BertEncoder(cfg).apply(
                {"params": p}, ids).sum())(p0)
        # rtol, not just atol: remat recompute reorders float32 sums, so
        # gradients of magnitude ~1e2 carry ~1e-4 absolute noise on some
        # XLA versions; a real parity break would be O(1) relative.
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4),
            g(cfg0), g(cfg1))

    def test_transformer_remat_parity(self):
        import dataclasses

        from tensorflow_train_distributed_tpu.models import transformer

        cfg0 = transformer.TRANSFORMER_PRESETS["transformer_tiny"]
        cfg1 = dataclasses.replace(cfg0, remat=True)
        rng = np.random.default_rng(1)
        src = rng.integers(0, cfg0.vocab_size, (2, 8)).astype(np.int32)
        M = transformer.Seq2SeqTransformer
        p0 = M(cfg0).init(jax.random.key(1), src, src)["params"]
        p1 = M(cfg1).init(jax.random.key(1), src, src)["params"]
        assert (jax.tree_util.tree_structure(p0)
                == jax.tree_util.tree_structure(p1))
        o0 = M(cfg0).apply({"params": p0}, src, src)
        o1 = M(cfg1).apply({"params": p0}, src, src)
        np.testing.assert_allclose(np.asarray(o0), np.asarray(o1),
                                   atol=1e-5)
        g = lambda cfg: jax.grad(  # noqa: E731
            lambda p: M(cfg).apply({"params": p}, src, src)
            .astype(jnp.float32).sum())(p0)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4),
            g(cfg0), g(cfg1))


@pytest.mark.slow  # fit loop
def test_vision_top5_metric(mesh8):
    """ImageNet convention: top-5 accuracy reported alongside top-1 (and
    top-5 >= top-1 by construction); LeNet/MNIST (10 classes) gets it,
    and it flows through fit's metric pipeline."""
    import optax

    from tensorflow_train_distributed_tpu.data import (
        DataConfig, HostDataLoader,
    )
    from tensorflow_train_distributed_tpu.data.datasets import get_dataset
    from tensorflow_train_distributed_tpu.models import lenet
    from tensorflow_train_distributed_tpu.training import (
        History, Trainer, TrainerConfig,
    )

    loader = HostDataLoader(get_dataset("mnist", num_examples=128),
                            DataConfig(global_batch_size=32))
    trainer = Trainer(lenet.make_task(), optax.adam(1e-3), mesh8,
                      config=TrainerConfig(log_every=1),
                      callbacks=[hist := History()])
    trainer.fit(iter(loader), steps=3)
    assert "top5_accuracy" in hist.history
    assert all(t5 >= t1 - 1e-6 for t1, t5 in
               zip(hist.history["accuracy"], hist.history["top5_accuracy"]))


@pytest.mark.slow  # forks a 16-device interpreter
def test_7b_partitions_on_16dev_v5e16_subprocess():
    """The exact v5e-16 topology (fsdp=4 x tp=4): needs 16 virtual
    devices, which the session-scoped 8-device conftest can't provide —
    fork a fresh interpreter (the multihost-test pattern)."""
    import subprocess
    import sys

    src = """
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 16)
import numpy as np, optax
from tensorflow_train_distributed_tpu.models import llama
from tensorflow_train_distributed_tpu.runtime.mesh import MeshConfig, build_mesh
from tensorflow_train_distributed_tpu.training import Policy, Trainer, TrainerConfig

mesh = build_mesh(MeshConfig(fsdp=4, tensor=4))
task = llama.CausalLmTask(llama.LLAMA_PRESETS["llama2_7b"])
tr = Trainer(task, optax.adamw(1e-4, b1=0.9, b2=0.95, weight_decay=0.1),
             mesh, policy=Policy.from_name("mixed_bfloat16"),
             config=TrainerConfig(log_every=1_000_000))
batch = {"tokens": np.zeros((16, 4096), np.int32),
         "targets": np.zeros((16, 4096), np.int32)}
compiled = tr.lower_train_step(batch).compile()
txt = compiled.as_text()
assert txt.count("all-gather") > 0 and txt.count("all-reduce") > 0
mem = compiled.memory_analysis()
# ~84 GB state over 16 devices: strictly sharded arguments.
assert mem.argument_size_in_bytes < 8 * 2**30, mem.argument_size_in_bytes
print("OK", txt.count("all-gather"), txt.count("all-reduce"),
      mem.argument_size_in_bytes)
"""
    out = subprocess.run([sys.executable, "-c", src], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_plan_train_memory_refuses_moe():
    """The activation model has no MoE dispatch/expert-buffer terms; a
    silent underestimate would green-light compiles that cannot fit."""
    import optax

    from tensorflow_train_distributed_tpu.runtime.compat import (
        abstract_mesh,
    )

    from tensorflow_train_distributed_tpu.models import moe
    from tensorflow_train_distributed_tpu.runtime.mesh import AXES
    from tensorflow_train_distributed_tpu.training import plan_train_memory

    sizes = dict.fromkeys(AXES, 1)
    sizes.update(expert=4)
    mesh = abstract_mesh(tuple(sizes[a] for a in AXES), AXES)
    b = {"tokens": np.zeros((4, 128), np.int32),
         "targets": np.zeros((4, 128), np.int32)}
    with pytest.raises(ValueError, match="MoE"):
        plan_train_memory(moe.make_task(moe.MOE_PRESETS["moe_tiny"]), b,
                          optax.adamw(1e-5), mesh)


class TestSubsampledStatsBN:
    """The BN-traffic attack (July v5e trace: BN statistics dominate the
    ResNet step): strided-stats BN must be exact at stride 1, use the
    subsampled statistics at stride 2, and interchange checkpoints with
    the exact-BN presets."""

    def _io(self, seed=0, shape=(4, 8, 8, 6)):
        rng = np.random.default_rng(seed)
        return rng.standard_normal(shape, dtype=np.float32) * 2.0 + 0.5

    def test_stride1_matches_flax_batchnorm(self):
        import flax.linen as nn

        from tensorflow_train_distributed_tpu.models.resnet import (
            SubsampledStatsBN,
        )

        x = jnp.asarray(self._io())
        ours = SubsampledStatsBN(use_running_average=False, momentum=0.9,
                                 epsilon=1e-5, dtype=jnp.float32,
                                 stats_stride=1)
        ref = nn.BatchNorm(use_running_average=False, momentum=0.9,
                           epsilon=1e-5, dtype=jnp.float32)
        v_ours = ours.init(jax.random.key(0), x)
        v_ref = ref.init(jax.random.key(0), x)
        y_ours, m_ours = ours.apply(v_ours, x, mutable=["batch_stats"])
        y_ref, m_ref = ref.apply(v_ref, x, mutable=["batch_stats"])
        np.testing.assert_allclose(np.asarray(y_ours), np.asarray(y_ref),
                                   rtol=2e-5, atol=2e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5),
            m_ours["batch_stats"], m_ref["batch_stats"])

    def test_stride2_uses_subsampled_statistics(self):
        from tensorflow_train_distributed_tpu.models.resnet import (
            SubsampledStatsBN,
        )

        x = jnp.asarray(self._io(1))
        bn = SubsampledStatsBN(use_running_average=False, momentum=0.0,
                               epsilon=0.0, dtype=jnp.float32,
                               stats_stride=2)
        v = bn.init(jax.random.key(0), x)
        y, mut = bn.apply(v, x, mutable=["batch_stats"])
        sub = np.asarray(x)[:, ::2, ::2, :].astype(np.float64)
        mean = sub.mean((0, 1, 2))
        var = (sub ** 2).mean((0, 1, 2)) - mean ** 2
        # momentum=0 → running stats ARE this batch's stats.
        np.testing.assert_allclose(
            np.asarray(mut["batch_stats"]["mean"]), mean, rtol=1e-4)
        np.testing.assert_allclose(
            np.asarray(mut["batch_stats"]["var"]), var, rtol=1e-3)
        # Normalize-apply uses those stats over the FULL tensor.
        want = (np.asarray(x) - mean) / np.sqrt(var)
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-3,
                                   atol=1e-4)

    def test_eval_uses_running_stats(self):
        from tensorflow_train_distributed_tpu.models.resnet import (
            SubsampledStatsBN,
        )

        x = jnp.asarray(self._io(2))
        bn = SubsampledStatsBN(use_running_average=True, momentum=0.9,
                               epsilon=1e-5, dtype=jnp.float32)
        v = bn.init(jax.random.key(0), x)
        y = bn.apply(v, x)  # fresh stats: mean 0, var 1 → near-identity
        np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                                   rtol=1e-4, atol=1e-4)

    def test_bnsub_preset_checkpoint_interchanges(self):
        import dataclasses

        from tensorflow_train_distributed_tpu.models import resnet

        cfg = dataclasses.replace(resnet.RESNET_PRESETS["resnet_tiny"])
        cfg_sub = dataclasses.replace(cfg, bn_stats_stride=2)
        x = jnp.zeros((1, 16, 16, 3))
        v = resnet.ResNet(cfg).init(jax.random.key(0), x, train=False)
        v_sub = resnet.ResNet(cfg_sub).init(jax.random.key(0), x,
                                            train=False)
        assert (jax.tree_util.tree_structure(v)
                == jax.tree_util.tree_structure(v_sub))
        # Exact-BN variables evaluate through the subsampled model.
        y = resnet.ResNet(cfg_sub).apply(v, x, train=False)
        assert np.isfinite(np.asarray(y)).all()

    @pytest.mark.slow
    def test_bnsub_resnet_trains(self, mesh8):
        import dataclasses

        import optax

        from tensorflow_train_distributed_tpu.data import (
            DataConfig, HostDataLoader, get_dataset,
        )
        from tensorflow_train_distributed_tpu.models import resnet

        cfg = dataclasses.replace(resnet.RESNET_PRESETS["resnet_tiny"],
                                  bn_stats_stride=2)
        loader = HostDataLoader(
            get_dataset("imagenet", num_examples=64, num_classes=10,
                        image_size=32),
            DataConfig(global_batch_size=16))
        trainer = Trainer(resnet.make_task(cfg, label_smoothing=0.0,
                                           weight_decay=0.0),
                          optax.adam(1e-3), mesh8,
                          config=TrainerConfig(log_every=4),
                          callbacks=[hist := History()])
        state = trainer.fit(iter(loader), steps=8)
        assert np.isfinite(hist.history["loss"]).all()
        means = [np.asarray(x) for path, x in
                 jax.tree_util.tree_leaves_with_path(
                     state.model_state["batch_stats"])
                 if path[-1].key == "mean"]
        assert any(np.abs(m).max() > 0 for m in means)


class TestLlama13bScale:
    """llama2_13b: partitions through the full SPMD pipeline, and the
    planner gives honest fit answers (v5e-16 at seq 4096 does NOT fit —
    shrink seq or grow the slice; that refusal is the feature)."""

    def _plan(self, seq, axes):
        from tensorflow_train_distributed_tpu.runtime.compat import (
            abstract_mesh,
        )

        from tensorflow_train_distributed_tpu.models import llama
        from tensorflow_train_distributed_tpu.runtime.mesh import AXES
        from tensorflow_train_distributed_tpu.training import (
            plan_train_memory,
        )

        sizes = dict.fromkeys(AXES, 1)
        sizes.update(axes)
        mesh = abstract_mesh(tuple(sizes[a] for a in AXES), AXES)
        task = llama.make_task(llama.LLAMA_PRESETS["llama2_13b"])
        b = {"tokens": np.zeros((4, seq), np.int32),
             "targets": np.zeros((4, seq), np.int32)}
        return plan_train_memory(task, b, optax.adamw(1e-5), mesh,
                                 device_kind="TPU v5e")

    def test_planner_refuses_v5e16_seq4096(self):
        plan = self._plan(4096, dict(fsdp=4, tensor=4))
        assert not plan["fits"]

    def test_planner_fits_v5e16_seq2048(self):
        plan = self._plan(2048, dict(fsdp=4, tensor=4))
        assert plan["fits"], plan

    def test_planner_fits_v5e32_seq4096(self):
        plan = self._plan(4096, dict(fsdp=8, tensor=4))
        assert plan["fits"], plan

    @pytest.mark.slow  # full 13B SPMD compile
    def test_13b_partitions_on_8dev_fsdp_tp(self):
        from tensorflow_train_distributed_tpu.models import llama
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            MeshConfig, build_mesh,
        )
        from tensorflow_train_distributed_tpu.training import (
            Policy, Trainer, TrainerConfig,
        )

        mesh = build_mesh(MeshConfig(fsdp=2, tensor=4))
        task = llama.CausalLmTask(llama.LLAMA_PRESETS["llama2_13b"])
        trainer = Trainer(
            task, optax.adamw(1e-4, b1=0.9, b2=0.95, weight_decay=0.1),
            mesh, policy=Policy.from_name("mixed_bfloat16"),
            config=TrainerConfig(log_every=1_000_000))
        batch = {"tokens": np.zeros((8, 4096), np.int32),
                 "targets": np.zeros((8, 4096), np.int32)}
        compiled = trainer.lower_train_step(batch).compile()
        txt = compiled.as_text()
        assert txt.count("all-gather") > 0 and txt.count("all-reduce") > 0
