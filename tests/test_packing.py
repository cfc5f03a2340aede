"""Sequence packing: packed rows must train identically to lone documents.

The money test: logits for a document inside a packed row (segment mask +
restarted RoPE positions) equal the logits of that document run alone —
proof the attention isolation and position arithmetic are exact, not
approximate.
"""

import pytest

pytestmark = pytest.mark.slow  # compile/fit-heavy: full-suite tier

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_train_distributed_tpu.data.packing import (
    PackedLmSource,
    pack_documents,
)
from tensorflow_train_distributed_tpu.models.llama import (
    LLAMA_PRESETS,
    CausalLmTask,
    LlamaModel,
    segment_relative_positions,
)


class TestPackDocuments:
    def test_layout_and_weights(self):
        docs = [np.arange(1, 5), np.arange(10, 13), np.arange(20, 22)]
        recs = pack_documents(docs, seq_len=8)
        assert len(recs) == 2
        r = recs[0]
        np.testing.assert_array_equal(r["tokens"],
                                      [1, 2, 3, 4, 10, 11, 12, 0])
        np.testing.assert_array_equal(r["segment_ids"],
                                      [1, 1, 1, 1, 2, 2, 2, 0])
        np.testing.assert_array_equal(r["targets"],
                                      [2, 3, 4, 0, 11, 12, 0, 0])
        np.testing.assert_array_equal(r["loss_weights"],
                                      [1, 1, 1, 0, 1, 1, 0, 0])

    def test_long_doc_splits_with_boundary_label(self):
        doc = np.arange(1, 12)  # 11 tokens over seq 8
        recs = pack_documents([doc], seq_len=8)
        assert len(recs) == 2
        # Split boundary keeps the true next token as a labeled target.
        assert recs[0]["targets"][-1] == 9
        assert recs[0]["loss_weights"][-1] == 1.0
        assert recs[1]["loss_weights"][2] == 0.0  # true end of doc
        # Continuation is a separate segment (rows can't attend anyway).
        assert recs[1]["segment_ids"][0] != 0

    def test_tiny_docs_skipped_and_validation(self):
        assert pack_documents([np.asarray([7])], 8) == []
        with pytest.raises(ValueError, match="seq_len"):
            pack_documents([np.arange(4)], 1)
        with pytest.raises(ValueError, match="packable"):
            PackedLmSource([np.asarray([1])], 8)


def test_segment_relative_positions():
    seg = jnp.asarray([[1, 1, 1, 2, 2, 3, 0, 0]])
    np.testing.assert_array_equal(
        np.asarray(segment_relative_positions(seg)),
        [[0, 1, 2, 0, 1, 0, 0, 1]])


class TestPackedForwardEquality:
    @pytest.fixture(scope="class", params=["llama_tiny", "llama_tiny_scan"])
    def setup(self, request):
        cfg = LLAMA_PRESETS[request.param]
        rng = np.random.default_rng(0)
        docs = [rng.integers(2, cfg.vocab_size, n).astype(np.int32)
                for n in (5, 7, 4)]
        init_toks = np.zeros((1, 16), np.int32)
        params = LlamaModel(cfg).init(jax.random.key(0),
                                      init_toks)["params"]
        return cfg, params, docs

    def test_packed_logits_match_lone_documents(self, setup):
        cfg, params, docs = setup
        rec = pack_documents(docs, seq_len=16)[0]
        model = LlamaModel(cfg)
        packed = np.asarray(model.apply(
            {"params": params}, jnp.asarray(rec["tokens"][None]),
            segment_ids=jnp.asarray(rec["segment_ids"][None]),
        ).astype(jnp.float32))
        off = 0
        for doc in docs:
            lone = np.asarray(model.apply(
                {"params": params},
                jnp.asarray(doc[None])).astype(jnp.float32))
            np.testing.assert_allclose(
                packed[0, off:off + doc.size], lone[0],
                rtol=2e-5, atol=2e-5)
            off += doc.size

    def test_packed_training_step_runs(self, setup, mesh8):
        import optax

        from tensorflow_train_distributed_tpu.data import (
            DataConfig, HostDataLoader,
        )
        from tensorflow_train_distributed_tpu.training import (
            History, Trainer, TrainerConfig,
        )

        cfg, params, _ = setup
        rng = np.random.default_rng(1)
        docs = [rng.integers(2, cfg.vocab_size, n).astype(np.int32)
                for n in rng.integers(3, 20, 64)]
        source = PackedLmSource(docs, seq_len=16)
        loader = HostDataLoader(source, DataConfig(global_batch_size=8))
        trainer = Trainer(CausalLmTask(cfg), optax.adam(1e-3), mesh8,
                          config=TrainerConfig(log_every=1),
                          callbacks=[hist := History()])
        trainer.fit(iter(loader), steps=3)
        assert np.isfinite(hist.history["loss"]).all()
        assert "loss_weight" in hist.history


def test_pack_from_tfrecord_varlen_corpus(tmp_path):
    """Real-corpus bridge: variable-length docs in TFRecord files (no
    fixed feature spec) pack straight into LM rows."""
    from tensorflow_train_distributed_tpu.data.tfrecord import (
        TFRecordSource, TFRecordWriter,
    )

    rng = np.random.default_rng(2)
    lens = [5, 9, 3, 12, 4]
    p = str(tmp_path / "docs.tfrecord")
    with TFRecordWriter(p) as w:
        for n in lens:
            w.write_example({"tokens": rng.integers(2, 200, n)})
    src = TFRecordSource(p)  # features=None → raw flat arrays
    packed = PackedLmSource.from_source(src, seq_len=16)
    total_tokens = sum(lens)
    seen = sum(int((r["segment_ids"] > 0).sum())
               for r in (packed[i] for i in range(len(packed))))
    assert seen == total_tokens  # every document token landed in a row
    r0 = packed[0]
    assert set(r0) == {"tokens", "targets", "segment_ids", "loss_weights"}
    assert r0["tokens"].shape == (16,)


def test_cli_pack_seq_trains_from_varlen_tfrecord(tmp_path):
    """--data-dir + --pack-seq: a directory of variable-length tokenized
    TFRecord docs trains a decoder LM packed, through the real CLI."""
    from tensorflow_train_distributed_tpu import launch
    from tensorflow_train_distributed_tpu.data.tfrecord import (
        TFRecordWriter,
    )

    rng = np.random.default_rng(4)
    with TFRecordWriter(str(tmp_path / "docs.tfrecord")) as w:
        for n in rng.integers(3, 30, 128):
            w.write_example({"tokens": rng.integers(2, 256, n)})
    result = launch.run(launch.build_parser().parse_args([
        "--config", "llama_tiny_sft", "--steps", "4",
        "--global-batch-size", "8", "--data-dir", str(tmp_path),
        "--pack-seq", "32", "--log-every", "1"]))
    assert np.isfinite(result.history["loss"]).all()
    assert "loss_weight" in result.history  # packed weighting active


def test_cli_pack_seq_guards(tmp_path):
    from tensorflow_train_distributed_tpu import launch
    from tensorflow_train_distributed_tpu.data.tfrecord import (
        TFRecordWriter,
    )

    with TFRecordWriter(str(tmp_path / "docs.tfrecord")) as w:
        w.write_example({"tokens": np.arange(2, 12)})
    args = ["--data-dir", str(tmp_path), "--pack-seq", "16",
            "--steps", "1", "--global-batch-size", "8", "--log-every", "1"]
    with pytest.raises(SystemExit, match="decoder LM"):
        launch.run(launch.build_parser().parse_args(
            ["--config", "bert_tiny_mlm", *args]))
    with pytest.raises(SystemExit, match="data-transform"):
        launch.run(launch.build_parser().parse_args(
            ["--config", "llama_tiny_sft", "--data-transform",
             "u8_image_to_f32", *args]))
    with pytest.raises(SystemExit, match="needs --data-dir"):
        launch.run(launch.build_parser().parse_args(
            ["--config", "llama_tiny_sft", "--pack-seq", "16",
             "--steps", "1"]))
    # Vocab overflow: llama_tiny_sft vocab is 256; write id 999.
    big = tmp_path / "big"
    big.mkdir()
    with TFRecordWriter(str(big / "docs.tfrecord")) as w:
        w.write_example({"tokens": np.asarray([1, 999, 3, 4])})
    with pytest.raises(SystemExit, match="vocab"):
        launch.run(launch.build_parser().parse_args(
            ["--config", "llama_tiny_sft", "--data-dir", str(big),
             "--pack-seq", "16", "--steps", "1",
             "--global-batch-size", "8", "--log-every", "1"]))


class TestMoePacking:
    """MoE family packed segments: same contract as the llama family."""

    @pytest.fixture(scope="class")
    def moe_setup(self):
        import dataclasses

        from tensorflow_train_distributed_tpu.models import moe

        # Generous capacity: with no capacity drops, routing is per-token
        # and the packed-vs-lone comparison is exact; tight capacity would
        # let a later document's tokens steal top-2 slots from an earlier
        # one only through the round-2 fill offsets (drops differ, values
        # that survive are identical either way).
        cfg = dataclasses.replace(
            moe.MOE_PRESETS["moe_tiny"], capacity_factor=4.0)
        rng = np.random.default_rng(3)
        docs = [rng.integers(2, cfg.vocab_size, n).astype(np.int32)
                for n in (5, 4, 3)]
        params = moe.MoeLmModel(cfg).init(
            jax.random.key(0), np.zeros((1, 16), np.int32))["params"]
        return cfg, params, docs

    def test_moe_packed_logits_match_lone_documents(self, moe_setup):
        from tensorflow_train_distributed_tpu.models import moe

        cfg, params, docs = moe_setup
        rec = pack_documents(docs, seq_len=16)[0]
        model = moe.MoeLmModel(cfg)
        packed = np.asarray(model.apply(
            {"params": params}, jnp.asarray(rec["tokens"][None]),
            segment_ids=jnp.asarray(rec["segment_ids"][None]),
        ).astype(jnp.float32))
        off = 0
        for doc in docs:
            lone = np.asarray(model.apply(
                {"params": params},
                jnp.asarray(doc[None])).astype(jnp.float32))
            np.testing.assert_allclose(
                packed[0, off:off + doc.size], lone[0],
                rtol=2e-5, atol=2e-5)
            off += doc.size

    def test_parity_divergence_onset_flagged_by_dropped_frac(self,
                                                             moe_setup):
        """Pin WHEN packed==lone parity breaks: exactly when capacity
        binds — and dropped_frac is the runtime signal.  Generous capacity: dropped_frac==0 and parity holds (the
        test above).  Binding capacity: dropped_frac>0 AND the packed
        row diverges from the lone document (earlier documents consumed
        the shared per-row budget)."""
        import dataclasses

        from tensorflow_train_distributed_tpu.models import moe

        cfg, params, docs = moe_setup
        tight = dataclasses.replace(cfg, capacity_factor=0.25)
        rec = pack_documents(docs, seq_len=16)[0]
        batch = {"tokens": rec["tokens"][None],
                 "targets": rec["tokens"][None],
                 "segment_ids": rec["segment_ids"][None]}

        def run(config, b):
            task = moe.MoeLmTask(config)
            _, (metrics, _) = task.loss_fn(
                params, {}, b, jax.random.key(1), True)
            model = moe.MoeLmModel(config)
            logits = model.apply(
                {"params": params}, jnp.asarray(b["tokens"]),
                segment_ids=jnp.asarray(b["segment_ids"]))
            return metrics, np.asarray(logits.astype(jnp.float32))

        m_ok, _ = run(cfg, batch)
        assert float(m_ok["dropped_frac"]) == 0.0  # parity regime

        m_tight, packed = run(tight, batch)
        assert float(m_tight["dropped_frac"]) > 0.0  # the signal fires
        # ... and parity is indeed broken for the last document.
        lone = np.asarray(moe.MoeLmModel(tight).apply(
            {"params": params},
            jnp.asarray(docs[-1][None])).astype(jnp.float32))
        off = sum(d.size for d in docs[:-1])
        assert not np.allclose(packed[0, off:off + docs[-1].size], lone[0],
                               rtol=2e-5, atol=2e-5)

    def test_moe_packed_training_step_runs(self, moe_setup, mesh8):
        import optax

        from tensorflow_train_distributed_tpu.data import (
            DataConfig, HostDataLoader,
        )
        from tensorflow_train_distributed_tpu.models import moe
        from tensorflow_train_distributed_tpu.training import (
            History, Trainer, TrainerConfig,
        )

        cfg, _, _ = moe_setup
        rng = np.random.default_rng(5)
        docs = [rng.integers(2, cfg.vocab_size, n).astype(np.int32)
                for n in rng.integers(3, 14, 48)]
        source = PackedLmSource(docs, seq_len=16)
        loader = HostDataLoader(source, DataConfig(global_batch_size=8))
        trainer = Trainer(moe.MoeLmTask(cfg), optax.adam(1e-3), mesh8,
                          config=TrainerConfig(log_every=1),
                          callbacks=[hist := History()])
        trainer.fit(iter(loader), steps=3)
        assert np.isfinite(hist.history["loss"]).all()
        assert "loss_weight" in hist.history


class TestGpipePacking:
    """Packed segments ride the GPipe carry: a dp×pp run on packed rows
    must match the dp-only run of the same checkpoint exactly."""

    def test_packed_dp_pp_matches_dp(self, mesh8):
        import optax

        from tensorflow_train_distributed_tpu.data import (
            DataConfig, HostDataLoader,
        )
        from tensorflow_train_distributed_tpu.models.llama import (
            LLAMA_PRESETS, CausalLmTask,
        )
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            MeshConfig, build_mesh,
        )
        from tensorflow_train_distributed_tpu.training import (
            History, Trainer, TrainerConfig,
        )

        cfg = LLAMA_PRESETS["llama_tiny_pp"]
        rng = np.random.default_rng(9)
        docs = [rng.integers(2, cfg.vocab_size, n).astype(np.int32)
                for n in rng.integers(3, 20, 64)]
        source = PackedLmSource(docs, seq_len=16)

        def run(mesh):
            loader = HostDataLoader(
                source, DataConfig(global_batch_size=16, shuffle=False))
            trainer = Trainer(CausalLmTask(cfg), optax.adam(1e-3), mesh,
                              config=TrainerConfig(log_every=1),
                              callbacks=[hist := History()])
            trainer.fit(iter(loader), steps=3)
            return hist.history["loss"]

        pp_mesh = build_mesh(MeshConfig(data=4, pipeline=2))
        dp_loss = run(mesh8)
        pp_loss = run(pp_mesh)
        np.testing.assert_allclose(dp_loss, pp_loss, rtol=2e-4)
