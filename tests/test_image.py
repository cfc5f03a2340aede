"""Image decode + augmentation: the real-data ImageNet ingestion path.

JPEG-bearing TFRecords → host-side decode/random-crop/flip (the
reference's tf.data image stage, SURVEY §2.1/§3.5) → ResNet fit — in
process, through the data-service workers, and through the real CLI.
"""

import pytest

pytestmark = pytest.mark.slow  # compile/fit-heavy: full-suite tier

import io
import os

import numpy as np
import pytest

from tensorflow_train_distributed_tpu.data import image as I
from tensorflow_train_distributed_tpu.data.tfrecord import (
    TFRecordWriter,
    encode_example,
    open_tfrecord_dir,
    write_features_sidecar,
)


def _jpeg_bytes(rng, h, w):
    from PIL import Image

    arr = rng.integers(0, 255, (h, w, 3)).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, "JPEG")
    return buf.getvalue(), arr


def _write_corpus(root, n=16, shards=2, seed=0):
    rng = np.random.default_rng(seed)
    os.makedirs(root, exist_ok=True)
    per = n // shards
    for s in range(shards):
        with TFRecordWriter(os.path.join(root, f"imgs-{s}.tfrecord")) as w:
            for i in range(per):
                data, _ = _jpeg_bytes(rng, int(rng.integers(40, 90)),
                                      int(rng.integers(40, 90)))
                w.write(encode_example({
                    "image/encoded": data,
                    "image/class/label": np.int64((s * per + i) % 10),
                }))
    write_features_sidecar(root, None)  # RAW marker: varlen bytes
    return root


class TestDecodeAugment:
    def test_decode_roundtrip_shape(self):
        rng = np.random.default_rng(0)
        data, arr = _jpeg_bytes(rng, 48, 64)
        img = I.decode_image(data)
        assert img.shape == (48, 64, 3) and img.dtype == np.uint8

    def test_train_record_shape_norm_and_determinism(self):
        rng = np.random.default_rng(1)
        data, _ = _jpeg_bytes(rng, 80, 60)
        rec = {"image/encoded": data, "image/class/label": np.int64(3)}
        a = I.imagenet_train_record(rec, size=32)
        b = I.imagenet_train_record(rec, size=32)
        assert a["image"].shape == (32, 32, 3)
        assert a["image"].dtype == np.float32
        assert a["label"] == 3
        # Normalized: values centered (not 0..255).
        assert abs(float(a["image"].mean())) < 3.0
        np.testing.assert_array_equal(a["image"], b["image"])

    def test_different_records_get_different_crops(self):
        rng = np.random.default_rng(2)
        d1, _ = _jpeg_bytes(rng, 70, 70)
        d2, _ = _jpeg_bytes(rng, 70, 70)
        a = I.imagenet_train_record({"jpeg": d1, "label": 0}, size=32)
        b = I.imagenet_train_record({"jpeg": d2, "label": 0}, size=32)
        assert not np.array_equal(a["image"], b["image"])

    def test_eval_center_crop_geometry(self):
        # A tall image: center crop takes the middle band.
        img = np.zeros((100, 50, 3), np.uint8)
        img[40:60] = 255  # bright middle band
        out = I.center_crop(img, 32)
        assert out.shape == (32, 32, 3)
        assert out.mean() > img.mean()  # crop centered on the band

    def test_bare_key_names_accepted(self):
        rng = np.random.default_rng(3)
        data, _ = _jpeg_bytes(rng, 50, 50)
        rec = I.imagenet_eval_record({"jpeg": data, "label": 7}, size=32)
        assert rec["label"] == 7

    def test_missing_keys_fail_loudly(self):
        with pytest.raises(KeyError, match="encoded image"):
            I.imagenet_train_record({"label": 1})
        rng = np.random.default_rng(4)
        data, _ = _jpeg_bytes(rng, 50, 50)
        with pytest.raises(KeyError, match="label"):
            I.imagenet_train_record({"jpeg": data})


class TestPerEpochAugmentation:
    """Fresh crop/flip per epoch (reference tf.data semantics), still
    deterministic across workers and restarts."""

    def test_same_record_fresh_crop_per_epoch(self):
        rng = np.random.default_rng(11)
        data, _ = _jpeg_bytes(rng, 80, 60)
        rec = {"jpeg": data, "label": 1}
        e0 = I.imagenet_train_record(rec, size=32, epoch=0)
        e1 = I.imagenet_train_record(rec, size=32, epoch=1)
        e1b = I.imagenet_train_record(rec, size=32, epoch=1)
        assert not np.array_equal(e0["image"], e1["image"])
        np.testing.assert_array_equal(e1["image"], e1b["image"])

    def test_loader_threads_epoch_into_transform(self, tmp_path):
        from tensorflow_train_distributed_tpu.data import (
            DataConfig, HostDataLoader,
        )

        root = _write_corpus(str(tmp_path))
        cfg = DataConfig(global_batch_size=8, shuffle=False, num_epochs=2)

        def batches():
            src = open_tfrecord_dir(root, transform="imagenet_train_32")
            assert src.epoch_aware
            return list(HostDataLoader(src, cfg))

        a = batches()
        assert len(a) == 4  # 2 epochs x 2 steps
        # Same records, different epoch: fresh crops.
        assert not np.array_equal(a[0]["image"], a[2]["image"])
        np.testing.assert_array_equal(a[0]["label"], a[2]["label"])
        # A second loader reproduces the stream exactly (worker/restart
        # determinism).
        for x, y in zip(a, batches()):
            np.testing.assert_array_equal(x["image"], y["image"])

    def test_mid_epoch_resume_reproduces_epoch_crops(self, tmp_path):
        from tensorflow_train_distributed_tpu.data import (
            DataConfig, HostDataLoader,
        )

        root = _write_corpus(str(tmp_path))
        src = open_tfrecord_dir(root, transform="imagenet_train_32")
        cfg = DataConfig(global_batch_size=8, shuffle=False, num_epochs=2)
        loader = HostDataLoader(src, cfg)
        full = list(loader)
        resumed = list(loader.iter_from(3))  # last batch of epoch 1
        assert len(resumed) == 1
        np.testing.assert_array_equal(full[3]["image"], resumed[0]["image"])

    def test_interleaved_iterators_do_not_corrupt_epochs(self, tmp_path):
        """The epoch travels with each fetch, not as source state — a
        second iterator opened mid-stream (periodic eval / resume probe)
        must not shift the first iterator's augmentation epoch."""
        from tensorflow_train_distributed_tpu.data import (
            DataConfig, HostDataLoader,
        )

        root = _write_corpus(str(tmp_path))
        src = open_tfrecord_dir(root, transform="imagenet_train_32")
        cfg = DataConfig(global_batch_size=8, shuffle=False, num_epochs=2)
        loader = HostDataLoader(src, cfg)
        sequential = list(loader)  # the reference stream

        it = iter(loader)
        got = [next(it)]           # epoch 0, batch 0
        # Interleave: a fresh epoch-0 iterator AND an epoch-1 probe.
        next(iter(loader))
        list(loader.iter_from(3))
        got += list(it)            # rest of the original stream
        assert len(got) == len(sequential)
        for x, y in zip(got, sequential):
            np.testing.assert_array_equal(x["image"], y["image"])

    def test_eval_split_view_keeps_fresh_epochs(self, tmp_path):
        """SliceSource (--eval-split wrapping) must forward the epoch —
        a frozen view would silently undo per-epoch augmentation."""
        from tensorflow_train_distributed_tpu.data import (
            DataConfig, HostDataLoader,
        )
        from tensorflow_train_distributed_tpu.data.datasets import (
            train_val_split,
        )

        root = _write_corpus(str(tmp_path))
        src = open_tfrecord_dir(root, transform="imagenet_train_32")
        train, _val = train_val_split(src, 0.25)
        assert train.epoch_aware
        cfg = DataConfig(global_batch_size=8, shuffle=False, num_epochs=2)
        b = list(HostDataLoader(train, cfg))
        assert not np.array_equal(b[0]["image"], b[1]["image"])

    def test_native_stager_warns_frozen_augmentation(self, tmp_path):
        from tensorflow_train_distributed_tpu.data import (
            DataConfig, HostDataLoader,
        )
        from tensorflow_train_distributed_tpu.native.staging import (
            NativeBatchStager,
        )

        if not NativeBatchStager.available():
            pytest.skip("native stager not built in this environment")
        root = _write_corpus(str(tmp_path))
        src = open_tfrecord_dir(root, transform="imagenet_train_32")
        cfg = DataConfig(global_batch_size=8, shuffle=False, num_epochs=1,
                         use_native=True)
        with pytest.warns(UserWarning, match="frozen"):
            next(iter(HostDataLoader(src, cfg)))

    def test_native_resume_matches_frozen_stream(self, tmp_path):
        """use_native freezes augmentation at epoch 0; a preemption
        resume (iter_from, always the Python path) must serve the SAME
        frozen crops or the restarted run diverges."""
        from tensorflow_train_distributed_tpu.data import (
            DataConfig, HostDataLoader,
        )
        from tensorflow_train_distributed_tpu.native.staging import (
            NativeBatchStager,
        )

        if not NativeBatchStager.available():
            pytest.skip("native stager not built in this environment")
        root = _write_corpus(str(tmp_path))
        src = open_tfrecord_dir(root, transform="imagenet_train_32")
        cfg = DataConfig(global_batch_size=8, shuffle=False, num_epochs=2,
                         use_native=True)
        loader = HostDataLoader(src, cfg)
        with pytest.warns(UserWarning, match="frozen"):
            stream = list(loader)  # 4 batches, all epoch-0 crops
        resumed = list(loader.iter_from(2))  # restart at epoch 1
        assert len(resumed) == 2
        for x, y in zip(stream[2:], resumed):
            np.testing.assert_array_equal(x["image"], y["image"])


class TestUint8DeviceNormalize:
    """Ship-raw-uint8 transforms + device-side ImageNet normalization:
    4x less host→device transfer, no host f32 math (measured +60%
    in-process host throughput, tools/bench_input.py)."""

    def test_u8_transform_matches_f32_pre_normalize(self):
        rng = np.random.default_rng(21)
        data, _ = _jpeg_bytes(rng, 80, 60)
        rec = {"jpeg": data, "label": 3}
        u8 = I.imagenet_train_record_u8(rec, size=32, epoch=1)
        f32 = I.imagenet_train_record(rec, size=32, epoch=1)
        assert u8["image"].dtype == np.uint8
        np.testing.assert_allclose(
            I._normalize(u8["image"]), f32["image"], rtol=1e-6, atol=1e-6)
        ev = I.imagenet_eval_record_u8(rec, size=32)
        assert ev["image"].dtype == np.uint8

    def test_u8_names_resolve_on_demand(self):
        from tensorflow_train_distributed_tpu.data.filesource import (
            resolve_transform,
        )

        fn = resolve_transform("imagenet_eval_u8_48")
        rng = np.random.default_rng(22)
        data, _ = _jpeg_bytes(rng, 64, 64)
        rec = fn({"jpeg": data, "label": 1})
        assert rec["image"].shape == (48, 48, 3)
        assert rec["image"].dtype == np.uint8

    def test_resnet_task_normalizes_uint8_on_device(self):
        import jax

        from tensorflow_train_distributed_tpu.models import resnet

        rng = np.random.default_rng(23)
        u8 = rng.integers(0, 255, (2, 32, 32, 3)).astype(np.uint8)
        f32 = (((u8.astype(np.float32) / 255.0) - I.MEAN_RGB)
               / I.STDDEV_RGB)
        labels = np.array([1, 2], np.int32)
        for preset in ("resnet_tiny", "resnet50_s2d"):
            task = resnet.make_task(resnet.RESNET_PRESETS[preset],
                                    label_smoothing=0.0, weight_decay=0.0)
            variables = task.init_variables(
                jax.random.key(0), {"image": f32, "label": labels})
            state = {"batch_stats": variables["batch_stats"]}
            la, _ = task.loss_fn(variables["params"], state,
                                 {"image": f32, "label": labels},
                                 None, False)
            lb, _ = task.loss_fn(variables["params"], state,
                                 {"image": u8, "label": labels},
                                 None, False)
            np.testing.assert_allclose(float(la), float(lb), rtol=1e-5)

    def test_resnet_task_normalizes_host_s2d_uint8(self):
        """12-channel uint8 (host-side space_to_depth) tiles the
        normalization constants in s2d channel order."""
        import jax

        from tensorflow_train_distributed_tpu.models import resnet
        from tensorflow_train_distributed_tpu.models.resnet import (
            space_to_depth,
        )

        rng = np.random.default_rng(24)
        u8 = rng.integers(0, 255, (2, 32, 32, 3)).astype(np.uint8)
        f32 = (((u8.astype(np.float32) / 255.0) - I.MEAN_RGB)
               / I.STDDEV_RGB)
        labels = np.array([3, 4], np.int32)
        task = resnet.make_task(resnet.RESNET_PRESETS["resnet50_s2d"],
                                label_smoothing=0.0, weight_decay=0.0)
        import jax.numpy as jnp

        f32_s2d = np.asarray(space_to_depth(jnp.asarray(f32)))
        u8_s2d = np.asarray(space_to_depth(jnp.asarray(u8)))
        assert u8_s2d.dtype == np.uint8
        variables = task.init_variables(
            jax.random.key(0), {"image": f32_s2d, "label": labels})
        state = {"batch_stats": variables["batch_stats"]}
        la, _ = task.loss_fn(variables["params"], state,
                             {"image": f32_s2d, "label": labels},
                             None, False)
        lb, _ = task.loss_fn(variables["params"], state,
                             {"image": u8_s2d, "label": labels},
                             None, False)
        np.testing.assert_allclose(float(la), float(lb), rtol=1e-5)

    def test_prep_image_joins_policy_compute_dtype(self):
        """Under a bf16 policy the normalized uint8 image must land in
        bf16 (f32 activations would silently promote every conv to f32,
        defeating the MXU win)."""
        import jax.numpy as jnp

        from tensorflow_train_distributed_tpu.models import resnet

        task = resnet.make_task(resnet.RESNET_PRESETS["resnet_tiny"])
        u8 = jnp.zeros((2, 8, 8, 3), jnp.uint8)
        bf16_params = {"w": jnp.ones((3,), jnp.bfloat16)}
        assert task._prep_image(u8, bf16_params).dtype == jnp.bfloat16
        f32_params = {"w": jnp.ones((3,), jnp.float32)}
        assert task._prep_image(u8, f32_params).dtype == jnp.float32
        # float inputs pass through untouched (policy already cast them)
        bf16_img = jnp.zeros((2, 8, 8, 3), jnp.bfloat16)
        assert task._prep_image(bf16_img, f32_params) is bf16_img

    def test_uint8_without_constants_fails_loudly(self):
        from tensorflow_train_distributed_tpu.models.lenet import LeNet
        from tensorflow_train_distributed_tpu.models.vision_task import (
            VisionTask,
        )

        task = VisionTask(LeNet())
        import jax.numpy as jnp

        with pytest.raises(ValueError, match="uint8_mean_std"):
            task._prep_image(jnp.zeros((1, 8, 8, 3), jnp.uint8), {})

    def test_cli_trains_resnet_from_u8_transform(self, tmp_path):
        from tensorflow_train_distributed_tpu import launch

        root = _write_corpus(str(tmp_path))
        result = launch.run(launch.build_parser().parse_args([
            "--config", "resnet_tiny", "--steps", "2",
            "--global-batch-size", "8", "--data-dir", root,
            "--data-transform", "imagenet_train_u8_32",
            "--log-every", "1"]))
        assert np.isfinite(result.history["loss"]).all()


class TestJpegTfrecordPath:
    def test_raw_sidecar_roundtrip(self, tmp_path):
        from tensorflow_train_distributed_tpu.data.tfrecord import (
            read_features_sidecar,
        )

        write_features_sidecar(tmp_path, None)
        assert read_features_sidecar(tmp_path) is None

    def test_open_dir_with_named_transform(self, tmp_path):
        root = _write_corpus(str(tmp_path))
        src = open_tfrecord_dir(root, transform="imagenet_train_32")
        assert len(src) == 16
        rec = src[5]
        assert rec["image"].shape == (32, 32, 3)
        # Transform names resolve lazily (data.image import side effect).
        from tensorflow_train_distributed_tpu.data.filesource import (
            resolve_transform,
        )

        assert resolve_transform("imagenet_eval_224") is not None

    def test_data_service_workers_decode_and_augment(self, tmp_path):
        """The out-of-process workers run the decode+augment CPU work —
        where the reference's tf.data service puts it."""
        from tensorflow_train_distributed_tpu.data import DataConfig
        from tensorflow_train_distributed_tpu.data.service import (
            DataServiceDispatcher, SourceSpec,
        )

        root = _write_corpus(str(tmp_path))
        spec = SourceSpec("tfrecord_dir",
                          {"root": root, "transform": "imagenet_train_32"})
        cfg = DataConfig(global_batch_size=8, shuffle=False, num_epochs=1)
        with DataServiceDispatcher(spec, cfg, num_workers=2) as disp:
            batches = list(disp.client())
        assert batches
        for b in batches:
            assert b["image"].shape == (8, 32, 32, 3)
            assert b["image"].dtype == np.float32

    def test_cli_trains_resnet_from_encoded_jpegs(self, tmp_path):
        """--data-dir of encoded images trains ResNet through the real
        CLI."""
        from tensorflow_train_distributed_tpu import launch

        root = _write_corpus(str(tmp_path))
        result = launch.run(launch.build_parser().parse_args([
            "--config", "resnet_tiny", "--steps", "2",
            "--global-batch-size", "8", "--data-dir", root,
            "--data-transform", "imagenet_train_32", "--log-every", "1"]))
        assert np.isfinite(result.history["loss"]).all()

    def test_raw_corpus_without_transform_rejected(self, tmp_path):
        root = _write_corpus(str(tmp_path))
        with pytest.raises(ValueError, match="data-transform"):
            open_tfrecord_dir(root)

    def test_any_size_resolves_on_demand(self):
        from tensorflow_train_distributed_tpu.data.filesource import (
            resolve_transform,
        )

        fn = resolve_transform("imagenet_train_64")
        rng = np.random.default_rng(6)
        data, _ = _jpeg_bytes(rng, 80, 80)
        rec = fn({"jpeg": data, "label": 1})
        assert rec["image"].shape == (64, 64, 3)

    def test_decoded_pixel_array_key_not_misread_as_bytes(self):
        # "image" holds DECODED pixels elsewhere in the package — the
        # transform must raise a schema error, not fail inside PIL.
        with pytest.raises(KeyError, match="encoded image"):
            I.imagenet_train_record(
                {"image": np.zeros((8, 8, 3), np.uint8), "label": 0})

    def test_native_stager_serves_decoded_batches(self, tmp_path):
        """use_native=True over a transformed JPEG corpus: the GIL-free
        stager serves byte-identical batches to the Python path (decode
        happens once, at pack time — a warm-start mode)."""
        from tensorflow_train_distributed_tpu.data import (
            DataConfig, HostDataLoader,
        )
        from tensorflow_train_distributed_tpu.native.staging import (
            NativeBatchStager,
        )

        if not NativeBatchStager.available():
            pytest.skip("native stager not built in this environment")
        root = _write_corpus(str(tmp_path))
        src = open_tfrecord_dir(root, transform="imagenet_train_32")
        cfg = DataConfig(global_batch_size=8, shuffle=False, num_epochs=1)
        py_batches = list(HostDataLoader(src, cfg))
        nat_batches = list(HostDataLoader(
            src, DataConfig(global_batch_size=8, shuffle=False,
                            num_epochs=1, use_native=True)))
        assert len(py_batches) == len(nat_batches) == 2
        for a, b in zip(py_batches, nat_batches):
            np.testing.assert_array_equal(a["image"], b["image"])
            np.testing.assert_array_equal(a["label"], b["label"])
