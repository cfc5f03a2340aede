"""Host input-pipeline throughput: records/sec through decode+augment.

The accelerator step is only half the ResNet story — the reference feeds
it from tf.data's parallel C++ decode. This tool measures what THIS
framework's host path sustains (pure CPU; needs no chip), so
"input-bound vs compute-bound" is a measured fact: the chip consumed
~2430 img/s (profiles/bench/last_tpu_result.json, 2026-07-31); the host
must match it with in-process decode, the out-of-process worker fleet
(--data-workers), or pre-decoded storage (the mmap path / native stager
warm start).

Modes benched over one generated JPEG TFRecord corpus:
- inprocess: HostDataLoader + imagenet_train transform on the trainer
  thread;
- workersN: DataServiceDispatcher with N worker processes;
- mmap: the same images pre-decoded into the mmap shard layout
  (u8_image_to_f32 transform) — the storage-side answer.

Prints one JSON line: records/sec per mode.
"""

import argparse
import io
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _make_corpus(root: str, n: int, hw: int, shards: int = 4) -> None:
    import numpy as np
    from PIL import Image

    from tensorflow_train_distributed_tpu.data.tfrecord import (
        TFRecordWriter, encode_example, write_features_sidecar,
    )

    rng = np.random.default_rng(0)
    per = n // shards
    for s in range(shards):
        with TFRecordWriter(os.path.join(root,
                                         f"imgs-{s}.tfrecord")) as w:
            for i in range(per):
                arr = rng.integers(0, 255, (hw, hw, 3)).astype(np.uint8)
                buf = io.BytesIO()
                Image.fromarray(arr).save(buf, "JPEG")
                w.write(encode_example({
                    "image/encoded": buf.getvalue(),
                    "image/class/label": np.int64(i % 1000)}))
    write_features_sidecar(root, None)


def _drain(batches, max_records: int, batch_size: int) -> float:
    t0 = time.perf_counter()
    seen = 0
    for b in batches:
        seen += batch_size
        if seen >= max_records:
            break
    return seen / (time.perf_counter() - t0)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--records", type=int, default=512,
                   help="records per timed drain")
    p.add_argument("--image-hw", type=int, default=256,
                   help="stored JPEG side length (decode cost driver)")
    p.add_argument("--size", type=int, default=224,
                   help="output crop size (imagenet_train_{size})")
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--workers", default="2,4",
                   help="comma list of worker-fleet sizes to bench")
    args = p.parse_args(argv)

    from tensorflow_train_distributed_tpu.runtime.mesh import force_platform

    force_platform("cpu")  # pure host benchmark; never takes the chip

    import numpy as np

    from tensorflow_train_distributed_tpu.data import (
        DataConfig, HostDataLoader,
    )
    from tensorflow_train_distributed_tpu.data.service import (
        DataServiceDispatcher, SourceSpec,
    )
    from tensorflow_train_distributed_tpu.data.tfrecord import (
        open_tfrecord_dir,
    )

    transform = f"imagenet_train_{args.size}"
    cfg = DataConfig(global_batch_size=args.batch, shuffle=True,
                     seed=0, num_epochs=None)
    results = {}
    decode = {}
    with tempfile.TemporaryDirectory() as root:
        _make_corpus(root, args.records, args.image_hw)

        # Raw DECODE throughput (no crop/augment): PIL vs the native
        # libjpeg thread pool (GIL-free; scales with cores in-process,
        # where the PIL path needs a process per core) vs DCT-domain
        # half-resolution decode (the cheap first step when the model
        # only needs a small crop).
        import io as io_lib

        from PIL import Image as PILImage

        from tensorflow_train_distributed_tpu.data.image import (
            _encoded_bytes,
        )
        from tensorflow_train_distributed_tpu.data.tfrecord import (
            TFRecordSource,
        )
        from tensorflow_train_distributed_tpu.native import jpeg as njpeg

        paths = sorted(
            os.path.join(root, f) for f in os.listdir(root)
            if f.endswith(".tfrecord"))
        raw_src = TFRecordSource(paths, None)
        raws = [_encoded_bytes(raw_src[i]) for i in range(len(raw_src))]

        t0 = time.perf_counter()
        for data in raws:
            with PILImage.open(io_lib.BytesIO(data)) as im:
                np.asarray(im.convert("RGB"), np.uint8)
        decode["pil"] = round(len(raws) / (time.perf_counter() - t0), 1)
        if njpeg.available():
            for threads in (1, 2, 4):
                t0 = time.perf_counter()
                njpeg.decode_batch(raws, num_threads=threads)
                decode[f"native_t{threads}"] = round(
                    len(raws) / (time.perf_counter() - t0), 1)
            t0 = time.perf_counter()
            njpeg.decode_batch(raws, scale_denom=2, num_threads=1)
            decode["native_halfres_t1"] = round(
                len(raws) / (time.perf_counter() - t0), 1)

        src = open_tfrecord_dir(root, transform=transform)
        results["inprocess"] = round(_drain(
            iter(HostDataLoader(src, cfg)), args.records, args.batch), 1)

        # uint8 ship-raw-normalize-on-device variant: no host f32 math,
        # 4x smaller batches over PCIe (models.resnet normalizes uint8
        # inputs; bit-exact parity tested).
        u8_src = open_tfrecord_dir(
            root, transform=f"imagenet_train_u8_{args.size}")
        results["inprocess_u8"] = round(_drain(
            iter(HostDataLoader(u8_src, cfg)), args.records, args.batch), 1)

        for n in (int(x) for x in args.workers.split(",") if x):
            spec = SourceSpec("tfrecord_dir",
                              {"root": root, "transform": transform})
            with DataServiceDispatcher(spec, cfg, num_workers=n) as disp:
                results[f"workers{n}"] = round(_drain(
                    iter(disp.client()), args.records, args.batch), 1)

        # Storage-side answer: pre-decoded uint8 mmap shards (decode paid
        # once at dataset build; steady-state is memory-bandwidth reads).
        from tensorflow_train_distributed_tpu.data.filesource import (
            open_sharded, write_shards,
        )

        decoded = [src[i] for i in range(min(len(src), args.records))]

        class _Dec:
            def __len__(self):
                return len(decoded)

            def __getitem__(self, i):
                r = decoded[i]
                return {"image": (np.clip((r["image"] * 0.25 + 0.5), 0, 1)
                                  * 255).astype(np.uint8),
                        "label": np.int32(r["label"])}

        mmap_root = os.path.join(root, "mmap")
        write_shards(mmap_root, _Dec(), num_shards=4)
        mm = open_sharded(mmap_root, transform="u8_image_to_f32")
        results["mmap_predecoded"] = round(_drain(
            iter(HostDataLoader(mm, cfg)), args.records, args.batch), 1)

    print(json.dumps({
        "metric": "input_pipeline_records_per_sec",
        "unit": "records/sec",
        "image_hw": args.image_hw,
        "crop": args.size,
        "modes": results,
        "decode_modes": decode,
        "value": max(results.values()),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
