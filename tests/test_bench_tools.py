"""Bench tooling: the HBM pre-flight guard and the shared timing path.

The guard is memory planning: a compile that cannot fit wastes minutes
of a budgeted chip call before it is refused — these tests
pin its calibration to the three measured v5e data points and its
skip-off-TPU contract, with fake device objects (no backend needed).
"""

import dataclasses
import importlib.util
import os

import pytest

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


@pytest.fixture(scope="module")
def bench_lm_mod():
    spec = importlib.util.spec_from_file_location(
        "bench_lm_under_test", os.path.join(_TOOLS, "bench_lm.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class FakeDevice:
    platform: str = "tpu"
    device_kind: str = "TPU v5 lite"


LLAMA_125M = dict(n_params=134_105_856, n_layers=12, d_model=768, seq=2048)


class TestHbmGuard:
    def test_measured_v5e_points(self, bench_lm_mod):
        """Calibration: b8 no-remat ran on the chip, b16 no-remat OOMed
        at 26.4 GiB (both measured 2026-07-30), remat always fits."""
        check = bench_lm_mod.check_hbm_budget
        dev = FakeDevice()
        check(batch=8, remat=False, causal=True, force=False, device=dev,
              **LLAMA_125M)  # fits → returns
        check(batch=8, remat=True, causal=True, force=False, device=dev,
              **LLAMA_125M)
        with pytest.raises(SystemExit):
            check(batch=16, remat=False, causal=True, force=False,
                  device=dev, **LLAMA_125M)

    def test_skipped_off_tpu_and_unknown_kind_is_an_error(self,
                                                          bench_lm_mod):
        bench_lm_mod.check_hbm_budget(
            batch=4096, remat=False, causal=True, force=False,
            device=FakeDevice(platform="cpu", device_kind="cpu"),
            **LLAMA_125M)  # off-TPU: the guard does not apply
        # A TPU the tables do not know is an error, not a skipped guard
        # (and not a default peak): nothing may be measured against it.
        mystery = FakeDevice(device_kind="TPU v99 mystery")
        with pytest.raises(ValueError, match="not in training.memory"):
            bench_lm_mod.check_hbm_budget(
                batch=4096, remat=False, causal=True, force=False,
                device=mystery, **LLAMA_125M)
        with pytest.raises(ValueError, match="not in training.memory"):
            bench_lm_mod.peak_tflops(mystery)

    def test_force_overrides(self, bench_lm_mod):
        bench_lm_mod.check_hbm_budget(
            batch=4096, remat=False, causal=True, force=True,
            device=FakeDevice(), **LLAMA_125M)

    def test_generation_budgets(self, bench_lm_mod):
        """llama_1b no-remat (state ~17 GiB) refuses on v5e, fits v5p."""
        kw = dict(n_params=1_300_000_000, n_layers=16, d_model=2048,
                  batch=4, seq=2048, remat=False, causal=True, force=False)
        with pytest.raises(SystemExit):
            bench_lm_mod.check_hbm_budget(
                device=FakeDevice(device_kind="TPU v5 lite"), **kw)
        bench_lm_mod.check_hbm_budget(
            device=FakeDevice(device_kind="TPU v5p"), **kw)

    def test_per_head_scores_matter(self, bench_lm_mod):
        """BERT-style einsum attention (score_heads=num_heads) refuses a
        config the flash-path model would wave through."""
        kw = dict(n_params=110_000_000, n_layers=12, d_model=768,
                  batch=32, seq=512, remat=False, causal=False,
                  force=False, device=FakeDevice())
        bench_lm_mod.check_hbm_budget(score_heads=1, **kw)
        with pytest.raises(SystemExit):
            bench_lm_mod.check_hbm_budget(score_heads=12, **kw)

    def test_refusal_record_is_json(self, bench_lm_mod, capsys):
        import json

        with pytest.raises(SystemExit):
            bench_lm_mod.check_hbm_budget(
                batch=4096, remat=False, causal=True, force=False,
                device=FakeDevice(), **LLAMA_125M)
        rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert "error" in rec and rec["estimated_gib"] > rec["budget_gib"]


def test_bench_bert_smoke_on_cpu_mesh(bench_lm_mod):
    """End-to-end tiny BERT bench on the test mesh (conftest forces CPU):
    the record schema the docstring promises actually lands."""
    spec = importlib.util.spec_from_file_location(
        "bench_bert_under_test", os.path.join(_TOOLS, "bench_bert.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rec = mod.bench_bert("bert_tiny", batch=2, seq=32, warmup=1, iters=2)
    assert rec["unit"] == "samples/sec/chip"
    assert rec["value"] > 0 and rec["backend"] == "cpu"
    assert rec["n_params"] > 0


def test_bench_generate_cpu_smoke():
    """Decode-throughput tool: full prefill+scan path on CPU, one JSON
    record with the required fields."""
    import json
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "bench_generate.py"),
         "--preset", "llama_tiny", "--batch", "2", "--prompt-len", "16",
         "--max-new", "16", "--iters", "2", "--platform", "cpu"],
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-800:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["value"] > 0
    assert rec["unit"] == "tokens/sec/chip"
    assert rec["backend"] == "cpu"
    assert rec["max_new_tokens"] == 16


def test_bench_generate_int8_cpu_smoke():
    """--quant int8 runs the weight-only serving path end-to-end and
    stamps the record."""
    import json
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "bench_generate.py"),
         "--preset", "llama_tiny", "--batch", "2", "--prompt-len", "16",
         "--max-new", "16", "--iters", "2", "--platform", "cpu",
         "--quant", "int8"],
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-800:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["value"] > 0
    assert rec["quant"] == "int8"


def test_bench_generate_rejects_max_new_one():
    """--max-new 1 cannot measure a decode rate (it IS the prefill call);
    argparse rejects it cleanly instead of a ZeroDivisionError."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "bench_generate.py"),
         "--preset", "llama_tiny", "--max-new", "1", "--platform", "cpu"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 2  # argparse usage error
    assert "--max-new must be >= 2" in out.stderr


def test_bench_input_cpu_smoke():
    """Input-pipeline bench: all modes produce positive rates."""
    import json
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "bench_input.py"),
         "--records", "64", "--image-hw", "64", "--size", "32",
         "--batch", "16", "--workers", "2"],
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-800:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(rec["modes"]) == {"inprocess", "inprocess_u8", "workers2",
                                 "mmap_predecoded"}
    assert all(v > 0 for v in rec["modes"].values())
    assert rec["decode_modes"]["pil"] > 0
    if any(k.startswith("native") for k in rec["decode_modes"]):
        assert rec["decode_modes"]["native_t1"] > 0


def test_bench_moe_cpu_smoke():
    """MoE train-throughput tool: full jitted step on CPU, one JSON
    record with active-param accounting."""
    import json
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "bench_moe.py"),
         "--preset", "moe_tiny", "--batch-per-chip", "4", "--seq", "64",
         "--iters", "2", "--platform", "cpu"],
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-800:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["value"] > 0
    assert 0 < rec["n_active_params"] < rec["n_params"]


def test_bench_generate_moe_preset_cpu_smoke():
    """MoE presets decode through the same bench path (generate's
    config dispatch); llama-only flags are rejected for them."""
    import json
    import subprocess
    import sys

    base = [sys.executable, os.path.join(_TOOLS, "bench_generate.py"),
            "--preset", "moe_tiny", "--batch", "2", "--prompt-len", "8",
            "--max-new", "8", "--iters", "2", "--platform", "cpu"]
    out = subprocess.run(base, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-800:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["value"] > 0
    out = subprocess.run(base + ["--kv-cache", "int8"],
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "llama-family" in (out.stderr + out.stdout)


@pytest.fixture(scope="module")
def bench_mod():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(
            os.path.dirname(_TOOLS), "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def test_bench_emit_headline_is_bounded_and_last(bench_mod, capsys):
    """Tail-capture contract: whatever the record's size, bench.py's
    LAST stdout line is a compact parseable headline — BENCH_r04
    recorded parsed:null because one fat line was the last one."""
    import json

    fat = {
        "metric": bench_mod.HEADLINE_METRIC, "value": 1.0,
        "unit": "images/sec/chip", "vs_baseline": 0.0,
        "backend": "tpu", "device_kind": "TPU v5 lite",
        "error": "x" * 500,
        "configs": {f"cfg{i}": {"v": i, "pad": "y" * 400}
                    for i in range(30)},
    }
    bench_mod._emit(fat)
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[0]) == fat     # full record, verbatim
    head = json.loads(lines[-1])           # last line parses
    assert len(lines[-1]) < 1000           # and is bounded
    assert head["value"] == 1.0 and head["device_kind"] == "TPU v5 lite"
    assert "configs" not in head and len(head["error"]) <= 160


def test_bench_without_a_chip_fails_and_says_so(bench_mod, capsys,
                                                monkeypatch):
    """No CPU fallback and no echo of an older result: off-TPU the run
    exits non-zero, its record names the backend it found, and a failed
    family child fails the run too.  (This process is on the CPU
    backend, so the parent's own platform check fires for real.)"""
    import json

    ran = []

    def fake_family(cmd, timeout_s):
        ran.append(cmd)
        return None, "RuntimeError: Unable to initialize backend 'tpu'"

    monkeypatch.setattr(bench_mod, "_run_family", fake_family)
    rc = bench_mod.main(["--families", "lm,resnet"])
    rec, head = map(json.loads,
                    capsys.readouterr().out.strip().splitlines()[-2:])
    assert rc == 1
    # Chip-needing children are told to fail without a chip.
    assert ran and ran[0][-2:] == ["--platform", "tpu"]
    assert set(rec["failed_configs"]) == {"lm", "resnet"}
    assert rec["backend"] == "cpu" and rec["value"] == 0.0
    assert "fallback" not in rec and "last_known_tpu" not in rec
    assert head["value"] == 0.0 and "error" in head


def test_bench_unknown_device_kind_is_an_error():
    """The peak is keyed by device_kind; a kind the tables do not know
    raises instead of defaulting (bench.py used to assume any TPU was a
    v5e and any other platform 1e9 TFLOP/s)."""
    from tensorflow_train_distributed_tpu.training.memory import tpu_peaks

    assert tpu_peaks("TPU v5 lite")["peak_tflops"] == 197.0
    assert tpu_peaks("TPU v5 lite")["hbm_bytes_per_sec"] == 819e9
    with pytest.raises(ValueError, match="not in training.memory"):
        tpu_peaks("TPU v99 mystery")


# ── decode MBU fields (the serving benches' shared byte model) ─────────


class TestDecodeMbuFields:
    """``bench_gateway.decode_mbu_fields`` — the model-bandwidth
    companion every serving record now carries: the byte model follows
    bench_generate's convention (cast params once + the slot-grid KV
    working set per decode step; int8 halves rows and adds f32
    scales), and off-TPU ``mbu_pct`` is honestly null, never a made-up
    number."""

    @pytest.fixture(scope="class")
    def mbu_mod(self):
        spec = importlib.util.spec_from_file_location(
            "bench_gateway_under_test",
            os.path.join(_TOOLS, "bench_gateway.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    @pytest.fixture(scope="class")
    def cfg(self):
        from tensorflow_train_distributed_tpu.models.llama import (
            LLAMA_PRESETS,
        )

        return LLAMA_PRESETS["llama_tiny"]

    def test_byte_model_and_cpu_null(self, mbu_mod, cfg):
        import jax.numpy as jnp

        n_params, slots, rows = 1000, 4, 64
        out = mbu_mod.decode_mbu_fields(cfg, n_params, slots, rows,
                                        tokens_per_sec=100.0)
        itemsize = jnp.dtype(cfg.dtype).itemsize
        kvh = cfg.num_kv_heads or cfg.num_heads
        hd = cfg.d_model // cfg.num_heads
        want = (n_params * itemsize
                + 2 * cfg.num_layers * slots * rows * kvh * hd
                * itemsize)
        assert out["decode_bytes_per_step"] == want
        assert out["mbu_pct"] is None      # CPU: no bandwidth table

    def test_int8_halves_rows_adds_scales(self, mbu_mod, cfg):
        import jax.numpy as jnp

        n_params, slots, rows = 1000, 4, 64
        fp = mbu_mod.decode_mbu_fields(cfg, n_params, slots, rows,
                                       100.0)
        q8 = mbu_mod.decode_mbu_fields(cfg, n_params, slots, rows,
                                       100.0, kv_int8=True)
        itemsize = jnp.dtype(cfg.dtype).itemsize
        kvh = cfg.num_kv_heads or cfg.num_heads
        hd = cfg.d_model // cfg.num_heads
        kv_rows = 2 * cfg.num_layers * slots * rows * kvh
        assert (fp["decode_bytes_per_step"]
                - q8["decode_bytes_per_step"]
                == kv_rows * hd * (itemsize - 1) - kv_rows * 4)
