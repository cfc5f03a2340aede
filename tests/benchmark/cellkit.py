"""Helpers of the benchmark's own tests: where things are, and driving
``benchmark/run.py`` past its look for a chip.  Nothing here describes a
TPU topology or touches a backend at import."""

import json
import os
import shutil
import sys
import threading

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cells")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

E2E = {
    "serve_tokens_per_s": ("tokens/s", "higher"),
    "gap_p95_ms": ("ms", "lower"),
    "ttft_p95_ms": ("ms", "lower"),
}


def make_cell_root(tmp_path):
    """A checkout-shaped temporary directory: the real manifest and the
    real benchmark files, plus whatever cells a test adds BY FILES AND
    MANIFEST ENTRIES ALONE through the returned ``add`` function."""
    root = tmp_path / "checkout"
    (root / "benchmark").mkdir(parents=True)
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmark", sub),
                        root / "benchmark" / sub)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)

    def add(cell, config, traffic, chips, metrics, per_layer=()):
        shutil.copy(os.path.join(CELLS, config + ".json"),
                    root / "benchmark" / "configs" / (config + ".json"))
        shutil.copy(os.path.join(CELLS, traffic + ".traffic.json"),
                    root / "benchmark" / "traffic" / (traffic + ".json"))
        manifest["configs"].append(
            {"name": config, "source": "tests", "reduced": [],
             "file": f"benchmark/configs/{config}.json", "why": "test size"})
        manifest["workloads"].append(
            {"name": cell, "config": config, "traffic": traffic,
             "chips": chips, "why": "test size"})
        for name in metrics:
            entry = next((m for m in manifest["end_to_end"]
                          if m["name"] == name), None)
            if entry is None:
                unit, better = E2E[name]
                entry = {"name": name, "unit": unit, "better": better,
                         "bound": 0.05, "source": "host_clock",
                         "workloads": []}
                manifest["end_to_end"].insert(0, entry)
            entry.setdefault("workloads", []).append(cell)
        for name in per_layer:
            next(m for m in manifest["per_layer"]
                 if m["name"] == name).setdefault("workloads",
                                                  []).append(cell)
        with open(root / "BENCHMARK.json", "w") as f:
            json.dump(manifest, f)
        return str(root)

    return add


def run_cell(root, cell, seed=7, seconds=1.5, extra=(), capsys=None):
    """Drive ``benchmark/run.py``'s ``main`` past its look for a chip
    (the tests' CPU path; the real command has no such switch) and
    return (exit code, result line as a dict, earlier lines)."""
    from benchmark import run

    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", "0", *extra], root=root,
                  require_platform=None)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    return rc, lines[-1], lines[:-1]


# -- a stand-in for the driver, for the load generator's tests --------------

CLOSED = {"loop": "closed", "callers": 5, "pool": 64, "mix_seed": 11,
          "ramp_s": 0.0,
          "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.7,
                         "min": 8, "max": 200},
          "output_len": {"dist": "uniform", "min": 4, "max": 12}}
OPEN = dict(CLOSED, loop="open", ramp_s=0.2,
            arrivals={"process": "poisson", "rate_per_s": 40.0})


class FakeHandle:
    def __init__(self, chunks):
        self.chunks = chunks
        self.abandoned = threading.Event()

    def iter_tokens(self):
        for toks, delay in self.chunks:
            if self.abandoned.wait(delay):
                raise RuntimeError("deadline")
            yield toks


class FakeDriver:
    """Commits ``chunk`` tokens every ``period`` seconds per request;
    refuses past ``limit`` requests in flight."""

    def __init__(self, period=0.01, chunk=4, limit=10 ** 9):
        self.period, self.chunk, self.limit = period, chunk, limit
        self.lock = threading.Lock()
        self.live = self.peak = 0

    def submit(self, prompt, max_new):
        with self.lock:
            if self.live >= self.limit:
                raise RuntimeError("AdmissionFull")
            self.live += 1
            self.peak = max(self.peak, self.live)
        chunks = []
        left = max_new
        while left > 0:
            n = min(self.chunk, left)
            chunks.append((list(range(n)), self.period))
            left -= n
        handle = FakeHandle(chunks)
        orig = handle.iter_tokens

        def wrapped():
            try:
                yield from orig()
            finally:
                with self.lock:
                    self.live -= 1
        handle.iter_tokens = wrapped
        return handle

    def abandon(self, handle):
        handle.abandoned.set()


def run_load(traffic, driver, seconds=0.6, drain=0.0, seed=9):
    from benchmark.harness import loadgen

    sched = loadgen.Schedule(traffic, seed, seconds, 1000)
    load = loadgen.LoadRun(sched, driver.submit, driver.abandon, seconds,
                           drain_s=drain)
    t_open = load.start()
    load.wait_window()
    load.finish(join_timeout=10)
    return load, loadgen.window_metrics(load.records, t_open, seconds,
                                        sched.loop)


FIXTURE = os.path.join(REPO, "benchmark", "fixtures", "small_trace.json")


# -- the program and the reference at a tiny size ---------------------------

CASES = {
    "plain": dict(qkv_bias=False, sliding_window=None),
    "bias": dict(qkv_bias=True, sliding_window=None),
    "window": dict(qkv_bias=False, sliding_window=8),
    "bias+window": dict(qkv_bias=True, sliding_window=8),
}


def tiny_setup(case):
    """(llama module, program config, configuration-file keys, seeded
    float32 weights) of a two-layer model with or without the q/k/v
    bias and the sliding window."""
    import dataclasses

    import jax.numpy as jnp

    from tensorflow_train_distributed_tpu.models import llama

    from benchmark.harness import program, weights

    cfg = dataclasses.replace(llama.LLAMA_PRESETS["llama_tiny_scan"],
                              remat=False, **CASES[case])
    file_cfg = {"hidden_size": 64, "num_attention_heads": 4,
                "num_key_value_heads": 2, "intermediate_size": 128,
                "vocab_size": 256, "num_hidden_layers": 2,
                "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
                "sliding_window": CASES[case]["sliding_window"],
                "attention_bias": CASES[case]["qkv_bias"]}
    params = weights.make_params(program.param_shapes(cfg), 2 ** 33 + 7,
                                 jnp.float32)
    return llama, cfg, file_cfg, params
