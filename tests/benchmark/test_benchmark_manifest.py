"""``BENCHMARK.json`` against the contract's rules of form, every cell's
files found by name, the real command refusing to run without a TPU;
and the harness driven by data: a cell added by files and manifest
entries alone is found and run end to end at test size on the CPU, with
``correct`` coming out false for the lower-precision control and for the
timed path broken underneath a whole run."""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark.harness.manifest import Manifest, ManifestError
from cellkit import REPO, run_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return Manifest(REPO)


def test_top_level_keys_and_limits(man):
    d = man.data
    assert set(d) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(d["paths"]) <= 16 and len(d["command"]) <= 32
    for p in d["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in d["end_to_end"])


def test_every_name_unit_and_line_fits_the_contract(man):
    d = man.data
    names = []
    for section, keys in (
            ("configs", {"name", "source", "file", "reduced", "why"}),
            ("workloads", {"name", "config", "traffic", "chips", "why"}),
            ("end_to_end", {"name", "unit", "better", "bound", "source",
                            "workloads"}),
            ("per_layer", {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"})):
        for e in d[section]:
            assert set(e) <= keys, (section, e)
            assert NAME.match(e["name"]), e["name"]
            names.append((section in ("end_to_end", "per_layer"),
                          section, e["name"]))
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200
                    assert "\n" not in e[key] and "\t" not in e[key]
    metric_names = [n for is_m, _, n in names if is_m]
    assert len(set(metric_names)) == len(metric_names)
    for m in d["end_to_end"] + d["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in d["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for c in d["configs"]:
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in d["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in d["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in d["workloads"])
    assert four <= max(1, len(d["workloads"]) // 4)


def test_every_cell_finds_its_files_by_name(man):
    d = man.data
    used = {w["config"] for w in d["workloads"]}
    assert used == {c["name"] for c in d["configs"]}
    files = [c["file"] for c in d["configs"]]
    assert len(set(files)) == len(files)
    for c in d["configs"]:
        assert any(c["file"].startswith(p + "/") for p in d["paths"])
        cfg = man.config(c["name"])
        # every cut is in the file, and the file is what is run
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(cfg["reduced"]) <= set(cfg["changed"])
        for key in cfg["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden_size|"
                                 r"intermediate_size|head)", key)
    for w in d["workloads"]:
        traffic = man.traffic(w["traffic"])
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "harness", traffic["kind"] + ".py"))
        e2e = man.end_to_end_for(w["name"])
        layer = man.per_layer_for(w["name"])
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert layer
        for m in layer:
            assert callable(man.layer_reader(m["name"]))
    with pytest.raises(ManifestError):
        man.workload("no-such-cell")
    with pytest.raises(ManifestError):
        man.layer_reader("no_such_metric")


def test_each_per_layer_metric_moves_a_metric_its_cells_report(man):
    d = man.data
    e2e = {m["name"]: m for m in d["end_to_end"]}
    cells = [w["name"] for w in d["workloads"]]
    for m in d["per_layer"]:
        assert m["moves"] in e2e, m
        target = e2e[m["moves"]]
        reports = target.get("workloads", cells)
        for cell in m.get("workloads", reports):
            assert cell in cells and cell in reports, (m["name"], cell)
    layers = {m["layer"] for m in d["per_layer"]}
    perf = open(os.path.join(REPO, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"PERF.md's layer list lacks {layer!r}"


def test_configuration_file_is_what_the_program_runs(man):
    from benchmark.harness import program

    for c in man.data["configs"]:
        cfg_file = man.config(c["name"])
        cfg = program.llama_config(cfg_file)
        assert cfg.num_layers == cfg_file["num_hidden_layers"]
        with pytest.raises(ValueError, match="would run"):
            program.llama_config(dict(cfg_file, hidden_size=1234))


def test_the_real_command_refuses_to_run_without_a_tpu():
    cell = Manifest(REPO).data["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert proc.returncode != 0
    assert "need 'tpu'" in proc.stderr
    for line in proc.stdout.splitlines():
        assert "\"correct\"" not in line


def _compared(earlier, phase="check"):
    rec = next(r for r in earlier if r.get("phase") == phase)
    return {row["number"]: row for row in rec["compared"]}


def test_a_serving_cell_added_by_files_alone_runs_end_to_end(
        cell_root, capsys):
    root = cell_root("tiny.closed", "tiny", "tiny-closed", 1,
                     ["serve_tokens_per_s", "gap_p95_ms"])
    rc, result, earlier = run_cell(root, "tiny.closed", capsys=capsys)
    assert rc == 0
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "gap_p95_ms",
                                      "setup_s"}
    assert result["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert result["device"]["platform"] == "cpu"
    window = next(r for r in earlier if r.get("phase") == "window")
    assert window["compiles_in_window"] == 0
    checked = next(r for r in earlier if r.get("phase") == "check")
    assert {row["number"] for row in checked["compared"]} == \
        {"served_gap_max", "served_gap_mean"}
    assert all(row["limit"] is not None for row in checked["compared"])


def test_an_open_loop_cell_added_by_files_alone_reports_ttft(
        cell_root, capsys):
    root = cell_root("tiny.open", "tiny", "tiny-open", 1, ["ttft_p95_ms"])
    rc, result, earlier = run_cell(root, "tiny.open", capsys=capsys)
    assert rc == 0 and result["correct"] is True, (result, [r for r in earlier if r.get("phase") == "window"])
    assert set(result["metrics"]) == {"ttft_p95_ms", "setup_s"}
    window = next(r for r in earlier if r.get("phase") == "window")
    assert window["generator_late_ms"]["n"] == result["attempted"]


def test_int8_engine_fails_the_limits_the_sound_engine_passes(
        cell_root, capsys):
    root = cell_root("tiny.closed", "tiny", "tiny-closed", 1,
                     ["serve_tokens_per_s", "gap_p95_ms"])
    rc, sound, earlier = run_cell(root, "tiny.closed", seed=21,
                                  capsys=capsys)
    assert rc == 0 and sound["correct"] is True
    sound_rows = _compared(earlier)
    rc, control, earlier = run_cell(root, "tiny.closed", seed=21,
                                    extra=["--control", "int8"],
                                    capsys=capsys)
    assert rc == 0 and control["correct"] is False
    rows = _compared(earlier)
    assert any(not row["within"] for row in rows.values())
    # the lower precision moves the number by far more than the limit's
    # room above the sound run
    assert rows["served_gap_max"]["value"] > \
        3 * max(sound_rows["served_gap_max"]["value"], 1e-4)


def test_a_token_altered_where_it_is_produced_makes_the_run_incorrect(
        cell_root, capsys, monkeypatch):
    from tensorflow_train_distributed_tpu.serving import ServingEngine

    def second_best(self, logits, seeds, counts):
        logits = logits.astype(jnp.float32)
        top = jnp.argmax(logits, axis=-1)
        hit = jax.nn.one_hot(top, logits.shape[-1], dtype=jnp.bool_)
        return jnp.argmax(jnp.where(hit, -jnp.inf, logits),
                          axis=-1).astype(jnp.int32)

    monkeypatch.setattr(ServingEngine, "_pick", second_best)
    root = cell_root("tiny.closed", "tiny", "tiny-closed", 1,
                     ["serve_tokens_per_s", "gap_p95_ms"])
    rc, result, earlier = run_cell(root, "tiny.closed", seed=22,
                                   capsys=capsys)
    assert rc == 0                      # the run itself goes through,
    assert result["failed"] == 0        # every request completes,
    assert result["correct"] is False   # and the comparison catches it
    rows = _compared(earlier)
    assert not rows["served_gap_mean"]["within"]
    checked = next(r for r in earlier if r.get("phase") == "check")
    assert checked["not_first_choice"] == checked["served_tokens"]
