"""The plumbing a chip run depends on: where the compile cache lives,
that ``chip_smoke.py`` fails without a chip (and rehearses on the CPU),
and that a parent which starts chip-needing children stays off JAX."""

import json
import os
import re
import subprocess
import sys

import jax
import pytest

from tensorflow_train_distributed_tpu.runtime import compile_cache

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


_TINY = ["--rehearse-cpu", "--config", "llama_tiny_sft"]
_SUPERVISE = (
    "import sys\n"
    "from tensorflow_train_distributed_tpu import launch\n"
    "from tensorflow_train_distributed_tpu.runtime import supervisor\n"
    "seen = {}\n"
    "def fake(argv, args):\n"
    "    seen['argv'] = argv\n"
    "    return 0\n"
    "supervisor.supervise_cli = fake\n"
    "rc = launch.main(['--config', 'mnist', '--steps', '1',\n"
    "                  '--supervise', '--platform', 'tpu'])\n"
    "from jax._src import xla_bridge\n"
    "print(rc, xla_bridge.backends_are_initialized(), seen['argv'])\n")
# Every child process this module needs: (argv, extra env).
_CHILDREN = {
    "no-chip": (["chip_smoke.py"], {}),
    "one-chip": (["chip_smoke.py", *_TINY, "--steps", "3", "--slots", "2",
                  "--chunk", "4", "--cache-len", "64"], {}),
    "four-chips": (["chip_smoke.py", *_TINY, "--chips", "4",
                    "--mesh-steps", "2"], {}),
    "conftest": (["-c", "import tests.conftest, jax; "
                        "print(jax.config.jax_compilation_cache_dir)"],
                 {compile_cache.ENV_VAR: "/placed/from/outside"}),
    "supervise": (["-c", _SUPERVISE], {}),
}


class _Done:
    def __init__(self, proc):
        self.stdout, self.stderr = proc.communicate(timeout=300)
        self.returncode = proc.returncode


@pytest.fixture(scope="module")
def child():
    """All of them started at once (tier-1 pays the slowest, ~25 s, not
    the sum), on the CPU backend with no outside cache directory."""
    procs = {}
    for name, (argv, extra) in _CHILDREN.items():
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop(compile_cache.ENV_VAR, None)
        env.update(extra)
        procs[name] = subprocess.Popen(
            [sys.executable, *argv], cwd=REPO, env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    done = {}
    try:
        yield lambda name: done.setdefault(name, _Done(procs[name]))
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
                proc.communicate()


# ── compile cache ───────────────────────────────────────────────────────


@pytest.fixture
def cache_config_restored():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_env_var_set_means_hands_off(monkeypatch,
                                           cache_config_restored):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/placed/from/outside")
    assert compile_cache.place_compile_cache() == "/placed/from/outside"
    # ...and no directory was set in code.
    assert jax.config.jax_compilation_cache_dir == cache_config_restored


def test_cache_unset_is_one_fixed_path_in_the_checkout(
        monkeypatch, cache_config_restored):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.REPO_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    for _ in range(2):      # idempotent: never a temp name, pid or time
        assert (compile_cache.place_compile_cache()
                == compile_cache.REPO_CACHE_DIR)
        assert (jax.config.jax_compilation_cache_dir
                == compile_cache.REPO_CACHE_DIR)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_conftest_does_not_override_an_outside_cache_dir(child):
    out = child("conftest")
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == "/placed/from/outside"


def test_only_the_helper_sets_a_cache_dir():
    """With the variable set no code path may set another directory:
    the config key appears in the helper and in tests, nowhere else."""
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        for name in files:
            if name.endswith((".py", ".sh")):
                path = os.path.join(root, name)
                with open(path, errors="replace") as f:
                    if "jax_compilation_cache_dir" in f.read():
                        hits.append(os.path.relpath(path, REPO))
    assert sorted(hits) == [
        "tensorflow_train_distributed_tpu/runtime/compile_cache.py",
        "tests/test_chip_plumbing.py"]


# ── chip_smoke.py ───────────────────────────────────────────────────────


def test_chip_smoke_without_a_chip_fails_and_prints_no_result(child):
    out = child("no-chip")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "Unable to initialize backend 'tpu'" in out.stderr


@pytest.mark.parametrize("name,phases", [
    ("one-chip", ["device", "kernels", "train", "serve", "summary"]),
    ("four-chips", ["device", "mesh", "summary"]),
])
def test_chip_smoke_rehearses_on_cpu_and_still_fails(name, phases, child):
    """The no-chip rehearsal runs every phase's control flow at
    llama_tiny_sft size — and proves the script, never the chip: the
    exit code is non-zero and the result line says ``"ok": false``."""
    out = child(name)
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    assert [r["phase"] for r in lines[:-1]] == phases, out.stderr[-3000:]
    assert all(r["ok"] for r in lines[:-2]), lines
    chips = 4 if name == "four-chips" else 1
    assert lines[-1] == {
        "ok": False, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": chips}}
    assert out.returncode != 0
    assert not re.search(r'"ok":\s*true\s*,\s*"device"', out.stdout)
    by = {r["phase"]: r for r in lines[:-1]}
    if chips == 4:
        assert by["mesh"]["param_devices"] == [0, 1, 2, 3]
        assert by["mesh"]["max_abs_diff"] <= by["mesh"]["tolerance"]
    else:
        assert by["kernels"]["paged_kv_gather_exact"] is True
        assert len(by["train"]["losses"]) == 3
        assert by["serve"]["greedy_vs_generate"] == "equal"
        assert by["serve"]["tokens_counted"] == sum(by["serve"]["max_new"])


# ── one process per chip ────────────────────────────────────────────────


def test_supervising_parent_stays_off_the_backend(child):
    """``launch.py --supervise`` re-execs the CLI as a child that needs
    the chip, so the parent must never initialize a JAX backend (a
    parent that has touched JAX holds the chip)."""
    out = child("supervise")
    assert out.returncode == 0, out.stderr[-2000:]
    rc, touched, *_ = out.stdout.split()
    assert (rc, touched) == ("0", "False"), out.stdout
