"""Self-healing supervisor tests: exit classification, restart budget,
backoff, journal — and the chaos headline: a supervised run with an
injected ``kill -9`` plus a torn latest checkpoint finishes with params
bitwise-identical to an uninterrupted run.

The unit tier drives ``TrainSupervisor`` over throwaway ``python -c``
children (no jax import — milliseconds per case).  The recovery tier
uses real CLI children: a ``testing.multiprocess`` worker SIGKILLed
mid-epoch then resumed, and ``tools/chaos_check.py`` (the CI smoke
tool) for the end-to-end parity proof.
"""

import importlib.util
import json
import os
import pathlib
import signal
import sys
import time

import pytest

from tensorflow_train_distributed_tpu.runtime.preemption import (
    PREEMPTION_EXIT_CODE,
)
from tensorflow_train_distributed_tpu.runtime.supervisor import (
    TrainSupervisor,
    classify_exit,
    strip_supervisor_flags,
)

REPO_ROOT = str(pathlib.Path(__file__).resolve().parents[1])
_TOOLS = os.path.join(REPO_ROOT, "tools")


@pytest.fixture(scope="module", autouse=True)
def _eight_cpu_devices_again():
    """``tools/chaos_check.py`` re-targets this process to 2 CPU devices
    for its in-process parity read (orbax rebuilds shardings from the
    checkpoint's sharding file, which names the child CLIs' devices).
    Put the suite's 8 back when the module ends: under xdist the worker
    is handed another file next, and most need them."""
    yield
    from tensorflow_train_distributed_tpu.runtime.mesh import (
        force_platform,
    )

    force_platform("cpu", 8)


def _child(code: str) -> list:
    return [sys.executable, "-c", code]


def _counter_child(tmp_path, rcs) -> list:
    """A child whose exit code follows ``rcs`` across attempts (state
    in a counter file — each launch is a fresh process)."""
    counter = tmp_path / "attempt_counter"
    code = (
        "import pathlib, sys\n"
        f"p = pathlib.Path({str(counter)!r})\n"
        "n = int(p.read_text()) if p.exists() else 0\n"
        "p.write_text(str(n + 1))\n"
        f"rcs = {list(rcs)!r}\n"
        "sys.exit(rcs[min(n, len(rcs) - 1)])\n"
    )
    return _child(code)


class TestClassification:
    def test_exit_codes(self):
        assert classify_exit(0) == "clean"
        assert classify_exit(PREEMPTION_EXIT_CODE) == "preemption"
        assert classify_exit(1) == "crash"
        assert classify_exit(-signal.SIGKILL) == "crash"
        assert classify_exit(-signal.SIGSEGV) == "crash"

    def test_strip_supervisor_flags(self):
        argv = ["--config", "mnist", "--supervise", "--max-restarts", "5",
                "--restart-backoff=0.1", "--steps", "8",
                "--supervisor-journal", "/tmp/j.jsonl",
                "--no-restart-on-preemption", "--checkpoint-dir", "/ck"]
        assert strip_supervisor_flags(argv) == [
            "--config", "mnist", "--steps", "8",
            "--checkpoint-dir", "/ck"]


class TestSupervisorLoop:
    def test_clean_exit_single_attempt(self, tmp_path):
        res = TrainSupervisor(_child("raise SystemExit(0)"),
                              backoff_s=0.0).run()
        assert (res.returncode, res.attempts, res.crashes) == (0, 1, 0)
        assert not res.gave_up

    def test_crash_relaunch_until_clean(self, tmp_path):
        journal = tmp_path / "j.jsonl"
        sleeps = []
        res = TrainSupervisor(
            _counter_child(tmp_path, [7, 7, 0]),
            max_restarts=3, backoff_s=0.5, backoff_jitter=0.0,
            journal_path=str(journal),
            sleep=sleeps.append).run()
        assert res.returncode == 0
        assert res.attempts == 3 and res.crashes == 2
        # jitter=0 pins the exact exponential; the jittered default is
        # bounded/seeded-pinned in test_preemption.py's storm tests.
        assert sleeps == [0.5, 1.0]       # exponential, per crash
        events = [json.loads(line) for line in journal.read_text().splitlines()]
        assert [e["class"] for e in events if e["event"] == "exit"] == [
            "crash", "crash", "clean"]
        assert events[-1]["event"] == "done"

    def test_budget_exhausted_gives_up_with_last_rc(self, tmp_path):
        journal = tmp_path / "j.jsonl"
        res = TrainSupervisor(
            _child("raise SystemExit(9)"), max_restarts=1,
            backoff_s=0.0, journal_path=str(journal)).run()
        assert res.gave_up and res.returncode == 9
        assert res.attempts == 2 and res.crashes == 2
        events = [json.loads(line) for line in journal.read_text().splitlines()]
        assert events[-1]["event"] == "giveup"

    def test_preemption_does_not_consume_crash_budget(self, tmp_path):
        # Two preemptions, then a crash, with a ZERO crash budget: the
        # preemptions must both relaunch for free and only the real
        # crash ends the loop.
        res = TrainSupervisor(
            _counter_child(tmp_path,
                           [PREEMPTION_EXIT_CODE, PREEMPTION_EXIT_CODE, 5]),
            max_restarts=0, backoff_s=0.0).run()
        assert res.preemptions == 2 and res.crashes == 1
        assert res.attempts == 3
        assert res.gave_up and res.returncode == 5

    def test_no_restart_on_preemption_hands_code_up(self, tmp_path):
        res = TrainSupervisor(
            _child(f"raise SystemExit({PREEMPTION_EXIT_CODE})"),
            restart_on_preemption=False, backoff_s=0.0).run()
        assert res.returncode == PREEMPTION_EXIT_CODE
        assert res.attempts == 1 and not res.gave_up

    def test_stop_signal_during_backoff_blocks_relaunch(self, tmp_path):
        # A SIGTERM landing while NO child is live (mid-backoff) has
        # nothing to forward to — the loop must stop instead of
        # launching a fresh child against the scheduler's kill.
        journal = tmp_path / "j.jsonl"

        def stop_mid_backoff(seconds):
            sup._stop_signal = signal.SIGTERM

        sup = TrainSupervisor(
            _child("raise SystemExit(3)"), max_restarts=5,
            backoff_s=0.5, journal_path=str(journal),
            sleep=stop_mid_backoff)
        res = sup.run()
        assert res.attempts == 1 and res.crashes == 1
        assert res.returncode == 128 + signal.SIGTERM
        assert not res.gave_up
        events = [json.loads(line)
                  for line in journal.read_text().splitlines()]
        assert events[-1]["event"] == "stopped"

    def test_attempt_env_exported(self, tmp_path):
        out = tmp_path / "attempts.txt"
        code = (
            "import os, pathlib, sys\n"
            f"p = pathlib.Path({str(out)!r})\n"
            "with p.open('a') as f:\n"
            "    f.write(os.environ['TTD_SUPERVISE_ATTEMPT'] + '\\n')\n"
            "sys.exit(3 if p.read_text().count('\\n') < 2 else 0)\n"
        )
        res = TrainSupervisor(_child(code), max_restarts=2,
                              backoff_s=0.0).run()
        assert res.returncode == 0
        assert out.read_text().splitlines() == ["0", "1"]


# --- recovery tier: real CLI children ---------------------------------------


def _resume_after_kill(rank, ckpt_dir, extra_steps):
    """Worker: resume the killed run and train ``extra_steps`` past the
    latest retained checkpoint (restore may legitimately fall back
    below it if the kill tore the newest save — that is the point)."""
    from tensorflow_train_distributed_tpu import launch

    steps = sorted(int(p.name) for p in pathlib.Path(ckpt_dir).iterdir()
                   if p.name.isdigit())
    target = steps[-1] + extra_steps
    result = launch.run(launch.build_parser().parse_args([
        "--config", "mnist", "--steps", str(target),
        "--global-batch-size", "16", "--log-every", "1",
        "--checkpoint-dir", ckpt_dir, "--checkpoint-every", "2"]))
    return {"latest_before": steps[-1], "target": target,
            "final_step": int(result.state.step)}


def _train_victim(rank, ckpt_dir):
    """Worker: train far longer than the parent lets it live."""
    from tensorflow_train_distributed_tpu import launch

    launch.run(launch.build_parser().parse_args([
        "--config", "mnist", "--steps", "2000",
        "--global-batch-size", "16", "--log-every", "1",
        "--checkpoint-dir", ckpt_dir, "--checkpoint-every", "2"]))
    return {"finished": True}


def test_kill9_mid_epoch_resume(tmp_path):
    """SIGKILL a training process mid-epoch (real subprocess via
    testing.multiprocess), then resume: the relaunch restores a
    retained step — falling back past any save the kill tore — and
    trains on to the new target."""
    from tensorflow_train_distributed_tpu.testing import (
        MultiProcessRunner, UnexpectedExitError,
    )

    ck = tmp_path / "ck"
    victim = MultiProcessRunner(
        "test_supervisor:_train_victim", 1, local_devices=2,
        init_distributed=False, timeout=240,
        payload={"ckpt_dir": str(ck)}).start()
    # Wait for a COMMITTED step >= 4 (marker present), then kill -9.
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        committed = [int(p.name) for p in ck.glob("[0-9]*")
                     if p.name.isdigit()
                     and (p / "_CHECKPOINT_METADATA").exists()]
        if committed and max(committed) >= 4:
            break
        time.sleep(0.05)
    else:
        victim.terminate(0)
        pytest.fail("victim never committed a step-4 checkpoint")
    victim.terminate(0, signal.SIGKILL)
    with pytest.raises(UnexpectedExitError) as ei:
        victim.join()
    assert ei.value.results[0].returncode == -signal.SIGKILL

    results = MultiProcessRunner(
        "test_supervisor:_resume_after_kill", 1, local_devices=2,
        init_distributed=False, timeout=240,
        payload={"ckpt_dir": str(ck), "extra_steps": 4}).run()
    v = results[0].value
    assert v["latest_before"] >= 4
    assert v["final_step"] == v["target"]
    # Mid-epoch by construction: mnist at batch 16 has 32 steps/epoch.
    assert v["latest_before"] < 32


def test_chaos_parity_kill9_plus_torn_checkpoint(tmp_path):
    """The headline acceptance: supervised run + injected kill -9 at a
    mid-run step + the latest checkpoint made torn → supervisor
    relaunches, restore quarantines the torn step and falls back, and
    the finished run's params are BITWISE-identical to the same config
    run uninterrupted.  Drives tools/chaos_check.py — the same
    one-command smoke CI uses."""
    spec = importlib.util.spec_from_file_location(
        "chaos_check_under_test", os.path.join(_TOOLS, "chaos_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    verdict = mod.run_chaos_check(str(tmp_path))
    assert verdict["ok"], verdict
    assert verdict["checks"]["params_bitwise_equal"]
    assert verdict["checks"]["bad_step_quarantined"]
    assert verdict["checks"]["killed_then_clean"]
