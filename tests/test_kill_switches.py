"""Kill-switch audit backfill: every ``TTD_*`` flag ttd-lint found
referenced-but-untested gets its minimal exercising test here (the
lint's "exercised by at least one test" evidence is REAL behavior, not
a name-drop: each test drives the flag through its reader).
"""

import importlib
import json
import os
import subprocess
import sys

from tensorflow_train_distributed_tpu.runtime import faults
from tensorflow_train_distributed_tpu.testing import multiprocess

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# ── TTD_FAULT_PLAN ─────────────────────────────────────────────────────


def test_fault_plan_armed_from_env(monkeypatch):
    monkeypatch.setenv("TTD_FAULT_PLAN", "step:3:raise")
    try:
        plan = faults.arm_from_env()
        assert plan is not None
        assert faults.ARMED
    finally:
        faults.disarm()
    assert not faults.ARMED
    # Unset env arms nothing.
    monkeypatch.delenv("TTD_FAULT_PLAN")
    assert faults.arm_from_env() is None
    assert not faults.ARMED


# ── TTD_TRACE_CAPACITY ─────────────────────────────────────────────────


def test_trace_capacity_sizes_the_recorder_ring():
    """Read at events-module import — pin it in a child interpreter so
    this process's live recorder is untouched."""
    env = dict(os.environ, TTD_TRACE_CAPACITY="123",
               JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         "from tensorflow_train_distributed_tpu.runtime import events;"
         "r = events.get_recorder();"
         "print(r.capacity);"
         "[events.instant('t/x', i=i) for i in range(200)];"
         "print(len(r))"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    capacity, length = out.stdout.split()
    assert capacity == "123"
    assert length == "123"          # ring bounded at the override


# ── TTD_TEST_LOCAL_DEVICES / TTD_TEST_INIT_DISTRIBUTED / TTD_RESULT ────


class _FakeProc:
    """Popen stand-in: captures env, emits a tagged result line."""

    captured = []

    def __init__(self, cmd, env=None, **kw):
        _FakeProc.captured.append(env)
        self.returncode = 0
        self._out = "noise\n" + multiprocess._RESULT_TAG \
            + json.dumps({"rank_ok": True}) + "\n"

    def communicate(self, timeout=None):
        return self._out, ""

    def poll(self):
        return self.returncode


def test_multiprocess_child_env_and_result_tag(monkeypatch):
    """The runner exports ``TTD_TEST_LOCAL_DEVICES`` /
    ``TTD_TEST_INIT_DISTRIBUTED`` to each child and parses the child's
    ``TTD_RESULT:`` stdout line back into ``ProcessResult.value`` —
    pinned against a stub Popen so no cluster spawns in tier-1 (the
    multihost-marked tests drive the real thing)."""
    _FakeProc.captured = []
    monkeypatch.setattr(multiprocess.subprocess, "Popen", _FakeProc)
    runner = multiprocess.MultiProcessRunner(
        "mod:fn", 2, local_devices=3, init_distributed=False,
        timeout=5.0)
    results = runner.run()
    assert len(_FakeProc.captured) == 2
    for env in _FakeProc.captured:
        assert env["TTD_TEST_LOCAL_DEVICES"] == "3"
        assert env["TTD_TEST_INIT_DISTRIBUTED"] == "0"
    assert [r.value for r in results] == [{"rank_ok": True}] * 2

    _FakeProc.captured = []
    runner = multiprocess.MultiProcessRunner("mod:fn", 1,
                                             init_distributed=True)
    runner.start()
    env = _FakeProc.captured[0]
    assert env["TTD_TEST_INIT_DISTRIBUTED"] == "1"
    assert env["TTD_NUM_PROCESSES"] == "1"
