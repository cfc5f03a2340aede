"""Profiling/observability tests: trace capture, step windows, memory stats."""

import glob
import os

import pytest

from tensorflow_train_distributed_tpu.runtime import events, profiling


def test_trace_writes_xplane(tmp_path):
    """A capture lands as an xplane, and an ``events.span`` recorded
    under it is one of its host events."""
    import jax
    import jax.numpy as jnp

    logdir = str(tmp_path / "trace")
    with profiling.trace(logdir):
        with events.span("unit-test/span"):
            jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    # XPlane capture lands under plugins/profile/<run>/ as .xplane.pb.
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert found, f"no xplane produced under {logdir}"
    data = jax.profiler.ProfileData.from_file(found[-1])
    names = {ev.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert "unit-test/span" in names


def test_profile_callback_window(tmp_path, monkeypatch):
    calls = []
    monkeypatch.setattr(profiling, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(profiling, "stop_trace",
                        lambda: calls.append(("stop", None)))
    cb = profiling.ProfileCallback(str(tmp_path), start_step=3, stop_step=5)
    for step in range(1, 8):
        cb.on_step_end(step, {})
    cb.on_train_end(None)
    assert [c[0] for c in calls] == ["start", "stop"]


def test_profile_callback_stops_at_train_end(tmp_path, monkeypatch):
    calls = []
    monkeypatch.setattr(profiling, "start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(profiling, "stop_trace",
                        lambda: calls.append("stop"))
    cb = profiling.ProfileCallback(str(tmp_path), start_step=1, stop_step=99)
    cb.on_step_end(1, {})
    cb.on_train_end(None)
    assert calls == ["start", "stop"]


def test_profile_callback_validates_window(tmp_path):
    with pytest.raises(ValueError):
        profiling.ProfileCallback(str(tmp_path), start_step=5, stop_step=3)


def test_device_memory_stats_enumerates_devices():
    import jax

    stats = profiling.device_memory_stats()
    assert len(stats) == len(jax.local_devices())
    assert all("device" in s for s in stats)


def test_speed_monitor_summary():
    import time

    mon = profiling.SpeedMonitor(examples_per_step=64)
    # Simulate fit's drain pattern: bursts of step reports per log window.
    for window in range(4):
        for step in (2 * window + 1, 2 * window + 2):
            mon.on_step_end(step, {})
        time.sleep(0.01)
    s = mon.summary()
    # 2 steps per ~10ms window → ~5 ms/step, never the µs intra-burst gap.
    assert 2.0 < s["median_step_ms"] < 50.0, s
    assert "examples_per_sec" in s


def test_speed_monitor_ignores_intra_burst_deltas():
    mon = profiling.SpeedMonitor()
    for step in range(1, 11):  # one burst, no wall time between steps
        mon.on_step_end(step, {})
    assert mon.summary() == {}  # no closed window yet → no bogus samples


class TestStallWatchdog:
    def test_fires_on_stall_and_quiet_when_stepping(self, capsys):
        import time

        from tensorflow_train_distributed_tpu.training import StallWatchdog

        wd = StallWatchdog(timeout_s=0.3)
        wd.on_train_begin(None)
        try:
            # Stepping regularly: never fires.
            for i in range(4):
                time.sleep(0.1)
                wd.on_step_end(i, {})
            assert wd.stall_count == 0
            # Silence past the timeout: fires (and re-arms, no spam).
            time.sleep(0.6)
            assert wd.stall_count >= 1
        finally:
            wd.on_train_end(None)
        assert not wd._thread.is_alive()

    def test_rejects_bad_timeout(self):
        import pytest as _pytest

        from tensorflow_train_distributed_tpu.training import StallWatchdog

        with _pytest.raises(ValueError, match="timeout_s"):
            StallWatchdog(timeout_s=0)

    def test_cli_flag_installs_watchdog(self):
        from tensorflow_train_distributed_tpu import launch

        result = launch.run(launch.build_parser().parse_args([
            "--config", "mnist", "--steps", "2", "--platform", "cpu",
            "--stall-timeout", "600",
        ]))
        import numpy as np

        assert np.isfinite(result.history["loss"][-1])


def test_profiler_server_starts_and_stops():
    import socket

    import jax

    from tensorflow_train_distributed_tpu.runtime.profiling import (
        start_profiler_server,
    )

    # A fixed port collides across concurrent CI runs; grab a free one.
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    start_profiler_server(port=port)
    try:
        with socket.create_connection(("127.0.0.1", port), timeout=5):
            pass  # something is listening
    finally:
        jax.profiler.stop_server()


def test_watchdog_stops_when_fit_raises(mesh8):
    """fit must run train_end (stopping the watchdog thread) even when a
    step raises — otherwise the daemon dumps stacks forever after."""
    import optax

    from tensorflow_train_distributed_tpu.training import (
        StallWatchdog, Trainer, TrainerConfig,
    )
    from tests.test_trainer import _BlobsTask, _loader

    wd = StallWatchdog(timeout_s=60)
    trainer = Trainer(_BlobsTask(), optax.adam(1e-2), mesh8,
                      config=TrainerConfig(log_every=1), callbacks=[wd])

    def exploding():
        yield next(iter(_loader()))
        raise RuntimeError("input pipeline died")

    with pytest.raises(RuntimeError, match="input pipeline died"):
        trainer.fit(exploding(), steps=10)
    assert wd._stop is None or wd._stop.is_set()
    assert not wd._thread.is_alive()


def test_watchdog_paused_during_eval():
    import time

    from tensorflow_train_distributed_tpu.training import StallWatchdog

    wd = StallWatchdog(timeout_s=0.2)
    wd.on_train_begin(None)
    try:
        wd.on_eval_begin()
        time.sleep(0.7)          # long eval window: must NOT count
        assert wd.stall_count == 0
        wd.on_eval_end()
        time.sleep(0.1)
        assert wd.stall_count == 0
    finally:
        wd.on_train_end(None)
