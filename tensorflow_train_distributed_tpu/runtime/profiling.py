"""Profiling and observability: XPlane traces, annotations, memory stats.

The reference profiles through the same underlying stack this module wraps:
TraceMe annotations recorded into XPlane protos viewed in TensorBoard
(SURVEY.md §5.1 — ``python/profiler/profiler_v2.py:81/130``, C++
``tsl/profiler/lib/traceme.h``; Keras hook ``TensorBoard(profile_batch=...)``
``tf_keras/src/callbacks.py:2371``).  JAX ships the identical XPlane
machinery as ``jax.profiler``, so traces land in the same TensorBoard
profile plugin — including TPU-side HLO op breakdowns this framework gets
for free.

Two surfaces:

- ``trace(logdir)`` / ``start_trace`` / ``stop_trace`` — whole-window
  capture (reference ``tf.profiler.experimental.start/stop``).
- ``ProfileCallback`` — step-window capture inside ``Trainer.fit``
  (reference ``TensorBoard(profile_batch=(a, b))``).

Host-side named spans inside a capture (reference
``tf.profiler.experimental.Trace``) are ``runtime.events.span``: the
flight recorder's spans are profiler annotations too.

Plus ``device_memory_stats`` for HBM occupancy (per-device bytes in use),
the observability hook the reference exposes via
``tf.config.experimental.get_memory_info``.
"""

from __future__ import annotations

import contextlib
import logging
from typing import Iterator, Optional

import jax

from tensorflow_train_distributed_tpu.training.callbacks import Callback

logger = logging.getLogger(__name__)


def start_trace(logdir: str) -> None:
    """Begin an XPlane trace capture into ``logdir`` (chief process only)."""
    if jax.process_index() == 0:
        jax.profiler.start_trace(logdir)
        logger.info("profiler trace started → %s", logdir)


def stop_trace() -> None:
    if jax.process_index() == 0:
        jax.profiler.stop_trace()
        logger.info("profiler trace stopped")


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """Capture a trace for the duration of the block."""
    start_trace(logdir)
    try:
        yield
    finally:
        stop_trace()


def start_profiler_server(port: int):
    """On-demand remote capture: the analog of the reference's
    ``tf.profiler.experimental.server.start`` (``profiler_v2.py:169``) —
    TensorBoard's "Capture profile" dialog (or
    ``jax.profiler.trace_remote``) can then pull a trace from a live
    training job without any pre-planned --profile-dir window.

    jax keeps the running server in a module-level global until
    ``jax.profiler.stop_server()``; the returned handle is informational.
    """
    try:
        server = jax.profiler.start_server(port)
    except ValueError as e:
        # jax allows one server per process; a second launch.run in the
        # same process keeps the existing one rather than crashing.
        logger.warning("profiler server not started (%s); keeping the "
                       "existing one", e)
        return None
    logger.info("profiler server listening on port %d", port)
    return server


def device_memory_stats() -> list[dict]:
    """Per-device memory stats (bytes_in_use / peak / limit where known).

    CPU/test backends report no stats; entries then carry only the device
    id so callers can still enumerate the fleet.
    """
    stats = []
    for d in jax.local_devices():
        s = d.memory_stats() or {}
        stats.append({
            "device": str(d),
            "bytes_in_use": s.get("bytes_in_use"),
            "peak_bytes_in_use": s.get("peak_bytes_in_use"),
            "bytes_limit": s.get("bytes_limit"),
        })
    return stats


class ProfileCallback(Callback):
    """Capture a trace over a step window during ``fit``.

    ``start_step``/``stop_step`` follow the reference's
    ``profile_batch=(start, stop)`` contract: capture begins after the step
    *before* ``start_step`` completes and ends after ``stop_step``.  Steps
    are observed at the trainer's ``log_every`` granularity, so the
    realized window snaps to log boundaries — always spanning at least the
    requested steps.
    """

    def __init__(self, logdir: str, *, start_step: int = 10,
                 stop_step: int = 20):
        if stop_step < start_step:
            raise ValueError(
                f"stop_step={stop_step} < start_step={start_step}")
        self.logdir = logdir
        self.start_step = start_step
        self.stop_step = stop_step
        self._active = False
        self._done = False

    def on_step_end(self, step, metrics):
        if self._done:
            return
        if not self._active and step >= self.start_step - 1:
            start_trace(self.logdir)
            self._active = True
            return
        if self._active and step >= self.stop_step:
            stop_trace()
            self._active = False
            self._done = True

    def on_train_end(self, state):
        if self._active:  # window extended past the end of training
            stop_trace()
            self._active = False
            self._done = True


class SpeedMonitor(Callback):
    """Rolling step-time / throughput stats, queryable and JSONL-loggable.

    The quantitative face of observability (§5.5): wall-time per optimizer
    step and examples/sec, aggregated between log events.  ``summary()``
    returns the final numbers — what ``bench.py`` and regression tests
    read.
    """

    def __init__(self, examples_per_step: Optional[int] = None):
        from tensorflow_train_distributed_tpu.training.callbacks import (
            StepRateTracker,
        )

        self.examples_per_step = examples_per_step
        self._tracker = StepRateTracker()
        self.step_times_ms: list[float] = []

    def on_step_end(self, step, metrics):
        # Burst-aware: one sample per drain window, not per callback call
        # (see StepRateTracker — naive per-call deltas are meaningless
        # under fit's log_every batching).
        ms = self._tracker.update(step)
        if ms is not None:
            self.step_times_ms.append(ms)

    def summary(self) -> dict:
        if not self.step_times_ms:
            return {}
        import numpy as np

        arr = np.asarray(self.step_times_ms)
        out = {
            "mean_step_ms": float(arr.mean()),
            "median_step_ms": float(np.median(arr)),
            "p90_step_ms": float(np.percentile(arr, 90)),
        }
        if self.examples_per_step:
            out["examples_per_sec"] = (
                self.examples_per_step / (out["median_step_ms"] / 1e3))
        return out
