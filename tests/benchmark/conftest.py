"""Fixtures of the benchmark's own tests (CPU only).

Why four of the five files here hold exactly ten tests.  The driver
runs the suite with pytest-xdist ``--dist loadfile``, which queues files
largest first and gives a worker its next file when the one it runs has
two tests left.  ``tests/test_supervisor.py`` (11 tests) re-targets its
worker to 2 CPU devices (``tools/chaos_check.py``) and has two tests
left some nine seconds in, so that worker is handed whatever heads the
queue then: in the seed ``tests/test_vit.py`` (10) or
``tests/test_embedding.py`` (9), and the latter needs the suite's 8
devices and errors (seen in two of three whole runs of this tree while
these files held 8 tests or fewer and queued behind it).  Files of ten
queue straight after ``test_supervisor.py``, so its worker takes one of
them, and the fixture below puts the 8 devices back before anything
else runs there.  This PR may not touch ``tools/chaos_check.py``; the
PR that restores the device count there makes the sizes free again."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cellkit  # noqa: E402


@pytest.fixture(autouse=True)
def eight_cpu_devices():
    """These tests run on the suite's 8 virtual CPU devices.  An
    earlier file in the same worker may have left the process with
    fewer (``tools/chaos_check.py`` re-targets it to 2); put them back,
    with the program's own seam."""
    import jax

    if len(jax.devices()) < 8:
        from tensorflow_train_distributed_tpu.runtime.mesh import (
            force_platform,
        )

        force_platform("cpu", 8)
    yield
    # Engines, drivers and trainers sit in reference cycles.  Collect
    # them now, at a quiet point, rather than leave them to a collector
    # pass inside some later test of this worker: their finalizers take
    # locks of the program's sanitizers, and a pass that starts while
    # the lock-order sanitizer holds its own lock never returns (seen
    # once, in tests/test_concurrency_fixes.py).
    import gc

    gc.collect()


@pytest.fixture
def cell_root(tmp_path):
    return cellkit.make_cell_root(tmp_path)
