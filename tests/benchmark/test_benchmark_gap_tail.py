"""``gap_p95_ms.longprompt``: the long-prompt cell's old end-to-end tail,
read per layer from what the runner measured (PERF.md section 2); and
the manifest's split of the gap metrics between the two Qwen cells."""

import pytest

import cellkit

from benchmark.harness import manifest as manifest_lib

LP = "qwen25-7b-1chip.longprompt-rate"
BD = "qwen25-7b-1chip.batch-decode"


@pytest.fixture(scope="module")
def man():
    return manifest_lib.Manifest(cellkit.REPO)


def test_gap_tail_is_the_runners_own_95th_percentile(man):
    read = man.layer_reader("gap_p95_ms.longprompt")
    e2e = {"gap_p90_ms": 402.0, "gap_p95_ms": 523.0}
    assert read({"result": {"end_to_end": e2e}}) == 523.0
    # nothing finished, so the runner took no gap: nothing to read, never 0
    assert read({"result": {"end_to_end": {"serve_tokens_per_s": 0.0}}}) is None


@pytest.mark.parametrize("cell, e2e, tail_per_layer", [
    (LP, {"gap_p90_ms", "setup_s"}, True),
    (BD, {"serve_tokens_per_s", "gap_p95_ms", "setup_s"}, False),
])
def test_each_qwen_cell_reports_the_gap_it_can_hold(man, cell, e2e,
                                                     tail_per_layer):
    assert {m["name"] for m in man.end_to_end_for(cell)} == e2e
    layer = {m["name"]: m for m in man.per_layer_for(cell)}
    assert ("gap_p95_ms.longprompt" in layer) == tail_per_layer
    for m in layer.values():
        assert m["moves"] in e2e, m
