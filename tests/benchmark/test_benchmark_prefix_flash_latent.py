"""``prefix_flash_roofline.longctx``: the reader is found by its name
and listed for the one cell whose pieces it reads; on a canned capture
whose ``_prefill_piece`` executions hold the latent kernel's events it
is the least MXU time of what the calls' attention requires (the pairs
their queries SEE under the learned choice, and each row they can see
up-projected once a layer) over those events' time, by hand; it is
``None`` (not 0) where the capture holds no such event (the parent
commit's program) or nothing was traced; a capture whose kernel time IS
the matrix unit's least for every pair the calls could see reads under
100%; ``costs_flash_latent`` by hand."""

import json
import os
import pytest

from cellkit import REPO

from benchmark.harness import costs_flash, costs_flash_latent
from benchmark.harness import manifest as manifest_lib

import test_benchmark_mimo as mimo
import test_benchmark_prefix_flash as flash
from test_benchmark_mimo import rec  # noqa: F401  (the ring's fixture)

NAME = "prefix_flash_roofline.longctx"
CELL = "deepseek-v32exp-1chip.longctx-mixed"
CONFIG = "deepseek-v32exp-1chip"
PEAK = mimo.PEAKS["bf16_flops_per_s"]
CALL = ('%prefix_flash_latent.{n} = bf16[1,128,1024,128]{{3,2,1,0}} '
        'custom-call(%a), custom_call_target="tpu_custom_call"')
PATH = ("jit(_prefill_piece)/MoeLmModel/layer_{i}/attention/attn/sparse/"
        "pallas_call")
TRAFFIC = {"engine": {"prefill_chunk": 1024, "prefill_budget": 2048}}
# a pair seen: QK^T over 128 + 64 and PV over 128, 128 heads; a row
# up-projected: a latent of 512 into 128 + 128 a head; five layers
PAIR = 2 * 128 * (192 + 128)
ROW = 2 * 128 * 512 * (128 + 128)


@pytest.fixture(scope="module")
def man():
    return manifest_lib.Manifest(REPO)


def capture(tmp_path, seconds, other=False):
    """The sink fixture's capture with the latent kernel's events put
    inside its two whole ``_prefill_piece`` executions (a call of four
    pieces from 2.4, of one from 3.4), ``seconds`` of each; ``other``:
    the plain rows' kernel's events instead."""
    with open(mimo.SCOPED) as f:
        raw = json.load(f)
    call = (flash.CALL.format(n=7, kvh=4, rep=16) if other
            else CALL.format(n=3))
    raw["op_names"][call] = PATH.format(i=2)
    ops = raw["devices"][0]["ops"]
    for t0, dur in zip((2.4, 3.4), seconds):
        ops.append([call, t0 + 0.01, dur / 2])
        ops.append([call, t0 + 0.02 + dur / 2, dur / 2])
    ops.append([call, 4.9, 0.05])   # outside every piece: nobody's
    path = tmp_path / "scoped_trace_flash_latent.json"
    path.write_text(json.dumps(raw))
    return str(path)


def latent_ctx(logs, rec, man, path, **extra):
    return dict(mimo.sink_ctx(logs, rec, path=path), traffic=TRAFFIC,
                config=man.config(CONFIG), **extra)


def test_the_reader_is_found_by_name_for_its_cell_alone(man):
    (m,) = [m for m in man.data["per_layer"] if m["name"] == NAME]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "device_trace",
                 "layer": "kernels / program roofline",
                 "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert callable(man.layer_reader(NAME))
    assert NAME in {m["name"] for m in man.per_layer_for(CELL)}
    assert NAME not in {m["name"] for m in man.per_layer_for(flash.CELL)}
    assert NAME in [m["name"] for m in man.data["per_layer"]][-1:]


def test_costs_of_what_the_choice_lets_a_query_see_by_hand(man):
    cfg = man.config(CONFIG)
    assert (cfg["index_topk"], cfg["num_hidden_layers"]) == (2048, 5)
    # a first piece: no query sees more rows than the choice keeps
    got = costs_flash_latent.call_flops(cfg, 1024, 1024, 1000)
    assert got == {"attend": 5 * PAIR * (1000 * 1001 // 2),
                   "up_project": 5 * ROW * 1000}
    # a walk of 8,192 rows in tiles of 512: the call's last query sits
    # at 7,680 or later, its first of 1,024 at 6,657 or later, and
    # every query there sees the 2,048 rows the choice keeps
    assert costs_flash.first_position_least(8192, 1024) == 6657
    got = costs_flash_latent.call_flops(cfg, 8192, 1024, 1024)
    assert got == {"attend": 5 * PAIR * 1024 * 2048,
                   "up_project": 5 * ROW * (6657 + 1024)}
    # across the choice's threshold: p + 1 below it, 2,048 from it on
    got = costs_flash_latent.call_flops(cfg, 2560, 1024, 1024)
    first = costs_flash.first_position_least(2560, 1024)
    assert first == 1025
    assert got["attend"] == 5 * PAIR * sum(
        min(p + 1, 2048) for p in range(first, first + 1024))
    # never more than a dense walk of the rows held computes, wherever
    # in its last tile the call ended
    for end in (7681, 8000, 8192):
        assert got["up_project"] <= 5 * ROW * 8192
        dense = sum(p + 1 for p in range(end - 1024, end))
        assert costs_flash_latent.call_flops(cfg, 8192, 1024, 1024)[
            "attend"] <= 5 * PAIR * dense


def test_the_share_is_the_least_time_over_the_kernels_time(
        man, rec, tmp_path):
    """Two joined calls (four pieces, 3,700 real rows of a walk of
    4,096; one piece, 1,024 of 1,024): the kernel's four events inside
    them, 0.4 s, against the least time of what their attention
    requires; the event outside the pieces is left out."""
    logs = []
    ctx = latent_ctx(logs, rec, man, capture(tmp_path, (0.3, 0.1)))
    cfg = man.config(CONFIG)
    flops = [costs_flash_latent.call_flops(cfg, 4096, 4096, 3700),
             costs_flash_latent.call_flops(cfg, 1024, 1024, 1024)]
    want = 100 * sum(sum(f.values()) for f in flops) / PEAK / 0.4
    got = mimo.reader(NAME)(ctx)
    assert got == pytest.approx(want) and 0 < got < 100
    (line,) = [r for r in logs if r.get("phase") == NAME]
    assert (line["pieces"], line["calls_per_piece"]) == (5, 4 / 5)
    assert line["flash_layers"] == [None]     # this ring's spans say nothing
    assert line["kernel_ms_per_piece"] == pytest.approx(80.0)
    assert line["least_ms_per_piece"]["up_project"] == pytest.approx(
        1e3 * sum(f["up_project"] for f in flops) / PEAK / 5)
    # the plain rows' reader finds nothing of its own in this capture
    assert mimo.reader(flash.NAME)(ctx) is None


def test_a_kernel_at_the_matrix_units_least_reads_under_100(
        man, rec, tmp_path):
    """Kernel events exactly as long as the MXU needs for a DENSE walk
    of every row the calls' padded queries could see, were the walk's
    last tile full, and for every row of it up-projected: counting what
    the choice lets the real queries see from the least position, the
    share is under 100% and raises nothing."""

    def most(rows, padded):
        first = rows - padded
        return (5 * PAIR * costs_flash.visible_pairs(first, padded)
                + 5 * ROW * rows) / PEAK

    ctx = latent_ctx([], rec, man, capture(
        tmp_path, (most(4096, 4096), most(1024, 1024))))
    got = mimo.reader(NAME)(ctx)
    assert 40 < got <= 100


def test_nothing_to_read_is_none_and_not_zero(man, rec, tmp_path):
    """The parent commit's capture (pieces that walk in XLA: no event
    of that name), a capture whose pieces run the OTHER kernel, a run
    that traced nothing, and a traffic file without the piece's
    length."""
    assert mimo.reader(NAME)(latent_ctx([], rec, man, mimo.SCOPED)) is None
    path = capture(tmp_path, (0.3, 0.1), other=True)
    assert mimo.reader(NAME)(latent_ctx([], rec, man, path)) is None
    path = capture(tmp_path, (0.3, 0.1))
    ctx = latent_ctx([], rec, man, path)
    ctx["tracer"] = None
    assert mimo.reader(NAME)(ctx) is None
    ctx = dict(mimo.sink_ctx([], rec, path=path),        # no ``traffic``
               config=man.config(CONFIG))
    assert mimo.reader(NAME)(ctx) is None


def test_the_manifest_before_this_reader_is_what_the_pins_ran_on(
        tmp_path, monkeypatch):
    """Two tests of ``test_benchmark_prefix_flash.py`` assert that ITS
    reader is the last of ``per_layer``, and this reader's entry is
    appended after it (``tests/conftest.py`` marks the expected
    failures; the file is not this PR's to edit).  Both run here whole,
    every assertion of them and of the tests the second runs in turn,
    on a checkout whose manifest lacks this one entry: appending it
    changed nothing that was there."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        data = json.load(f)
    assert data["per_layer"][-1]["name"] == NAME
    del data["per_layer"][-1]
    root = tmp_path / "before_flash_latent"
    root.mkdir()
    os.symlink(os.path.join(REPO, "benchmark"), root / "benchmark")
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    flash.test_the_reader_is_found_by_name_for_its_cell_alone(
        manifest_lib.Manifest(str(root)))
    monkeypatch.setattr(flash, "REPO", str(root))
    inner = tmp_path / "inner"
    inner.mkdir()
    flash.test_the_manifest_before_this_reader_is_what_the_pins_ran_on(
        inner, monkeypatch)
