"""``prefix_flash_roofline.agent``: the reader is found by its name and
listed for the one cell whose pieces it reads; on a canned capture whose
``_prefill_piece`` executions hold the kernel's events under both kinds
of layer it is the visible pairs' least MXU time over those events'
time, by hand; it is ``None`` (not 0) where the capture holds no such
event (the parent commit's program) or nothing was traced; a capture
whose kernel time IS the matrix unit's least for every pair the calls
could see reads under 100%; ``costs_flash`` by hand."""

import json
import os
import pytest

from cellkit import REPO

from benchmark.harness import costs_flash
from benchmark.harness import manifest as manifest_lib

import test_benchmark_mimo as mimo
from test_benchmark_mimo import rec  # noqa: F401  (the ring's fixture)

NAME = "prefix_flash_roofline.agent"
CELL = "mimo-v25-1chip.agent-context"
PEAK = mimo.PEAKS["bf16_flops_per_s"]
CALL = ('%prefix_flash_attention.{n} = bf16[1,{kvh},{rep},4096,128]'
        '{{4,3,2,1,0}} custom-call(%a), custom_call_target='
        '"tpu_custom_call"')
PATH = ("jit(_prefill_piece)/MoeLmModel/layer_{i}/layer_{i}._mha/attn/"
        "{kind}/attention/attention._slot_decode_step/pallas_call")
TRAFFIC = {"engine": {"prefill_chunk": 1024, "prefill_budget": 4096}}


@pytest.fixture(scope="module")
def man():
    return manifest_lib.Manifest(REPO)


def capture(tmp_path, full_s, window_s):
    """The sink fixture's capture with the kernel's events put inside
    its two whole ``_prefill_piece`` executions (a call of four pieces
    from 2.4, of one from 3.4): a full layer's and a window layer's
    call in each, ``full_s`` / ``window_s`` seconds (of the first call,
    of the second)."""
    with open(mimo.SCOPED) as f:
        raw = json.load(f)
    full = CALL.format(n=7, kvh=4, rep=16)
    window = CALL.format(n=8, kvh=8, rep=8)
    raw["op_names"][full] = PATH.format(i=5, kind="full")
    raw["op_names"][window] = PATH.format(i=1, kind="window")
    ops = raw["devices"][0]["ops"]
    for t0, i in ((2.4, 0), (3.4, 1)):
        ops.append([full, t0 + 0.01, full_s[i]])
        ops.append([window, t0 + 0.02, window_s[i]])
    # a kernel's event outside every piece is nobody's
    ops.append([full, 4.9, 0.05])
    path = tmp_path / "scoped_trace_flash.json"
    path.write_text(json.dumps(raw))
    return str(path)


def flash_ctx(logs, rec, path, **extra):
    return dict(mimo.sink_ctx(logs, rec, path=path), traffic=TRAFFIC,
                **extra)


def test_the_reader_is_found_by_name_for_its_cell_alone(man):
    (m,) = [m for m in man.data["per_layer"] if m["name"] == NAME]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "device_trace",
                 "layer": "kernels / program roofline",
                 "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert callable(man.layer_reader(NAME))
    assert NAME in {m["name"] for m in man.per_layer_for(CELL)}
    assert man.data["per_layer"][-1]["name"] == NAME


def test_costs_of_the_visible_pairs_by_hand(man):
    cfg = man.config("mimo-v25-1chip")
    # queries at 0..3 see 1 + 2 + 3 + 4 keys; under a window of 3,
    # 1 + 2 + 3 + 3; from position 10 on, 11 + 12 and 3 + 3
    assert costs_flash.visible_pairs(0, 4) == 10
    assert costs_flash.visible_pairs(0, 4, 3) == 9
    assert costs_flash.visible_pairs(10, 2) == 23
    assert costs_flash.visible_pairs(10, 2, 3) == 6
    assert costs_flash.visible_pairs(1, 5, 4) == 2 + 3 + 4 + 4 + 4
    for first in (0, 5, 127, 128, 4000):
        for window in (None, 128):
            assert costs_flash.visible_pairs(first, 300, window) == sum(
                min(p + 1, window or p + 1)
                for p in range(first, first + 300))
    # a call's last piece walked 4,096 rows in tiles of 512: its last
    # query sits at 3,584 or later, its first of 4,096 at 0; of 1,024
    # at 2,561 or later
    assert costs_flash.first_position_least(4096, 4096) == 0
    assert costs_flash.first_position_least(4096, 1024) == 2561
    got = costs_flash.call_flops(cfg, 4096, 1024, 1000)
    pair = 2 * 64 * (192 + 128)
    assert got == {
        "full": 2 * pair * (1000 * 2561 + 1000 * 1001 // 2),
        "window": 5 * pair * 1000 * 128}
    # never more than the call computed, wherever in its tile it ended
    for end in (3585, 3800, 4096):
        seen = sum(p + 1 for p in range(end - 1024, end - 24))
        assert got["full"] <= 2 * pair * seen


def test_the_share_is_the_visible_pairs_least_over_the_kernels_time(
        man, rec, tmp_path):
    """Two joined calls (four pieces, 3,700 real rows of a walk of
    4,096; one piece, 1,024 of 1,024): the kernel's four events inside
    them, 0.5 s of both kinds, against the least time of what their
    queries see; the event outside the pieces is left out."""
    logs = []
    ctx = flash_ctx(logs, rec, capture(tmp_path, (0.2, 0.1), (0.15, 0.05)))
    cfg = man.config("mimo-v25-1chip")
    flops = [costs_flash.call_flops(cfg, 4096, 4096, 3700),
             costs_flash.call_flops(cfg, 1024, 1024, 1024)]
    want = 100 * sum(sum(f.values()) for f in flops) / PEAK / 0.5
    got = mimo.reader(NAME)(ctx)
    assert got == pytest.approx(want) and 0 < got < 100
    (line,) = [r for r in logs if r.get("phase") == NAME]
    assert (line["pieces"], line["calls_per_piece"]) == (5, 4 / 5)
    assert line["flash_layers"] == [None]     # this ring's spans say nothing
    assert line["kernel_ms_per_piece"] == pytest.approx(
        {"full": 60.0, "window": 40.0})
    assert line["pct"]["window"] == pytest.approx(
        100 * sum(f["window"] for f in flops) / PEAK / 0.2)
    # the accepted readers of the same capture read what they read
    assert mimo.reader("prefill_piece_ms.agent")(ctx) == pytest.approx(260.0)


def test_a_kernel_at_the_matrix_units_least_reads_under_100(
        man, rec, tmp_path):
    """Kernel events exactly as long as the MXU needs for EVERY pair the
    calls' padded queries could see, were the walk's last tile full:
    counting the real queries' visible pairs from the least position,
    the share is under 100% and raises nothing."""
    cfg = man.config("mimo-v25-1chip")
    pair = 2 * 64 * (192 + 128)

    def most(rows, padded):
        first = rows - padded
        return (2 * pair * costs_flash.visible_pairs(first, padded) / PEAK,
                5 * pair * costs_flash.visible_pairs(first, padded, 128)
                / PEAK)

    a, b = most(4096, 4096), most(1024, 1024)
    ctx = flash_ctx([], rec, capture(tmp_path, (a[0], b[0]), (a[1], b[1])))
    got = mimo.reader(NAME)(ctx)
    assert 50 < got <= 100


def test_nothing_to_read_is_none_and_not_zero(man, rec, tmp_path):
    """The parent commit's capture (pieces that walk in XLA: no event
    of that name), a program of another family, a run that traced
    nothing, and a traffic file without the piece's length."""
    assert mimo.reader(NAME)(flash_ctx([], rec, mimo.SCOPED)) is None
    bare = {k: {a: v for a, v in attrs.items()
                if a not in ("kv_window_blocks", "kv_bytes", "pieces")}
            for k, attrs in mimo.ATTRS.items()}
    ctx = dict(mimo.sink_ctx([], rec, path=mimo.SCOPED_MOE, attrs=bare),
               traffic=TRAFFIC)
    assert mimo.reader(NAME)(ctx) is None
    path = capture(tmp_path, (0.2, 0.1), (0.15, 0.05))
    ctx = flash_ctx([], rec, path)
    ctx["tracer"] = None
    assert mimo.reader(NAME)(ctx) is None
    ctx = mimo.sink_ctx([], rec, path=path)         # no ``traffic`` at all
    assert mimo.reader(NAME)(ctx) is None


def test_the_manifest_before_this_reader_is_what_the_pins_ran_on(
        tmp_path, monkeypatch):
    """``test_benchmark_mimo.py``'s test of the manifest as it was
    counts the lists that hold its cell's name, and this reader's is one
    more (``tests/conftest.py`` marks the expected failure; the file is
    not this PR's to edit).  It runs here whole, every assertion of it
    and of the three tests it runs in turn, on a checkout whose
    manifest lacks this one entry: appending it changed nothing that
    was there."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        data = json.load(f)
    assert data["per_layer"][-1]["name"] == NAME
    del data["per_layer"][-1]
    root = tmp_path / "before_flash"
    root.mkdir()
    os.symlink(os.path.join(REPO, "benchmark"), root / "benchmark")
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    monkeypatch.setattr(mimo, "REPO", str(root))
    inner = tmp_path / "inner"
    inner.mkdir()
    mimo.test_the_tests_that_pin_the_manifest_run_whole_as_it_was(
        inner, monkeypatch)
