"""The program's own spans (`consensus_specs_tpu/utils/spans.py`) in the
benchmark's trace reduction and readers: on a synthetic trace the five
readers that predate them read the same numbers with and without them,
each new reader reads its hand-reckoned value (and nothing from a
program without them), and an idle gap takes the innermost program
span as its label; the names cannot collide with the harness's; and a
real profile of a minimal-preset `state_transition` carries them."""
import time

import jax
import pytest

from benchmark import harness, trace
from consensus_specs_tpu.resilience import sites
from consensus_specs_tpu.utils import bls, spans

CELL = "altair-mainnet-262k.boundary-block"
LAYERS = {"pairing": ["jit__miller*"], "epoch": ["jit_prog*"]}
OLD_READERS = ("transition_self_s", "sigpipe_s", "epoch_s",
               "device_idle_share", "epoch_sweep_roofline")
SEED = 3_000_000_019

# one block on thread "main", its flush on "flusher"; times in ns
HARNESS_SPANS = [
    ("window", "main", 0, 1200),
    ("state_transition", "main", 200, 1200),
    ("process_slots", "main", 200, 600),
    ("hash_tree_root", "main", 205, 265),
    ("process_epoch", "main", 300, 560),
    ("sigpipe.collect", "main", 600, 650),
    ("sigpipe.flush", "flusher", 650, 1000),
    ("process_block", "main", 650, 1150),
    ("hash_tree_root", "main", 1150, 1190),
]
PROGRAM_SPANS = [
    ("ssz.copy", "main", 0, 150),
    ("merkle.reroot", "main", 210, 260),
    ("dispatch:ssz.merkle_sweep", "main", 215, 255),
    ("epoch.collect", "main", 300, 400),
    ("dispatch:ops.epoch_sweep", "main", 400, 450),
    ("epoch.writeback", "main", 450, 540),
    ("epoch.tail", "main", 540, 560),
    ("sigpipe.prepare", "flusher", 650, 700),
    ("dispatch:ops.g1_aggregate", "flusher", 660, 690),
    ("dispatch:ops.msm", "flusher", 700, 750),
    ("dispatch:ops.pairing_fold", "flusher", 750, 850),
    ("dispatch:bls.pairing_check", "flusher", 850, 950),
    ("sigpipe.join", "main", 660, 1000),
    ("sigpipe.join", "gossip", 300, 350),      # not the block's thread
    ("block.header", "main", 1000, 1010),
    ("block.operations", "main", 1010, 1100),
    ("merkle.reroot", "main", 1155, 1185),
    ("dispatch:ops.msm", "flusher", 2000, 2100),   # after the window
]
PROGRAMS = {"0": [("jit_prog(1)", 410, 440),
                  ("jit__miller_step_double(7)", 760, 840),
                  ("jit_unknown(9)", 1190, 1300)]}

# hand-reckoned, in ns for the one block
EXPECTED = {
    "sigpipe_join_s": 1000 - 660,
    "sigpipe_dispatch_s": 30 + 50 + 100 + 100,
    "epoch_collect_s": 100,
    "epoch_writeback_s": 90,
    "merkle_s": 50 + 30,
    "state_copy_s": 150,
}


def _ctx(with_program_spans: bool) -> harness.Context:
    t = trace.Trace(spans=list(HARNESS_SPANS), programs=PROGRAMS)
    if with_program_spans:
        t.spans += PROGRAM_SPANS
    reduced = trace.reduce(t, LAYERS)
    return harness.Context(cell=harness.find_cell(CELL), blocks=1,
                           reduced=reduced, device_kind="TPU v5 lite",
                           stage=reduced)


def test_old_readers_read_the_same_with_program_spans():
    without, with_ = _ctx(False), _ctx(True)
    for name in OLD_READERS:
        read = harness.load_reader(name)
        assert read(with_) == read(without), name
        assert read(without) is not None, name


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_new_reader_reads_its_span(name):
    read = harness.load_reader(name)
    assert read(_ctx(True)) == pytest.approx(EXPECTED[name] * 1e-9)
    assert read(_ctx(False)) is None     # a program without the spans


def test_idle_gaps_take_the_innermost_program_span():
    with_ = _ctx(True).reduced
    # 450 lies in process_epoch (harness) and epoch.writeback (program)
    assert trace.host_label(with_.spans, "main", 450) == "epoch.writeback"
    assert trace.host_label(_ctx(False).reduced.spans, "main", 450) == \
        "process_epoch"
    # the gap 440..760 has its midpoint, 600, in sigpipe.collect only;
    # the gap 0..410 has its midpoint, 205, in hash_tree_root, before
    # merkle.reroot opens
    gaps = dict(with_.gaps)
    assert gaps["sigpipe.collect"] == 760 - 440
    assert gaps["hash_tree_root"] == 410


def test_program_span_names_are_not_the_harness_names():
    harness_names = {e["span"] for e in harness.load_json(
        harness.HERE, "spans.json")} | {trace.WINDOW}
    assert not harness_names & set(spans.SPANS)
    assert not any(n.startswith(spans.DISPATCH) for n in harness_names)
    # the reduction keeps the program's annotations by their label
    assert spans.LABEL_PREFIX == trace.SPAN_PREFIX


def test_dispatch_reader_sites_are_registered():
    reader = harness.load_reader("sigpipe_dispatch_s")
    for site in reader.__globals__["SITES"]:
        assert sites.is_registered(site), site
        assert sites.site(site).kind == sites.DISPATCH, site
        assert spans.declared(spans.DISPATCH + site)


@pytest.fixture
def host_bls(monkeypatch):
    monkeypatch.setattr(bls, "use_tpu", bls.use_native)


def _minimal_cell() -> harness.Cell:
    cell = harness.find_cell(CELL)
    cell.config.update(preset="minimal", validators=256)
    return cell


def test_profiled_transition_carries_program_spans(tmp_path):
    """A real profile of one minimal-preset epoch-boundary block, loaded
    as the benchmark loads one: the flush join on the block's thread,
    the device calls under `dispatch:<site>`, and only declared names."""
    from benchmark.traffic.build import build
    from consensus_specs_tpu import sigpipe
    from consensus_specs_tpu.sigpipe import pipeline_async
    from consensus_specs_tpu.specs import get_spec
    from consensus_specs_tpu.ssz import hash_tree_root, incremental

    cell = _minimal_cell()
    spec = get_spec(cell.config["fork"], cell.config["preset"])
    traffic = build(spec, cell.config, cell.mix, SEED)
    incremental.enable()
    incremental.track(traffic.state)
    hash_tree_root(traffic.state)
    sigpipe.enable()
    pipeline_async.enable()
    try:
        with harness.profiled(str(tmp_path), harness.HOST_TRACE_MODE):
            post = traffic.state.copy()
            spec.state_transition(post, traffic.signed)
        assert bytes(hash_tree_root(post)) == traffic.post_root
    finally:
        pipeline_async.reset()
        sigpipe.disable()
        incremental.disable()
    got = trace.load_xplane(str(tmp_path)).spans
    names = {n for n, _t, _s, _e in got}
    assert all(spans.declared(n) for n in names), names
    block_thread = {t for n, t, _s, _e in got if n == "block.operations"}
    assert {t for n, t, _s, _e in got if n == "sigpipe.join"} == \
        block_thread
    assert {"dispatch:ops.epoch_sweep", "dispatch:bls.pairing_check",
            "dispatch:ssz.merkle_sweep"} <= names
    assert {"ssz.copy", "merkle.reroot", "epoch.collect",
            "epoch.writeback", "epoch.tail", "sigpipe.prepare",
            "block.header", "block.sync_aggregate"} <= names


def test_traced_cell_reads_every_program_metric(host_bls):
    """The harness's own traced run at the minimal preset: each new
    metric is in the result line, and the flush's wait cannot exceed
    the flush, nor the epoch's phases the epoch."""
    result = harness.run_cell(_minimal_cell(), SEED, 0.5, True,
                              time.perf_counter(), jax.devices()[:1],
                              require_modes=False)
    assert result["correct"], result["checks"]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(EXPECTED) <= set(got)
    assert got["sigpipe_join_s"] <= got["sigpipe_s"]
    assert got["epoch_collect_s"] + got["epoch_writeback_s"] <= \
        got["epoch_s"]
