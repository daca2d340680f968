"""The trace reduction on a small synthetic trace: busy union, idle
share, device time per layer, unassigned programs, idle gaps labelled
with the host span open at the time, self time, and the readers."""
import pytest

from benchmark import harness, trace

LAYERS = {"pairing": ["jit__miller*"], "merkle": ["jit__hash_pairs*"],
          "epoch": ["jit_prog*"]}


def synthetic() -> trace.Trace:
    t = trace.Trace()
    # window 0..1000 ns on the main thread; one block
    t.spans = [
        ("window", "main", 0, 1000),
        ("state_transition", "main", 0, 1000),
        ("process_slots", "main", 0, 400),
        ("process_epoch", "main", 100, 300),
        ("hash_tree_root", "main", 500, 600),
        ("sigpipe.flush", "flusher", 450, 900),
        ("sigpipe.collect", "main", 400, 450),
        ("state_transition", "main", 2000, 3000),   # outside the window
    ]
    t.programs = {"0": [("jit_prog(1)", 150, 250),
                        ("jit__miller_step_double(7)", 600, 700),
                        ("jit__miller_step_double(7)", 650, 750),
                        ("jit__hash_pairs_fixed(3)", 520, 560),
                        ("jit_unknown(9)", 900, 1100)]}
    return t


def test_reduce():
    r = trace.reduce(synthetic(), LAYERS)
    assert r.window == (0, 1000)
    # program runs clipped to the window: 100 + 150 + 40 + 100
    assert r.busy_ns == 390
    assert r.layer_ns == {"epoch": 100, "pairing": 200, "merkle": 40}
    assert r.unassigned == {"jit_unknown(9)": 100}
    assert sum(ns for _l, ns in r.gaps) == 1000 - 390
    # the longest gap, 250..520, has its midpoint in sigpipe.collect..
    # hash_tree_root: 385 lies in process_slots only
    assert r.gaps[0] == ("process_slots", 270)
    assert len([s for s in r.spans if s[0] == "state_transition"]) == 1


def test_union_and_self_time():
    assert trace.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    r = trace.reduce(synthetic(), LAYERS)
    # state_transition 1000 minus the layers below on any thread: epoch
    # 100..300, collect 400..450, flush 450..900 (hash_tree_root inside)
    below = {"process_epoch", "hash_tree_root", "sigpipe.collect",
             "sigpipe.flush"}
    assert trace.self_ns(r.spans, "state_transition", below) == 1000 - 700
    assert trace.span_ns(r.spans, {"sigpipe.collect", "sigpipe.flush"}) \
        == 500


def test_window_must_be_one_span():
    t = synthetic()
    t.spans.append(("window", "main", 5000, 6000))
    with pytest.raises(ValueError):
        trace.reduce(t, LAYERS)


def _ctx(name="altair-mainnet-262k.boundary-block", kind="TPU v5 lite"):
    cell = harness.find_cell(name)
    reduced = trace.reduce(synthetic(), LAYERS)
    return harness.Context(cell=cell, blocks=1, reduced=reduced,
                           device_kind=kind, stage=reduced)


def test_readers():
    ctx = _ctx()
    read = {m: harness.load_reader(m)(ctx) for m in (
        "transition_self_s", "sigpipe_s", "epoch_s", "device_idle_share",
        "epoch_sweep_roofline")}
    assert read["transition_self_s"] == pytest.approx(300e-9)
    assert read["sigpipe_s"] == pytest.approx(500e-9)
    assert read["epoch_s"] == pytest.approx(200e-9)
    assert read["device_idle_share"] == pytest.approx(61.0)
    roofline = harness.load_reader("epoch_sweep_roofline")
    bytes_ = roofline.__globals__["epoch_bytes"](262144)
    assert read["epoch_sweep_roofline"] == pytest.approx(
        100 * bytes_ / 819e9 / 100e-9)


def test_device_readers_read_the_stage():
    """The device readers read the device stage's trace, not the
    block's: a stage with no epoch program leaves the roofline out."""
    cell = harness.find_cell("altair-mainnet-262k.boundary-block")
    block = trace.reduce(synthetic(), LAYERS)
    stage = trace.Trace(spans=[("window", "main", 0, 100)],
                        programs={"0": [("jit_g1_sum_axis(2)", 10, 30)]})
    ctx = harness.Context(cell=cell, blocks=1, reduced=block,
                          device_kind="TPU v5 lite",
                          stage=trace.reduce(stage, LAYERS))
    assert harness.load_reader("device_idle_share")(ctx) == \
        pytest.approx(80.0)
    assert harness.load_reader("epoch_sweep_roofline")(ctx) is None
    assert harness.load_reader("epoch_s")(ctx) == pytest.approx(200e-9)
    ctx.stage = None
    assert harness.load_reader("device_idle_share")(ctx) is None


def test_every_traffic_names_a_device_stage():
    import json
    import os
    folder = os.path.join(harness.HERE, "traffic")
    for f in sorted(os.listdir(folder)):
        if f.endswith(".json"):
            with open(os.path.join(folder, f)) as fh:
                assert json.load(fh)["device_stage"] in harness.DEVICE_STAGES


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.load_reader("epoch_sweep_roofline")(_ctx(kind="cpu"))
