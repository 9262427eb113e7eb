"""The trace reduction, checked against two small traces recorded on a
TPU v5e with the harness's host spans: the fused shallow-water kernel on
one chip (240 x 512, three 25-step calls) and the XLA step with its halo
exchange on a 2x2 grid of four chips (480 x 1024, three 25-step calls).
"""

import gzip
import os
from types import SimpleNamespace

import pytest

from perf import harness, tracefile

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
STEPS = 75  # three calls of 25 steps in each recorded window


class _Device:
    def __init__(self, i):
        self.id = i


def _path(tmp_path, name):
    out = tmp_path / name
    with gzip.open(os.path.join(DATA, name + ".gz"), "rb") as f:
        out.write_bytes(f.read())
    return str(out)


@pytest.fixture(scope="module")
def traces(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("traces")
    return {
        "1x1": (_path(tmp, "sw_1x1_small.xplane.pb"), 1),
        "2x2": (_path(tmp, "sw_2x2_small.xplane.pb"), 4),
    }


def _reduce(traces, which):
    path, n = traces[which]
    return tracefile.reduce(path, [_Device(i) for i in range(n)])


def _metrics(trace):
    window = harness.Window()
    window.steps = STEPS
    run = SimpleNamespace(trace=trace, window=window, setup_s=0.0)
    names = ("device.idle_share", "kernel.sw_fused_ms_per_step",
             "step.xla_ms_per_step", "halo.collective_ms_per_step",
             "halo.exposed_ms_per_step")
    return {n: harness.load_module("layer_metrics", n).read(run)
            for n in names}


def _naive(path, device, lo, hi):
    """Instruction seconds per class on one chip, by a plain scan of the
    raw events: leaf instructions only, clipped to the window."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    plane = next(p for p in data.planes
                 if p.name == f"/device:TPU:{device}")
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    sums = {"kernel": 0.0, "collective": 0.0, "xla": 0.0}
    for ev in line.events:
        s, e = max(ev.start_ns * 1e-9, lo), min(ev.end_ns * 1e-9, hi)
        if e <= s:
            continue
        if any(f" {op}(" in ev.name
               for op in ("while", "conditional", "call")):
            continue
        if 'custom_call_target="tpu_custom_call"' in ev.name:
            sums["kernel"] += e - s
        elif any(f" collective-permute{sfx}(" in ev.name
                 for sfx in ("", "-start", "-done")):
            sums["collective"] += e - s
        else:
            sums["xla"] += e - s
    return sums


@pytest.mark.parametrize("which", ["1x1", "2x2"])
def test_class_seconds_match_a_plain_scan(traces, which):
    trace = _reduce(traces, which)
    lo, hi = trace.window
    for dev, chip in trace.chips.items():
        want = _naive(traces[which][0], dev, lo, hi)
        for cls, seconds in want.items():
            assert chip.seconds(cls) == pytest.approx(seconds, rel=1e-9,
                                                      abs=1e-12)


def test_opcodes_and_counts():
    assert tracefile.opcode(
        '%closed_call.16 = (f32[8,128]{1,0:T(8,128)}) custom-call(f32[8,128]'
        '{1,0} %p), custom_call_target="tpu_custom_call"'
    ) == "custom-call:tpu_custom_call"
    assert tracefile.opcode(
        "%collective-permute-start = (f32[1,1,3602]{2,1,0:T(1,128)S(1)}, "
        "u32[]{:S(2)}) collective-permute-start(f32[1,1,3602] %x)"
    ) == "collective-permute-start"
    assert tracefile.op_class("while") == "control"


def test_one_chip_kernel_trace(traces):
    trace = _reduce(traces, "1x1")
    (chip,) = trace.chips.values()
    ops = [op for _, _, op, _ in chip.ops]
    assert ops.count("custom-call:tpu_custom_call") == 18
    assert not chip.collective()
    m = _metrics(trace)
    assert m["halo.collective_ms_per_step"] is None
    assert m["halo.exposed_ms_per_step"] is None
    assert m["kernel.sw_fused_ms_per_step"] == pytest.approx(
        0.00852901333333341, rel=1e-9)
    assert m["step.xla_ms_per_step"] == pytest.approx(
        0.0006725733333345918, rel=1e-9)
    assert m["device.idle_share"] == pytest.approx(0.8726238053849983,
                                                   rel=1e-9)


def test_four_chip_halo_trace(traces):
    trace = _reduce(traces, "2x2")
    assert sorted(trace.chips) == [0, 1, 2, 3]
    for chip in trace.chips.values():
        ops = [op for _, _, op, _ in chip.ops]
        assert ops.count("collective-permute-start") == 1500
        assert ops.count("collective-permute-done") == 1500
        exposed = tracefile.length(chip.exposed_collective())
        assert 0 < exposed <= tracefile.length(chip.collective())
    m = _metrics(trace)
    assert m["kernel.sw_fused_ms_per_step"] is None
    assert m["halo.collective_ms_per_step"] == pytest.approx(
        0.11757341333333134, rel=1e-9)
    assert m["halo.exposed_ms_per_step"] == pytest.approx(
        0.09345133333333644, rel=1e-9)
    assert m["step.xla_ms_per_step"] == pytest.approx(
        0.2349137066666651, rel=1e-9)
    assert m["device.idle_share"] == pytest.approx(0.18448955354987184,
                                                   rel=1e-9)


@pytest.mark.parametrize("which", ["1x1", "2x2"])
def test_busy_and_idle_cover_the_window(traces, which):
    trace = _reduce(traces, which)
    for dev, chip in trace.chips.items():
        idle = sum(d for d, gdev, _ in trace.idle_gaps() if gdev == dev)
        busy = tracefile.length(chip.busy())
        assert busy + idle == pytest.approx(trace.window_s(), rel=1e-9)
    b = trace.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(name.startswith(("perf.", "none"))
               for name, _ in b["idle_gaps"])


def test_interval_arithmetic():
    u = tracefile.union([(0, 2), (1, 3), (5, 6)])
    assert u == [(0, 3), (5, 6)]
    assert tracefile.subtract(u, [(1, 2), (2.5, 5.5)]) == [
        (0, 1), (2, 2.5), (5.5, 6)]
