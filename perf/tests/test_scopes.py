"""Device time by named scope (``perf/scopes.py``), checked against the
trace reduction on the two small traces of ``test_tracefile.py``, whose
program named no scope, and two recorded on a TPU v5e by
``record_trace.py`` from the program with its scopes: the fused kernel on
one chip (240 x 512) and the XLA step with its halo exchange on a 2x2
grid (240 x 512 per chip), each one solve of three 25-step advance calls.
"""

import os

import pytest

from mpi4jax_tpu.models.shallow_water import SCOPES
from perf import scopes, tracefile

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
STEPS = 75
TRACES = {"1x1": ("sw_1x1_small", 1), "2x2": ("sw_2x2_small", 4),
          "1x1s": ("sw_1x1_scoped", 1), "2x2s": ("sw_2x2_scoped", 4)}
# ms per step on the worst chip, per scope and class, of the scoped traces
PINNED = {
    "1x1s": {"kernel": 0.01279337333333334, "pad": 3.642666666692218e-05,
             "unpad": 2.4960000000092318e-05,
             "unscoped": 0.0009491999999999834},
    "2x2s": {"collective": 0.09345394666666987, "halo": 0.13785722666666925,
             "step": 0.09292458666667058, "unscoped": 0.004133040000007249},
}


class _Device:
    def __init__(self, i):
        self.id = i


@pytest.fixture(scope="module")
def traces(tmp_path_factory):
    """``{which: (path of the unzipped trace, its bytes, chips)}``."""
    tmp = tmp_path_factory.mktemp("traces")
    out = {}
    for which, (name, n) in TRACES.items():
        buf = scopes.load(os.path.join(DATA, name + ".xplane.pb.gz"))
        path = tmp / (name + ".xplane.pb")
        path.write_bytes(buf)
        out[which] = (str(path), buf, n)
    return out


def _xspace_class():
    """A message class for the part of ``XSpace`` that the reader reads,
    from a schema declared here, for the protobuf runtime to decode (map
    fields as the repeated key/value entries they are on the wire)."""
    pytest.importorskip("google.protobuf")
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    proto = descriptor_pb2.FileDescriptorProto(
        name="xspace_part.proto", package="xspace_part", syntax="proto2")

    def message(name, *fields):
        m = proto.message_type.add(name=name)
        for fname, number, kind, repeated in fields:
            f = m.field.add(name=fname, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if isinstance(kind, str):
                f.type = F.TYPE_MESSAGE
                f.type_name = ".xspace_part." + kind
            else:
                f.type = kind

    message("XStat", ("metadata_id", 1, F.TYPE_INT64, False),
            ("str_value", 5, F.TYPE_STRING, False),
            ("ref_value", 7, F.TYPE_UINT64, False))
    message("XEventMetadata", ("name", 2, F.TYPE_STRING, False),
            ("stats", 5, "XStat", True))
    message("XStatMetadata", ("name", 2, F.TYPE_STRING, False))
    message("EventEntry", ("key", 1, F.TYPE_INT64, False),
            ("value", 2, "XEventMetadata", False))
    message("StatEntry", ("key", 1, F.TYPE_INT64, False),
            ("value", 2, "XStatMetadata", False))
    message("XPlane", ("name", 2, F.TYPE_STRING, False),
            ("event_metadata", 4, "EventEntry", True),
            ("stat_metadata", 5, "StatEntry", True))
    message("XSpace", ("planes", 1, "XPlane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("xspace_part.XSpace"))


@pytest.mark.parametrize("which", sorted(TRACES))
def test_op_names_match_the_protobuf_runtime(traces, which):
    path, buf, n = traces[which]
    planes = {f"/device:TPU:{i}" for i in range(n)}
    space = _xspace_class().FromString(buf)
    want = {}
    for plane in space.planes:
        if plane.name not in planes:
            continue
        stat = {e.key: e.value.name for e in plane.stat_metadata}
        names = want.setdefault(plane.name, {})
        for entry in plane.event_metadata:
            op = ""
            for st in entry.value.stats:
                if stat.get(st.metadata_id) == "tf_op":
                    op = (st.str_value if st.HasField("str_value")
                          else stat[st.ref_value])
                    op = op.rpartition(":")[0] if ":" in op else op
            names.setdefault(entry.value.name, set()).add(op)
    got = scopes.op_names(buf, planes)
    assert got == want
    # every instruction event of a chip has its metadata
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if plane.name in planes:
            line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
            assert {ev.name for ev in line.events} <= set(got[plane.name])


@pytest.mark.parametrize("which", sorted(TRACES))
def test_scopes_partition_what_the_reduction_reads(traces, which):
    """On every chip the seconds of each scope add up, class by class, to
    what ``tracefile.reduce`` reads: kernel, collective and XLA seconds,
    and all leaf instruction time."""
    path, buf, n = traces[which]
    trace = tracefile.reduce(path, [_Device(i) for i in range(n)])
    split = scopes.split(buf, SCOPES)
    assert set(split) == set(trace.chips)
    for dev, chip in trace.chips.items():
        by_class = {}
        for (_, op), t in split[dev].items():
            cls = tracefile.op_class(op)
            by_class[cls] = by_class.get(cls, 0.0) + t
        for cls in ("kernel", "collective", "xla"):
            assert by_class.get(cls, 0.0) == pytest.approx(
                chip.seconds(cls), rel=1e-9, abs=1e-15)
        assert sum(split[dev].values()) == pytest.approx(
            sum(e - s for s, e, _, _ in chip.ops), rel=1e-9)


@pytest.mark.parametrize("which", ["1x1", "2x2"])
def test_a_program_without_scopes_is_all_unscoped(traces, which):
    _, buf, _ = traces[which]
    for seconds in scopes.split(buf, SCOPES).values():
        assert {sc for sc, _ in seconds} == {"unscoped"}


def test_scope_of_an_op_name():
    body = "jit(sw_advance)/shard_map/while/body/closed_call/"
    assert scopes.scope(body + "sw_step/halo_exchange/dynamic_update_slice",
                        SCOPES) == "halo_exchange"
    assert scopes.scope(body + "sw_step/jit(_pad)/pad", SCOPES) == "sw_step"
    assert scopes.scope("jit(sw_advance)/while/body/closed_call/"
                        "sw_fused_step/pallas_call", SCOPES) == (
                            "sw_fused_step")
    assert scopes.scope(body + "sw_step/add", ()) == "unscoped"
    assert scopes.scope("jit(sw_advance)/while", SCOPES) == "unscoped"
    assert scopes.scope("state.h", SCOPES) == "unscoped"
    assert scopes.scope("", SCOPES) == "unscoped"


def test_one_event_name_of_two_scopes_fails_loudly():
    # the same instruction text in a bootstrap and an advance program
    assert scopes.event_scopes({"%fusion.1 = f32[8] fusion()": {
        "jit(sw_bootstrap)/sw_step/add", "jit(sw_advance)/sw_step/add"}},
        SCOPES) == {"%fusion.1 = f32[8] fusion()": "sw_step"}
    with pytest.raises(RuntimeError, match="scopes"):
        scopes.event_scopes({"%copy.1 = f32[8] copy()": {
            "jit(sw_advance)/sw_step/halo_exchange/slice",
            "jit(sw_advance)/sw_step/slice"}}, SCOPES)
    with pytest.raises(RuntimeError, match="scopes"):
        scopes.event_scopes({"%copy.1 = f32[8] copy()": {
            "", "jit(sw_advance)/sw_step/slice"}}, SCOPES)


def _ms(traces, which):
    _, buf, _ = traces[which]
    return scopes.summary(scopes.split(buf, SCOPES), STEPS)["ms_per_step"]


def test_one_chip_scoped_trace(traces):
    _, buf, _ = traces["1x1s"]
    (seconds,) = scopes.split(buf, SCOPES).values()
    assert {sc for sc, _ in seconds} == {
        "sw_fused_step", "sw_pad_rows", "sw_unpad_rows", "unscoped"}
    assert [k for k in seconds if k[1] == "custom-call:tpu_custom_call"] \
        == [("sw_fused_step", "custom-call:tpu_custom_call")]
    ms = _ms(traces, "1x1s")
    assert ms == pytest.approx({
        "sw_fused_step kernel": PINNED["1x1s"]["kernel"],
        "sw_pad_rows xla": PINNED["1x1s"]["pad"],
        "sw_unpad_rows xla": PINNED["1x1s"]["unpad"],
        "unscoped xla": PINNED["1x1s"]["unscoped"],
    }, rel=1e-9)


def test_four_chip_scoped_trace(traces):
    _, buf, _ = traces["2x2s"]
    for seconds in scopes.split(buf, SCOPES).values():
        assert {sc for sc, _ in seconds} == {
            "halo_exchange", "sw_step", "unscoped"}
        # every collective is the halo exchange's
        assert {sc for sc, op in seconds
                if tracefile.op_class(op) == "collective"} == {
                    "halo_exchange"}
    ms = _ms(traces, "2x2s")
    assert ms == pytest.approx({
        "halo_exchange collective": PINNED["2x2s"]["collective"],
        "halo_exchange xla": PINNED["2x2s"]["halo"],
        "sw_step xla": PINNED["2x2s"]["step"],
        "unscoped xla": PINNED["2x2s"]["unscoped"],
    }, rel=1e-9)
    # the dynamic-update-slices that the opcode view gave to the halo
    # exchange alone split between it and the step's own interior writes
    ops = dict(scopes.summary(scopes.split(buf, SCOPES), STEPS,
                              top=100)["device_ops"])
    assert ops["halo_exchange dynamic-update-slice"] > 0
    assert ops["sw_step dynamic-update-slice"] > 0


def test_command_prints_the_summary(traces, capsys):
    import json

    assert scopes.main([os.path.join(DATA, "sw_1x1_scoped.xplane.pb.gz"),
                        "--steps", str(STEPS),
                        "--scopes", ",".join(SCOPES)]) == 0
    line = json.loads(capsys.readouterr().out)
    assert line["chips"] == 1
    assert line["ms_per_step"] == pytest.approx(_ms(traces, "1x1s"))
    assert line["device_ops"][0][0] == (
        "sw_fused_step custom-call:tpu_custom_call")



# -- the scopes in the benchmark's own reduction ---------------------------
READERS = ("device.idle_share", "kernel.sw_fused_ms_per_step",
           "step.xla_ms_per_step", "halo.collective_ms_per_step",
           "halo.exposed_ms_per_step")
SCOPE_READERS = ("halo.pack_ms_per_step", "step.stencil_ms_per_step",
                 "step.pad_ms_per_step", "step.unscoped_ms_per_step")


def _read(names, trace):
    from types import SimpleNamespace

    from perf import harness

    window = harness.Window()
    window.steps = STEPS
    run = SimpleNamespace(trace=trace, window=window)
    return {n: harness.load_module("layer_metrics", n).read(run)
            for n in names}


def _reduced(traces, which, names):
    path, _, n = traces[which]
    return tracefile.reduce(path, [_Device(i) for i in range(n)], names)


@pytest.mark.parametrize("which", ["1x1s", "2x2s"])
def test_readers_read_the_same_with_scopes(traces, which):
    plain = _reduced(traces, which, ())
    scoped = _reduced(traces, which, SCOPES)
    assert not plain.scoped and scoped.scoped
    want, got = _read(READERS, plain), _read(READERS, scoped)
    assert set(want) == set(got)
    for name, value in want.items():
        if value is None:
            assert got[name] is None, name
        else:
            assert got[name] == pytest.approx(value, rel=1e-12), name
    assert scoped.busy_s() == pytest.approx(plain.busy_s(), rel=1e-12)
    assert scoped.breakdown()["idle_gaps"] == plain.breakdown()["idle_gaps"]


@pytest.mark.parametrize("which", ["1x1s", "2x2s"])
def test_scoped_xla_seconds_add_up_to_the_xla_seconds(traces, which):
    trace = _reduced(traces, which, SCOPES)
    for chip in trace.chips.values():
        found = {sc for _, _, _, sc in chip.ops}
        assert found <= set(SCOPES) | {scopes.UNSCOPED}
        assert sum(chip.seconds("xla", sc) for sc in found) == pytest.approx(
            chip.seconds("xla"), rel=1e-9)
        assert chip.seconds(scope=scopes.UNSCOPED) == pytest.approx(
            sum(chip.seconds(cls, scopes.UNSCOPED)
                for cls in ("kernel", "collective", "xla")), rel=1e-12)


def test_without_scopes_every_instruction_is_unscoped(traces):
    trace = _reduced(traces, "2x2s", ())
    for chip in trace.chips.values():
        assert {sc for _, _, _, sc in chip.ops} == {scopes.UNSCOPED}
    # and no reader claims a split it could not make
    assert all(v is None for v in _read(SCOPE_READERS, trace).values())


@pytest.mark.parametrize("which", ["1x1s", "2x2s"])
def test_scope_readers_give_the_offline_split(traces, which):
    """The readers of ``perf/layer_metrics`` against ``perf/scopes.py``'s
    split of the same recording, and its pinned numbers."""
    got = _read(SCOPE_READERS, _reduced(traces, which, SCOPES))
    ms = _ms(traces, which)
    want = {
        "halo.pack_ms_per_step": ms.get("halo_exchange xla"),
        "step.stencil_ms_per_step": ms.get("sw_step xla"),
        "step.pad_ms_per_step": (
            ms["sw_pad_rows xla"] + ms["sw_unpad_rows xla"]
            if "sw_pad_rows xla" in ms else None),
        "step.unscoped_ms_per_step": sum(
            v for k, v in ms.items() if k.startswith("unscoped ")),
    }
    for name, value in want.items():
        if value is None:
            assert got[name] is None, name
        else:
            assert got[name] == pytest.approx(value, rel=1e-9), name
    pins = PINNED[which]
    if which == "1x1s":
        assert got["step.pad_ms_per_step"] == pytest.approx(
            pins["pad"] + pins["unpad"], rel=1e-9)
    else:
        assert got["halo.pack_ms_per_step"] == pytest.approx(
            pins["halo"], rel=1e-9)
        assert got["step.stencil_ms_per_step"] == pytest.approx(
            pins["step"], rel=1e-9)
    assert got["step.unscoped_ms_per_step"] == pytest.approx(
        pins["unscoped"], rel=1e-9)


def test_breakdown_names_each_instruction_by_scope(traces):
    trace = _reduced(traces, "1x1s", SCOPES)
    ops = dict(trace.breakdown(top=100)["device_ops"])
    assert ops["sw_fused_step custom-call:tpu_custom_call"] == pytest.approx(
        dict(_reduced(traces, "1x1s", ()).breakdown()["device_ops"])[
            "custom-call:tpu_custom_call"], rel=1e-12)
    assert all(name.split(" ")[0] in set(SCOPES) | {scopes.UNSCOPED}
               for name in ops)
