"""What the harness hands the metric readers besides the window and the
trace: the split of set-up with its compile spans, the system's counters
and the device kind, driven through a whole run on the CPU at a small
size, with the look for a chip skipped."""

import time
from types import SimpleNamespace

import jax
import pytest

from perf import harness
from perf.systems import shallow_water as system

SPAN = "/jax/core/compile/jaxpr_to_mlir_module_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


def test_compile_seconds_are_the_union_of_nested_spans(monkeypatch):
    """JAX reports each span when it ends.  An outer trace (0-10) holds
    an inner program's trace (2-3), lowering (3-4) and compile (4-7);
    then the outer lowering (10-11) and compile (11-15), a cache event
    that is no compile span, and a lone compile (20-21)."""
    clock = iter([3.0, 4.0, 7.0, 10.0, 11.0, 15.0, 21.0])
    monkeypatch.setattr(harness, "time",
                        SimpleNamespace(perf_counter=lambda: next(clock)))
    counter = harness._CompileCounter()
    for event, duration in [(TRACE, 1.0), (SPAN, 1.0), (COMPILE, 3.0),
                            (TRACE, 10.0), (SPAN, 1.0), (COMPILE, 4.0),
                            ("/jax/compilation_cache/cache_hits", 0.5),
                            (COMPILE, 1.0)]:
        counter._listen(event, duration)
    assert counter.count == 5  # traces and compiles, as the window counts
    assert len(counter.spans) == 7
    assert counter.seconds() == pytest.approx(15.0 + 1.0, abs=1e-12)


def _spec():
    spec = harness.resolve("sw_ref.periodic")
    spec["config"] = dict(spec["config"], ny=24, nx=48, model_days=0.002)
    spec["traffic"] = dict(spec["traffic"], steps_per_call=4)
    return spec


@pytest.fixture
def contexts(monkeypatch):
    """The context each metric reader of a run is given."""
    seen = []

    def load_module(kind, name):
        def read(run):
            seen.append(run)
            return 1.0
        return SimpleNamespace(read=read)

    monkeypatch.setattr(harness, "load_module", load_module)
    return seen


def _run():
    return harness.run(_spec(), 2**31 + 3, 0.0, 0,
                       t_start=time.perf_counter(), devices=jax.devices())


@pytest.mark.parametrize("counters", [None, {"solves_started": 7}])
def test_readers_get_setup_counters_and_device_kind(monkeypatch, contexts,
                                                    counters):
    if counters is not None:
        monkeypatch.setattr(system.Cell, "counters",
                            lambda self: dict(counters), raising=False)
    result = _run()
    assert result["correct"], result["check"]
    assert len(contexts) == 2  # step_ms and setup_s
    ctx = contexts[0]
    assert ctx.counters == (counters or {})
    assert ctx.device_kind == jax.devices()[0].device_kind
    assert ctx.setup == result["setup"]
    assert set(ctx.setup) == {"init_s", "generate_s", "warm_up_s",
                              "compile_s"}
    assert all(v >= 0 for v in ctx.setup.values())
    parts = (ctx.setup["init_s"] + ctx.setup["generate_s"]
             + ctx.setup["warm_up_s"])
    assert parts == pytest.approx(ctx.setup_s, rel=1e-9)
    # the programs are traced and compiled in set-up, not in the window
    assert 0 < ctx.setup["compile_s"] < ctx.setup_s
    assert result["compiles_in_window"] == 0
    assert list(result)[-2:] == ["setup", "check"]
