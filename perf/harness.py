"""Runs one cell of ``BENCHMARK.json`` once and prints its result line.

Everything that belongs to one configuration, traffic mix or metric is
found by name in files of its own:

- ``BENCHMARK.json`` names the cell's configuration, traffic and chips,
  and the metrics it reports;
- the configuration's ``file`` names its ``system`` (``perf/systems/
  <system>.py``: builds the solver through the package's user entry and
  drives it) and its ``reference`` (``perf/references/<reference>.py``:
  the plain implementation, with ``readings(got, want)``, the numbers
  the check compares), and holds the ``limits`` of those numbers;
- the traffic mix is ``perf/traffic/<traffic>.json``, read by
  ``perf/schedule.py``;
- an end-to-end metric is ``perf/end_to_end/<name>.py`` and a per-layer
  metric ``perf/layer_metrics/<name>.py``, each with ``read(run)``
  returning a number, or None where it finds nothing to read.

A run: set-up (state generation, compilation or the compile cache, one
execution of every call), then the measured window of ``--seconds``
(``--trace 0``) or ``trace_solves`` whole solves under the profiler
(``--trace 1``), then the check of finished solves against the
reference, which runs after the program's state is freed.

A metric reader gets the run's context: ``setup_s`` and its split
``setup`` (``init_s``, ``generate_s``, ``warm_up_s``, ``compile_s``), the
driven ``window``, the reduced ``trace`` (``--trace 1`` only; each
instruction in one of the system's ``SCOPES`` where it names them), the
system's ``counters`` after the window (``Cell.counters()`` where the
system has it, else empty) and the chips' ``device_kind``.
"""

from __future__ import annotations

import collections
import contextlib
import importlib
import importlib.util
import json
import math
import os
import shutil
import time
from types import SimpleNamespace

PERF = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")
# the spans of tracing, lowering and compiling (or loading from the
# cache) a program; those of an inner program nest in its caller's
COMPILE_SPANS = COMPILE_EVENTS + (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",)


class NoDevice(Exception):
    """The machine lacks what the cell needs; no result is printed."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    """``perf/<kind>/<name>.py`` as a module; metric names hold dots, so
    these are loaded by path."""
    path = os.path.join(PERF, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"perf_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric, workload):
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload, bench=None):
    """The cell's configuration, traffic and metrics, by name."""
    if bench is None:
        bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {
        "name": workload,
        "chips": cell["chips"],
        "config": load_json(os.path.join(ROOT, entry["file"])),
        "traffic": load_json(
            os.path.join(PERF, "traffic", cell["traffic"] + ".json")),
        "end_to_end": [m for m in bench["end_to_end"]
                       if _applies(m, workload)],
        "per_layer": [m for m in bench["per_layer"]
                      if _applies(m, workload)],
    }


def chip_devices(chips):
    """The accelerator's devices; raises NoDevice without enough TPU
    chips of a kind that ``perf/peaks.json`` lists."""
    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: jax reports platform "
                       f"{devices[0].platform!r} ({kind})")
    if len(devices) < chips:
        raise NoDevice(f"{chips} chips asked for, {len(devices)} found")
    if kind not in load_json(os.path.join(PERF, "peaks.json")):
        raise NoDevice(f"device kind {kind!r} is not in perf/peaks.json")
    return devices


class Window:
    """What the driven part of a run did, for the metric readers."""

    def __init__(self):
        self.seconds = 0.0
        self.calls = 0
        self.steps = 0
        self.solves = 0
        self.kept = {}  # slot -> result of its latest finished solve
        self.compiles = 0
        self.solve_seconds = []  # between consecutive answers


def drive(cell, *, seconds=None, solves=None):
    """Solves back to back, each from the next seeded initial state,
    until ``seconds`` have passed or ``solves`` have finished.  The host
    dispatches a solve's calls back to back and then waits for the
    answer of the solve before it, which the device finishes while this
    one waits in its queue.  The window closes at a wait and counts the
    solves finished by then; the solve still in flight is finished
    untimed, so that its answer is judged too."""
    import jax
    from jax.profiler import TraceAnnotation

    w = Window()
    steps = sum(n for _, n in cell.calls)
    inflight = collections.deque()  # (slot, final state) per solve
    dispatched = 0
    counter = _CompileCounter()
    t0 = t_last = time.perf_counter()
    with counter, TraceAnnotation("perf.window"):
        while True:
            if solves is None or dispatched < solves:
                slot = dispatched % len(cell.inputs)
                with TraceAnnotation("perf.prepare"):
                    state = cell.initial(slot)
                for kind, _ in cell.calls:
                    with TraceAnnotation("perf.dispatch"):
                        state = cell.call(kind, state)
                inflight.append((slot, state))
                dispatched += 1
                if len(inflight) < 2 and dispatched != solves:
                    continue
            slot, state = inflight.popleft()
            with TraceAnnotation("perf.sync"):
                jax.block_until_ready(state)
            t = time.perf_counter()
            w.solve_seconds.append(t - t_last)
            t_last = t
            w.seconds = t - t0
            w.kept[slot] = cell.result(state)
            w.solves += 1
            w.calls += len(cell.calls)
            w.steps += steps
            if seconds is not None and w.seconds >= seconds or (
                    w.solves == solves):
                break
    w.compiles = counter.count
    for slot, state in inflight:
        w.kept[slot] = cell.result(jax.block_until_ready(state))
    return w


class _CompileCounter(contextlib.AbstractContextManager):
    """Counts traces and compiles while it is entered, and keeps the
    host-clock ``(start, end)`` of each trace, lowering and compile."""

    def __init__(self):
        self.count = 0
        self.spans = []

    def _listen(self, event, duration, **kwargs):
        if event in COMPILE_EVENTS:
            self.count += 1
        if event in COMPILE_SPANS:
            # JAX reports each span as it ends
            end = time.perf_counter()
            self.spans.append((end - duration, end))

    def seconds(self):
        """Host seconds in which some program was traced, lowered or
        compiled: the union of the spans, which nest."""
        from perf.tracefile import length, union

        return length(union(self.spans))

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


def memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return max((p for p in peaks if p is not None), default=None)


def _finite(x):
    return x if x is not None and math.isfinite(x) else None


def check(spec, cell, window, seed):
    """Readings of the sampled finished solves against the reference,
    the worst of each with its limit, and how many solves failed.
    Frees the program's state first."""
    from perf import schedule

    slots = schedule.checked_solves(seed, window.kept, spec["traffic"])
    got = {s: cell.to_host(window.kept[s]) for s in slots}
    window.kept.clear()
    cell.free()
    return judge(spec, cell, got)


def judge(spec, cell, got):
    """``got`` (initial-state slot -> global fields of the finished
    solve) against the float32 reference from the same slot: the worst
    reading of each number beside its limit, and how many solves failed.
    """
    import jax.numpy as jnp

    config = spec["config"]
    ref = importlib.import_module(f"perf.references.{config['reference']}")
    limits = config["limits"]
    worst = {name: 0.0 if got else None for name in limits}
    failed = 0
    for s in sorted(got):
        want = cell.reference(s, jnp.float32)
        readings = {k: _finite(v)
                    for k, v in ref.readings(got[s], want).items()}
        failed += any(readings[k] is None or readings[k] > limits[k]
                      for k in limits)
        for k in limits:
            worst[k] = (None if readings[k] is None or worst[k] is None
                        else max(worst[k], readings[k]))
    return {k: {"value": worst[k], "limit": limits[k]} for k in limits}, \
        failed


def verdict(readings):
    """``correct``: every number read, and none above its limit."""
    return all(r["value"] is not None and r["value"] <= r["limit"]
               for r in readings.values())


def run(spec, seed, seconds, trace, *, t_start, devices):
    """One run of one cell on ``devices``; returns the result object."""
    import jax

    system = importlib.import_module(
        f"perf.systems.{spec['config']['system']}")
    with _CompileCounter() as compiles:
        cell = system.Cell(spec["config"], spec["traffic"], devices, seed)
        t_init = time.perf_counter()
        cell.generate()
        t_generated = time.perf_counter()
        cell.warm_up()
        t_warm = time.perf_counter()
    setup_s = t_warm - t_start
    setup = {"init_s": t_init - t_start, "generate_s": t_generated - t_init,
             "warm_up_s": t_warm - t_generated,
             "compile_s": compiles.seconds()}

    reduced = None
    if trace:
        from perf import tracefile

        trace_dir = os.path.join(ROOT, ".perf_trace", spec["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        try:
            window = drive(cell, solves=spec["traffic"]["trace_solves"])
        finally:
            jax.profiler.stop_trace()
        reduced = tracefile.reduce(tracefile.find_xplane(trace_dir),
                                   cell.devices,
                                   getattr(system, "SCOPES", ()))
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        window = drive(cell, seconds=seconds)
    peak = memory_peak(cell.devices)
    counters = cell.counters() if hasattr(cell, "counters") else {}

    ctx = SimpleNamespace(setup_s=setup_s, setup=setup, window=window,
                          trace=reduced, counters=counters,
                          device_kind=cell.devices[0].device_kind)
    listed = spec["per_layer"] if trace else spec["end_to_end"]
    kind = "layer_metrics" if trace else "end_to_end"
    metrics = {}
    for m in listed:
        value = load_module(kind, m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    readings, failed = check(spec, cell, window, seed)
    correct = verdict(readings)
    all_devices = jax.devices()
    device = {"platform": all_devices[0].platform,
              "kind": all_devices[0].device_kind,
              "count": len(all_devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": window.calls,
              "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s()
        result["breakdown"] = reduced.breakdown()
    result["compiles_in_window"] = window.compiles
    result["solve_seconds"] = window.solve_seconds
    result["setup"] = setup
    result["check"] = readings
    return result
