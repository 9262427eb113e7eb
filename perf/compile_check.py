"""Compile each cell's programs at full size for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 perf/compile_check.py [workload ...]

Runs without a chip: the TPU compiler builds the cell's step calls (and
the reference's solve, on one chip) for a described ``v5e:2x2`` and
prints, per program, whether a Mosaic kernel is in it, the collectives
it holds and ``memory_analysis()``.  Nothing runs, so it says nothing of
results or times.
"""

import os
import re
import sys
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _report(name, compiled, seconds):
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    collectives = sorted(set(re.findall(
        r"\s(all-reduce|all-gather|all-to-all|collective-permute)"
        r"(?:-start)?\(", text)))
    print(f"{name}: compiled in {seconds:.1f} s; Mosaic kernel "
          f"{'yes' if 'tpu_custom_call' in text else 'no'}; collectives "
          f"{collectives or 'none'}; argument "
          f"{mem.argument_size_in_bytes / 2**20:.1f} MiB, output "
          f"{mem.output_size_in_bytes / 2**20:.1f} MiB, temp "
          f"{mem.temp_size_in_bytes / 2**20:.1f} MiB per chip", flush=True)


def check(workload, topo):
    import importlib

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from mpi4jax_tpu.models.shallow_water import SWState
    from perf import harness
    from perf.references import shallow_water as reference

    spec = harness.resolve(workload)
    config = spec["config"]
    system = importlib.import_module(f"perf.systems.{config['system']}")
    cell = system.Cell(config, spec["traffic"], topo.devices, 0)
    grid, model, calls = cell.grid, cell.model, cell.calls
    sharding = NamedSharding(grid.mesh, P(*grid.axes))
    state = SWState(*(jax.ShapeDtypeStruct(model.stacked_shape, jnp.float32,
                                           sharding=sharding),) * 6)
    for kind, n in sorted(set(calls)):
        t0 = time.perf_counter()
        # impl="auto" picks its path for the platform of the mesh in
        # context: the described TPU's, not this machine's backend
        with jax.set_mesh(grid.mesh):
            fn = model.step_fn(n, first=kind == "bootstrap")
        compiled = fn.lower(state).compile()
        _report(f"{workload} {kind} ({n} steps)", compiled,
                time.perf_counter() - t0)

    one = SingleDeviceSharding(topo.devices[0])
    shape = (config["ny"] + 2, config["nx"] + 2)
    ref_state = tuple(jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)
                      for _ in range(6))
    items = tuple((k, config[k]) for k in reference.CONSTANTS)
    t0 = time.perf_counter()
    compiled = reference._solve.lower(
        ref_state, items, sum(n for _, n in calls)).compile()
    _report(f"{workload} reference solve", compiled,
            time.perf_counter() - t0)


def main(argv):
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    from perf import harness

    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    for workload in argv or [w["name"] for w in bench["workloads"]]:
        check(workload, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
