"""Shallow water as a user runs it: the system under test for a cell.

It builds the solver through the package's user entry, as
``examples/shallow_water.py`` does: ``ShallowWater(ProcessGrid(grid),
(ny, nx), SWParams(...)).step_fn(n)`` with every option at its default
(``impl="auto"``: the fused Pallas step on a 1x1 grid of a TPU, the XLA
step with the halo exchange on a larger grid).  The program receives
only the initial states that set-up generates from the seed, laid out as
the solver's stacked halo-padded blocks.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from mpi4jax_tpu.models import shallow_water
from perf import schedule
from perf.references import shallow_water as reference

FIELDS = ("h", "u", "v")
# the program's named scopes and kernel: a traced run's reduction gives
# each instruction the innermost of them in its op_name
SCOPES = shallow_water.SCOPES


class Cell:
    """One configuration under one traffic mix, ready to be driven."""

    def __init__(self, config, traffic, devices, seed):
        from mpi4jax_tpu.models.shallow_water import (
            ShallowWater, SWParams, SWState)
        from mpi4jax_tpu.parallel.grid import ProcessGrid

        self.config = config
        gy, gx = config["grid"]
        self.grid = ProcessGrid((gy, gx), devices=devices[:gy * gx])
        params = SWParams(
            dx=config["dx"], dy=config["dy"], gravity=config["gravity"],
            depth=config["depth"], coriolis_f=config["coriolis_f"],
            coriolis_beta=config["coriolis_beta"],
            periodic_x=config["periodic_x"])
        self.model = ShallowWater(
            self.grid, (config["ny"], config["nx"]), params)
        self.devices = list(self.grid.mesh.devices.flat)
        self.calls = schedule.solve_calls(
            reference.steps_per_solve(config), traffic)
        self.step = {kind: self.model.step_fn(n, first=kind == "bootstrap")
                     for kind, n in set(self.calls)}
        self.modes = schedule.initial_modes(seed, traffic)
        self.inputs = []

        ny_loc, nx_loc = config["ny"] // gy, config["nx"] // gx

        def to_blocks(f):
            # block (i, j) is the global window of its interior and ghost
            # ring; blocks are stacked along both axes
            rows = [f[i * ny_loc:(i + 1) * ny_loc + 2] for i in range(gy)]
            f = jax.numpy.concatenate(rows, axis=0)
            cols = [f[:, j * nx_loc:(j + 1) * nx_loc + 2] for j in range(gx)]
            return jax.numpy.concatenate(cols, axis=1)

        sharding = NamedSharding(self.grid.mesh, P(*self.grid.axes))
        self._make = jax.jit(
            lambda modes: SWState(*(
                to_blocks(f) for f in reference.initial_state(config, modes))),
            out_shardings=SWState(*(sharding,) * 6))
        self._reference_init = jax.jit(
            lambda modes, dtype: reference.initial_state(config, modes, dtype),
            static_argnums=1)

    # -- set-up -------------------------------------------------------------
    def generate(self):
        """The seeded initial states, made on the device in one jitted
        call each, in the solver's stacked-block layout."""
        self.inputs = [self._make(m) for m in self.modes]
        jax.block_until_ready(self.inputs)

    def warm_up(self):
        """Compile and run every call of the schedule once."""
        state = self.inputs[0]
        for kind in ("bootstrap", "advance", "advance"):
            state = jax.block_until_ready(self.step[kind](state))

    # -- the timed path -----------------------------------------------------
    def initial(self, slot):
        return self.inputs[slot]

    def call(self, kind, state):
        return self.step[kind](state)

    @staticmethod
    def result(state):
        """What a finished solve is judged by (kept on the device)."""
        return tuple(getattr(state, f) for f in FIELDS)

    # -- the check ----------------------------------------------------------
    def to_host(self, result):
        """Global interior fields of a kept result, as numpy arrays."""
        return {f: self.model.interior(a) for f, a in zip(FIELDS, result)}

    def free(self):
        self.inputs = []
        self.step = {}

    def reference(self, slot, dtype):
        """The plain reference's answer for the solve from ``slot``."""
        n_steps = sum(n for _, n in self.calls)
        with jax.default_device(self.devices[0]):
            state = self._reference_init(self.modes[slot], dtype)
            out = reference.solve(state, self.config, n_steps)
            return {f: np.asarray(a[1:-1, 1:-1], np.float32)
                    for f, a in zip(FIELDS, out[:3])}
