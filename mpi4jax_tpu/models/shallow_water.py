"""Distributed nonlinear shallow-water model (the framework's flagship app).

Physical setup matches the reference demo so benchmarks are comparable
(/root/reference/examples/shallow_water.py: Sadourny energy-conserving
C-grid scheme, geostrophic-jet initial condition, Adams–Bashforth-2 with
offset 0.1, CFL dt = 0.125·dx/√(gH), periodic in x, walls in y, lateral
viscosity 1e-3·f·dx²).  The *implementation* is TPU-first and shares no
structure with it:

- 2-D domain decomposition is a ``ProcessGrid`` over a device mesh; each
  halo update is a *batched* ``lax.ppermute`` (several fields stacked into
  one collective per direction) instead of the reference's ~10 token-chained
  single-field sendrecv calls per step (shallow_water.py:277-412 there) —
  fewer, larger ICI transfers (SURVEY.md §7 hard part 2).
- The time loop is ``lax.fori_loop`` inside one ``shard_map``-ped jit.
- The distributed initial condition uses the framework's own collectives:
  the geostrophic height profile is a *global* cumulative integral along y,
  computed as local cumsum + exclusive cross-rank prefix via ``scan`` —
  plus mean-centering via ``psum``.
- Stencils are slice-expressions on halo-padded blocks (C-grid):
  interior = a[1:-1, 1:-1]; east = a[1:-1, 2:]; north = a[2:, 1:-1].
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import ops
from ..parallel.grid import ProcessGrid
from ..parallel.halo import halo_exchange

# The names the solver gives its parts inside a compiled program, which a
# profiler trace shows in each instruction's op_name (docs/observability.md,
# "Device scopes"): the halo exchange, the XLA step, the fused path's
# padding and cut back, and its kernel.
SCOPES = ("halo_exchange", "sw_step", "sw_pad_rows", "sw_unpad_rows",
          "sw_fused_step")


class SWParams(NamedTuple):
    dx: float
    dy: float
    gravity: float = 9.81
    depth: float = 100.0
    coriolis_f: float = 2e-4
    coriolis_beta: float = 2e-11
    day_seconds: float = 86_400.0
    ab_a: float = 1.6  # Adams–Bashforth 1.5 + offset
    ab_b: float = -0.6
    periodic_x: bool = True

    @property
    def dt(self) -> float:
        return 0.125 * min(self.dx, self.dy) / float(
            np.sqrt(self.gravity * self.depth)
        )

    @property
    def viscosity(self) -> float:
        return 1e-3 * self.coriolis_f * self.dx**2


class SWState(NamedTuple):
    h: jax.Array
    u: jax.Array
    v: jax.Array
    dh: jax.Array
    du: jax.Array
    dv: jax.Array


# stencil views on a 1-cell halo-padded block
def _C(a):
    return a[..., 1:-1, 1:-1]


def _E(a):
    return a[..., 1:-1, 2:]


def _W(a):
    return a[..., 1:-1, :-2]


def _N(a):
    return a[..., 2:, 1:-1]


def _S(a):
    return a[..., :-2, 1:-1]


def _NE(a):
    return a[..., 2:, 2:]


def _SE(a):
    return a[..., :-2, 2:]


def _NW(a):
    return a[..., 2:, :-2]


def _pad(interior):
    return jnp.pad(interior, [(1, 1), (1, 1)])


def _embed(old, interior):
    """Write a new interior into ``old``, preserving its ghost ring (the
    physical-wall ghost values must persist across steps; the exchange
    refreshes only interior-facing ghosts)."""
    return old.at[1:-1, 1:-1].set(interior)


class ShallowWater:
    """Shallow-water solver over a 2-D process grid.

    ``global_shape = (ny, nx)`` is the physical (unpadded) domain; each rank
    owns a ``(ny/gy + 2, nx/gx + 2)`` halo-padded block.
    """

    def __init__(
        self,
        grid: ProcessGrid,
        global_shape,
        params: Optional[SWParams] = None,
    ):
        self.grid = grid
        self.ny, self.nx = global_shape
        gy, gx = grid.shape
        if self.ny % gy or self.nx % gx:
            raise ValueError(
                f"domain {global_shape} not divisible by grid {grid.shape}"
            )
        self.ny_loc = self.ny // gy
        self.nx_loc = self.nx // gx
        self.params = params or SWParams(dx=5e3, dy=5e3)
        self.block_shape = (self.ny_loc + 2, self.nx_loc + 2)
        # stacked-block global shapes for shard_map I/O
        self.stacked_shape = (
            gy * self.block_shape[0],
            gx * self.block_shape[1],
        )

    # -- per-rank coordinate fields (inside shard_map) --------------------
    def _local_coords(self):
        p = self.params
        iy = lax.axis_index(self.grid.axes[0])
        ix = lax.axis_index(self.grid.axes[1])
        # halo-inclusive index ranges, offset by this rank's block origin
        jy = jnp.arange(-1, self.ny_loc + 1) + iy * self.ny_loc
        jx = jnp.arange(-1, self.nx_loc + 1) + ix * self.nx_loc
        y = jy.astype(jnp.float32) * p.dy
        x = jx.astype(jnp.float32) * p.dx
        return jnp.meshgrid(y, x, indexing="ij")

    def _coriolis(self, yy):
        p = self.params
        return p.coriolis_f + yy * p.coriolis_beta

    # -- boundary handling ------------------------------------------------
    def _exchange(self, fields, kinds):
        """Batched halo exchange + physical wall conditions.

        ``kinds``: per-field C-grid location "h" | "u" | "v" — v-point
        fields get the no-normal-flow wall at the north boundary, u-point
        fields a wall at east when x is not periodic (reference behavior:
        enforce_boundaries' trailing wall masks).
        """
        p = self.params
        out = halo_exchange(
            tuple(fields),
            self.grid,
            halo=1,
            periodic=(False, p.periodic_x),
        )
        gy_ax, gx_ax = self.grid.axes
        at_north = lax.axis_index(gy_ax) == lax.axis_size(gy_ax) - 1
        at_east = lax.axis_index(gx_ax) == lax.axis_size(gx_ax) - 1
        result = []
        for f, kind in zip(out, kinds):
            if kind == "v":
                f = f.at[-2, :].set(jnp.where(at_north, 0.0, f[-2, :]))
            elif kind == "u" and not p.periodic_x:
                f = f.at[:, -2].set(jnp.where(at_east, 0.0, f[:, -2]))
            result.append(f)
        return result

    # -- initial conditions ----------------------------------------------
    def _initial_local(self):
        """Geostrophic jet (reference setup) via distributed collectives."""
        p = self.params
        yy, xx = self._local_coords()
        ly = self.ny * p.dy
        lx = self.nx * p.dx

        u0 = 10.0 * jnp.exp(-((yy - 0.5 * ly) ** 2) / (0.02 * lx) ** 2)
        v0 = jnp.zeros_like(u0)

        # h in geostrophic balance: h(y) = -(1/g)∫ f·u dy — a global prefix
        # integral along y.  Local cumsum + exclusive cross-rank prefix sum.
        integrand = -p.dy * u0 * self._coriolis(yy) / p.gravity
        body = integrand[1:-1, 1:-1]  # interior rows only
        local_cum = jnp.cumsum(body, axis=0)
        col_total = local_cum[-1]
        incl = ops.scan(col_total, op=ops.SUM, comm=self.grid.axis_comm(0))
        offset = incl - col_total  # exclusive prefix from ranks above... south
        h_int = local_cum + offset[None, :]

        # center around the resting depth (global mean over the interior)
        total = ops.allreduce(
            jnp.sum(h_int), op=ops.SUM, comm=self.grid.comm
        )
        h_int = h_int - total / float(self.ny * self.nx)

        h_int = (
            p.depth
            + h_int
            + 0.2
            * jnp.sin(_C(xx) / lx * 10 * jnp.pi)
            * jnp.cos(_C(yy) / ly * 8 * jnp.pi)
        )

        # edge-pad: physical-wall ghosts continue the boundary value (zero
        # normal gradient), interior ghosts are replaced by the exchange
        h0 = jnp.pad(h_int, 1, mode="edge")
        h0, u0, v0 = self._exchange((h0, u0, v0), ("h", "u", "v"))
        zero = jnp.zeros(self.block_shape, jnp.float32)
        return SWState(h0, u0, v0, zero, zero, zero)

    # -- dynamics ---------------------------------------------------------
    # every instruction of a step carries sw_step in its op_name; those of
    # its exchanges carry halo_exchange inside it
    @jax.named_scope("sw_step")
    def _step_local(self, state: SWState, first: bool) -> SWState:
        p = self.params
        dt = p.dt
        dx, dy, g = p.dx, p.dy, p.gravity
        h, u, v, dh, du, dv = state

        # h with edge-valued ghosts: physical-wall ghost rows keep the edge
        # value, interior ghosts are overwritten by the exchange.
        (hc,) = self._exchange((jnp.pad(_C(h), 1, mode="edge"),), ("h",))

        # fe/fn/q/ke all derive from (hc, u, v) whose ghosts are already
        # valid — compute them together and exchange in ONE batched
        # collective per direction (the reference interleaves four separate
        # token-ordered exchanges here, shallow_water.py:277-345 there)
        fe = _pad(0.5 * (_C(hc) + _E(hc)) * _C(u))
        fn = _pad(0.5 * (_C(hc) + _N(hc)) * _C(v))
        yy, _ = self._local_coords()
        zeta = (_E(v) - _C(v)) / dx - (_N(u) - _C(u)) / dy
        thickness = 0.25 * (_C(hc) + _E(hc) + _N(hc) + _NE(hc))
        q = _pad((self._coriolis(_C(yy)) + zeta) / thickness)
        ke = _pad(
            0.5
            * (
                0.5 * (_C(u) ** 2 + _W(u) ** 2)
                + 0.5 * (_C(v) ** 2 + _S(v) ** 2)
            )
        )
        fe, fn, q, ke = self._exchange(
            (fe, fn, q, ke), ("u", "v", "h", "h")
        )

        dh_new = -(_C(fe) - _W(fe)) / dx - (_C(fn) - _S(fn)) / dy

        du_new = -g * (_E(h) - _C(h)) / dx + 0.5 * (
            _C(q) * 0.5 * (_C(fn) + _E(fn))
            + _S(q) * 0.5 * (_S(fn) + _SE(fn))
        )
        dv_new = -g * (_N(h) - _C(h)) / dy - 0.5 * (
            _C(q) * 0.5 * (_C(fe) + _N(fe))
            + _W(q) * 0.5 * (_W(fe) + _NW(fe))
        )
        du_new = du_new - (_E(ke) - _C(ke)) / dx
        dv_new = dv_new - (_N(ke) - _C(ke)) / dy

        if first:
            h = _embed(h, _C(h) + dt * dh_new)
            u = _embed(u, _C(u) + dt * du_new)
            v = _embed(v, _C(v) + dt * dv_new)
        else:
            h = _embed(h, _C(h) + dt * (p.ab_a * dh_new + p.ab_b * _C(dh)))
            u = _embed(u, _C(u) + dt * (p.ab_a * du_new + p.ab_b * _C(du)))
            v = _embed(v, _C(v) + dt * (p.ab_a * dv_new + p.ab_b * _C(dv)))
        h, u, v = self._exchange((h, u, v), ("h", "u", "v"))

        if p.viscosity > 0:
            nu = p.viscosity
            gx_u = _pad(nu * (_E(u) - _C(u)) / dx)
            gy_u = _pad(nu * (_N(u) - _C(u)) / dy)
            gx_v = _pad(nu * (_E(v) - _C(v)) / dx)
            gy_v = _pad(nu * (_N(v) - _C(v)) / dy)
            gx_u, gy_u, gx_v, gy_v = self._exchange(
                (gx_u, gy_u, gx_v, gy_v), ("u", "v", "u", "v")
            )
            u = _embed(
                u,
                _C(u)
                + dt
                * (
                    (_C(gx_u) - _W(gx_u)) / dx
                    + (_C(gy_u) - _S(gy_u)) / dy
                ),
            )
            v = _embed(
                v,
                _C(v)
                + dt
                * (
                    (_C(gx_v) - _W(gx_v)) / dx
                    + (_C(gy_v) - _S(gy_v)) / dy
                ),
            )
            h, u, v = self._exchange((h, u, v), ("h", "u", "v"))

        return SWState(
            h, u, v, _pad(dh_new), _pad(du_new), _pad(dv_new)
        )

    # -- public driver ----------------------------------------------------
    def _spmd(self, fn, out_specs=None):
        spec = P(*self.grid.axes)
        return jax.shard_map(
            fn,
            mesh=self.grid.mesh,
            in_specs=spec,
            out_specs=out_specs if out_specs is not None else spec,
            check_vma=False,
        )

    def init(self) -> SWState:
        """Initial state as stacked-block global arrays."""
        # cache the jitted builder: a fresh jax.jit wrapper per call
        # would retrace AND recompile every time
        fn = getattr(self, "_init_fn", None)
        if fn is None:

            def go(dummy):
                del dummy
                # local blocks are concatenated along both grid axes by
                # out_specs, yielding stacked-block global arrays directly
                return self._initial_local()

            fn = jax.jit(
                self._spmd(go, out_specs=SWState(*(P(*self.grid.axes),) * 6))
            )
            self._init_fn = fn

        dummy = jnp.zeros(
            (self.grid.shape[0], self.grid.shape[1]), jnp.float32
        )
        return fn(dummy)

    def step_fn(self, n_steps: int, first: bool = False,
                donate: bool = False, impl: str = "auto",
                tile_rows: int = 120, fuse: int = 3):
        """A jitted function advancing the stacked-block state n_steps.

        ``donate=True`` donates the input state's buffers to the output
        (callers must not reuse the argument after the call) — saves one
        state-sized allocation per invocation on HBM-bound configs.

        ``impl``: "xla" — slice-stencil step (`_step_local`, works on any
        grid); "pallas" — the fused single-kernel step
        (`_sw_pallas.fused_step`, single-block periodic-x grids only:
        6 reads + 6 writes of HBM per step instead of ~a dozen
        materialized intermediates); "auto" — pallas when eligible and
        the target is a TPU, else the XLA step.  A kernel that fails to
        compile raises: no path swaps in another step behind the caller.

        ``tile_rows``/``fuse`` tune the Pallas path: row-tile height and
        temporal blocking factor (``fuse`` steps per HBM round-trip —
        see ``_sw_pallas.fused_step``).  Defaults tuned on a v5e at the
        flagship (1800, 3600) size: 120/3 ≈ 0.519 ms/step (one TPU v5e,
        ``perf/run.py --workload sw_ref.periodic``); larger windows
        (144/3, 128/4, 120/5) overflow what the Mosaic compiler will
        build.
        """
        gy, gx = self.grid.shape
        if impl not in ("auto", "xla", "pallas"):
            raise ValueError(f"unknown impl {impl!r}")
        eligible = (gy, gx) == (1, 1) and self.params.periodic_x
        if impl == "pallas" and not eligible:
            raise ValueError(
                "impl='pallas' needs a 1x1 grid with periodic_x=True"
            )
        if impl == "auto" and eligible:
            # compiled-kernel path only where it pays; off-TPU the kernel
            # would run interpreted (tests opt in via impl="pallas")
            from ..ops.flash import target_platform

            use_pallas = target_platform() == "tpu"
        else:
            use_pallas = impl == "pallas"

        tr, fz = (tile_rows, fuse) if use_pallas else (0, 1)

        def local(*flat):
            s = SWState(*flat)
            if use_pallas:
                from . import _sw_pallas

                shape = s.h.shape
                # pad to the kernel's aligned block ONCE, outside
                # the time loop (12 extra copies/step otherwise).
                # Single-step calls reuse the fused tiling's T so
                # both kernels agree on the padded shape.
                T_eff, _, _ = _sw_pallas._tiling(shape[0], tr, fz)
                s = _sw_pallas.pad_rows(s, tile_rows=tr, fuse=fz)

                def one_step(st, is_first):
                    return _sw_pallas.fused_step(
                        st, self.params, first=is_first,
                        logical_shape=shape, tile_rows=T_eff,
                        fuse=1,
                    )

                def fused_steps(st):
                    return _sw_pallas.fused_step(
                        st, self.params, first=False,
                        logical_shape=shape, tile_rows=tr,
                        fuse=fz,
                    )
            else:
                def one_step(st, is_first):
                    return self._step_local(st, is_first)

                fused_steps = None

            if first:
                s = one_step(s, True)
                remaining = n_steps - 1
            else:
                remaining = n_steps
            if fused_steps is not None and fz > 1:
                # temporal blocking: whole fused calls, then the
                # remainder one step at a time.  The calls go two per
                # loop iteration (ping-pong): the kernel writes fresh
                # buffers while it still reads the carry, so with one
                # call per body XLA copies all six outputs back into the
                # carry every iteration; with two, the carry is dead once
                # the first call has read it and the second call's
                # outputs take its buffers.  (Aliasing a call's outputs
                # onto its own inputs is unsound: tile i+1 reads tile
                # i's rows as its top halo.)
                whole, rest = divmod(remaining, fz)
                if whole > 1:
                    s = lax.fori_loop(
                        0, whole // 2,
                        lambda _, st: fused_steps(fused_steps(st)), s)
                if whole % 2:
                    s = fused_steps(s)
                for _ in range(rest):
                    s = one_step(s, False)
            elif remaining > 0:
                s = lax.fori_loop(
                    0,
                    remaining,
                    lambda _, st: one_step(st, False),
                    s,
                )
            if use_pallas:
                s = _sw_pallas.unpad_rows(s, shape)
            return s

        spec = P(*self.grid.axes)
        mapped = jax.shard_map(
            local,
            mesh=self.grid.mesh,
            in_specs=spec,
            out_specs=SWState(*(spec,) * 6),
            check_vma=False,
        )

        # named programs: the HLO module and the trace's XLA Modules line
        # tell a bootstrap call from an advance call
        def sw_bootstrap(state):
            return mapped(*state)

        def sw_advance(state):
            return mapped(*state)

        return jax.jit(
            sw_bootstrap if first else sw_advance,
            donate_argnums=(0,) if donate else (),
        )

    def interior(self, field: jax.Array) -> np.ndarray:
        """Reassemble the physical (ny, nx) field from stacked blocks."""
        gy, gx = self.grid.shape
        b = np.asarray(field).reshape(
            gy, self.block_shape[0], gx, self.block_shape[1]
        )
        b = b[:, 1:-1, :, 1:-1]  # (gy, ny_loc, gx, nx_loc)
        return b.reshape(self.ny, self.nx)

    def total_mass(self, state: SWState) -> float:
        return float(np.sum(self.interior(state.h)) * self.params.dx * self.params.dy)
