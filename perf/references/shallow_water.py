"""Plain shallow-water reference, written from the equations.

The scheme of the mpi4jax shallow-water benchmark
(https://mpi4jax.readthedocs.io/en/latest/shallow-water.html): Sadourny's
energy-conserving C-grid discretisation of the nonlinear shallow-water
equations, Adams-Bashforth time stepping (a = 1.6, b = -0.6, the first
step forward Euler), lateral viscosity 1e-3 * f * dx**2, walls in y, and
in x either periodic or walls (the configuration's ``periodic_x``).

Everything here is one global domain in straightforward ``jax.numpy``:
no decomposition, no halo exchange, no kernel, and nothing imported from
the package under test.  Fields are ``(ny + 2, nx + 2)`` with one ghost
ring, the layout a user of the solver reads back:

- periodic x: ghost columns repeat the opposite interior column;
- walls in x: ghost columns are wall values that no step changes, like
  the ghost rows (``h`` continues its edge column, ``u`` keeps the jet,
  ``v`` is zero), and ``u`` on the last interior column is the
  no-normal-flow east wall (zero);
- ghost rows are wall values that no step changes: ``h`` continues its
  edge row, ``u`` keeps the jet profile evaluated one row beyond the
  edge, ``v`` is zero;
- ``v`` on the last interior row is the no-normal-flow wall (zero);
- flux, vorticity, energy and viscous-flux fields are zero on their
  ghost rows, and between walls in x on their ghost columns;
- the layer thickness used for fluxes is edge-valued on its wall rows,
  and between walls in x on its wall columns.

``dtype`` selects the precision of every field and every operation:
float32 is the reference, a lower one is the control that the
comparison must reject.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DAY_SECONDS = 86_400.0
AB_A, AB_B = 1.6, -0.6
# the constants a step reads from the configuration
CONSTANTS = ("dx", "dy", "gravity", "depth", "coriolis_f", "coriolis_beta",
             "periodic_x")


def time_step(config):
    """CFL time step: an eighth of a cell crossing by a gravity wave."""
    return 0.125 * min(config["dx"], config["dy"]) / float(
        np.sqrt(config["gravity"] * config["depth"]))


def steps_per_solve(config):
    """Model steps that cover ``config["model_days"]``."""
    return int(config["model_days"] * DAY_SECONDS / time_step(config))


# -- neighbour views of the interior --------------------------------------
def _at(a, dj, di):
    """Interior-shaped view of ``a`` shifted by ``dj`` rows, ``di`` cols."""
    ny, nx = a.shape[0] - 2, a.shape[1] - 2
    return a[1 + dj:1 + dj + ny, 1 + di:1 + di + nx]


def _x_ghosts(a, periodic):
    """Periodic x: each ghost column repeats the opposite interior
    column.  Walls in x: the ghost columns keep what they hold."""
    if not periodic:
        return a
    return a.at[:, 0].set(a[:, -2]).at[:, -1].set(a[:, 1])


def _v_wall(a):
    return a.at[-2, :].set(0)


def _u_wall(a, periodic):
    """Walls in x: no flow through the east wall, a u-point field's last
    interior column (ghost rows included) is zero."""
    return a if periodic else a.at[:, -2].set(0)


def _ring0(x, periodic):
    """Interior values with a zero ghost ring, then the x ghosts."""
    return _x_ghosts(jnp.pad(x, 1), periodic)


def _set_interior(a, x, periodic):
    return _x_ghosts(a.at[1:-1, 1:-1].set(x), periodic)


# -- initial state ---------------------------------------------------------
def initial_state(config, modes, dtype=jnp.float32):
    """Geostrophic jet plus a perturbation of ``h``, on the global grid.

    ``modes`` is an ``(m, 5)`` array of ``(amplitude, kx, ky, phase_x,
    phase_y)``: each adds ``amplitude * sin(2 pi kx x / Lx + phase_x) *
    cos(pi ky y / Ly + phase_y)`` to ``h``.  Integer ``kx`` keeps ``h``
    periodic in x; between walls any ``kx`` serves.  Returns ``(h, u, v,
    dh, du, dv)``.
    """
    periodic = config["periodic_x"]
    ny, nx = config["ny"], config["nx"]
    dx, dy = config["dx"], config["dy"]
    f0, beta, g = (config["coriolis_f"], config["coriolis_beta"],
                   config["gravity"])
    ly, lx = ny * dy, nx * dx
    # coordinates of every row and column, ghosts included
    y = jnp.arange(-1, ny + 1, dtype=jnp.float32) * dy
    x = jnp.arange(-1, nx + 1, dtype=jnp.float32) * dx
    yy, xx = jnp.meshgrid(y, x, indexing="ij")

    u = 10.0 * jnp.exp(-((yy - 0.5 * ly) ** 2) / (0.02 * lx) ** 2)
    f = f0 + beta * yy
    # geostrophic balance g dh/dy = -f u, integrated over the interior
    # rows from the south wall, then centred on the resting depth
    dh_dy = -dy * (u * f)[1:-1, 1:-1] / g
    h_int = jnp.cumsum(dh_dy, axis=0)
    h_int = config["depth"] + h_int - jnp.mean(h_int)
    xi, yi = xx[1:-1, 1:-1], yy[1:-1, 1:-1]
    for amp, kx, ky, px, py in modes:
        h_int = h_int + amp * (
            jnp.sin(2 * jnp.pi * kx * xi / lx + px)
            * jnp.cos(jnp.pi * ky * yi / ly + py))
    h = _x_ghosts(jnp.pad(h_int, 1, mode="edge"), periodic)
    u = _u_wall(_x_ghosts(u, periodic), periodic)
    v = jnp.zeros_like(u)
    zero = jnp.zeros_like(u)
    return tuple(a.astype(dtype) for a in (h, u, v, zero, zero, zero))


# -- one time step ---------------------------------------------------------
def step(state, config, first):
    h, u, v, dh, du, dv = state
    dtype = h.dtype
    dx, dy = config["dx"], config["dy"]
    f0, beta, g = (config["coriolis_f"], config["coriolis_beta"],
                   config["gravity"])
    dt = time_step(config)
    nu = 1e-3 * f0 * dx ** 2
    ny = h.shape[0] - 2
    per = config["periodic_x"]

    # layer thickness with edge-valued wall rows (and wall columns)
    hh = h.at[0].set(h[1]).at[-1].set(h[-2])
    if not per:
        hh = hh.at[:, 0].set(hh[:, 1]).at[:, -1].set(hh[:, -2])

    # mass fluxes, potential vorticity and kinetic energy
    fe = _u_wall(_ring0(0.5 * (_at(hh, 0, 0) + _at(hh, 0, 1))
                        * _at(u, 0, 0), per), per)
    fn = _v_wall(_ring0(0.5 * (_at(hh, 0, 0) + _at(hh, 1, 0))
                        * _at(v, 0, 0), per))
    zeta = ((_at(v, 0, 1) - _at(v, 0, 0)) / dx
            - (_at(u, 1, 0) - _at(u, 0, 0)) / dy)
    thickness = 0.25 * (_at(hh, 0, 0) + _at(hh, 0, 1)
                        + _at(hh, 1, 0) + _at(hh, 1, 1))
    y = (jnp.arange(ny, dtype=dtype) * dy)[:, None]
    coriolis = f0 + beta * y
    q = _ring0((coriolis + zeta) / thickness, per)
    ke = _ring0(0.5 * (0.5 * (_at(u, 0, 0) ** 2 + _at(u, 0, -1) ** 2)
                       + 0.5 * (_at(v, 0, 0) ** 2 + _at(v, -1, 0) ** 2)),
                per)

    # tendencies
    dh_new = (-(_at(fe, 0, 0) - _at(fe, 0, -1)) / dx
              - (_at(fn, 0, 0) - _at(fn, -1, 0)) / dy)
    du_new = (-g * (_at(h, 0, 1) - _at(h, 0, 0)) / dx
              + 0.5 * (_at(q, 0, 0) * 0.5 * (_at(fn, 0, 0) + _at(fn, 0, 1))
                       + _at(q, -1, 0) * 0.5
                       * (_at(fn, -1, 0) + _at(fn, -1, 1)))
              - (_at(ke, 0, 1) - _at(ke, 0, 0)) / dx)
    dv_new = (-g * (_at(h, 1, 0) - _at(h, 0, 0)) / dy
              - 0.5 * (_at(q, 0, 0) * 0.5 * (_at(fe, 0, 0) + _at(fe, 1, 0))
                       + _at(q, 0, -1) * 0.5
                       * (_at(fe, 0, -1) + _at(fe, 1, -1)))
              - (_at(ke, 1, 0) - _at(ke, 0, 0)) / dy)

    # Adams-Bashforth (forward Euler on the first step)
    a, b = (1.0, 0.0) if first else (AB_A, AB_B)
    h = _set_interior(
        h, _at(h, 0, 0) + dt * (a * dh_new + b * _at(dh, 0, 0)), per)
    u = _u_wall(_set_interior(
        u, _at(u, 0, 0) + dt * (a * du_new + b * _at(du, 0, 0)), per), per)
    v = _v_wall(_set_interior(
        v, _at(v, 0, 0) + dt * (a * dv_new + b * _at(dv, 0, 0)), per))

    # lateral viscosity: divergence of nu * grad, zero flux through walls
    def viscous(c):
        flux_x = _u_wall(_ring0(nu * (_at(c, 0, 1) - _at(c, 0, 0)) / dx,
                                per), per)
        flux_y = _v_wall(_ring0(nu * (_at(c, 1, 0) - _at(c, 0, 0)) / dy,
                                per))
        return ((_at(flux_x, 0, 0) - _at(flux_x, 0, -1)) / dx
                + (_at(flux_y, 0, 0) - _at(flux_y, -1, 0)) / dy)

    u, v = (_u_wall(_set_interior(u, _at(u, 0, 0) + dt * viscous(u), per),
                    per),
            _v_wall(_set_interior(v, _at(v, 0, 0) + dt * viscous(v), per)))
    return (h, u, v, jnp.pad(dh_new, 1), jnp.pad(du_new, 1),
            jnp.pad(dv_new, 1))


@functools.partial(jax.jit, static_argnames=("config_items", "n_steps"))
def _solve(state, config_items, n_steps):
    config = dict(config_items)
    state = step(state, config, first=True)
    return jax.lax.fori_loop(
        1, n_steps, lambda _, s: step(s, config, first=False), state)


def solve(state, config, n_steps):
    """``n_steps`` steps from ``state``, the first one forward Euler."""
    items = tuple((k, config[k]) for k in CONSTANTS)
    return _solve(tuple(state), items, n_steps)


def readings(got, want):
    """The numbers the check compares: for each of h, u and v, the
    largest ``|got - want|`` over the field's scale in the reference,
    ``max |h|`` for h and the largest flow speed for u and v (v alone is
    about a hundredth of u here, so its own maximum would magnify the
    same error a hundredfold).  A field that is not finite reads NaN."""
    speed = max(np.max(np.abs(want["u"])), np.max(np.abs(want["v"])))
    scale = {"h": np.max(np.abs(want["h"])), "u": speed, "v": speed}
    return {f"{f}_err": float(np.max(np.abs(
        got[f].astype(np.float64) - want[f])) / scale[f]) for f in "huv"}
