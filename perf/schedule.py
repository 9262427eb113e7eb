"""The one generator of work: turns a traffic file and a seed into calls.

A traffic file (``perf/traffic/<name>.json``) holds parameters only:

- ``steps_per_call``: model steps in one call of the solver's step
  function;
- ``bootstrap_steps``: steps of the first call of a solve (the forward
  Euler start);
- ``initial_states``: how many distinct initial states set-up draws from
  the seed; solves take them in turn;
- ``perturbation``: ``amplitude``, ``modes`` and ``max_wavenumber`` of the
  seeded perturbation added to each initial state;
- ``trace_solves``: whole solves that a ``--trace 1`` run records;
- ``check_solves``: finished solves, drawn from the seed, that are
  compared with the reference after the window.

A solve covers the configuration's steps (``steps_needed``): the
bootstrap call, then whole calls until the count is reached, as a user's
time loop does.  Every seed gets the same calls; only the initial
values differ.
"""

from __future__ import annotations

import numpy as np


def solve_calls(steps_needed, traffic):
    """``[(kind, n_steps), ...]`` of one solve."""
    first = traffic["bootstrap_steps"]
    per_call = traffic["steps_per_call"]
    n_calls = -(-(steps_needed - first) // per_call)
    return [("bootstrap", first)] + [("advance", per_call)] * n_calls


def rng(seed, stream):
    """Generator for one purpose of one seed; any whole number works."""
    return np.random.default_rng([int(seed) % 2**63, stream])


def initial_modes(seed, traffic):
    """Per initial state, the ``(modes, 5)`` perturbation parameters
    ``(amplitude, kx, ky, phase_x, phase_y)``; amplitudes sum to
    ``amplitude``."""
    p = traffic["perturbation"]
    gen = rng(seed, 0)
    out = []
    for _ in range(traffic["initial_states"]):
        amp = gen.uniform(0.5, 1.0, p["modes"])
        amp *= p["amplitude"] / amp.sum()
        kx = gen.integers(1, p["max_wavenumber"] + 1, p["modes"])
        ky = gen.integers(1, p["max_wavenumber"] + 1, p["modes"])
        phase = gen.uniform(0.0, 2 * np.pi, (2, p["modes"]))
        out.append(np.stack([amp, kx, ky, phase[0], phase[1]],
                            axis=1).astype(np.float32))
    return out


def checked_solves(seed, finished, traffic):
    """Which of the ``finished`` solve slots to compare, drawn from the
    seed."""
    gen = rng(seed, 1)
    k = min(traffic["check_solves"], len(finished))
    return sorted(gen.choice(sorted(finished), size=k, replace=False)
                  .tolist())
