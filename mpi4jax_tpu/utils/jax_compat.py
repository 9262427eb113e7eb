"""JAX version handling.

The reference pins a "latest known good" jax and warns beyond it
(/root/reference/mpi4jax/_src/jax_compat.py:24-47).  We do the same with a
much smaller surface: this framework is written for jax 0.9 and carries
no shims for older releases.
"""

from __future__ import annotations

import warnings

from . import config

MIN_JAX_VERSION = (0, 9, 0)
LATEST_TESTED_JAX_VERSION = (0, 9, 0)


def _parse(version: str) -> tuple:
    parts = []
    for piece in version.split(".")[:3]:
        digits = "".join(ch for ch in piece if ch.isdigit())
        parts.append(int(digits or 0))
    return tuple(parts)


def check_jax_version() -> None:
    import jax

    found = _parse(jax.__version__)
    if found < MIN_JAX_VERSION:
        raise ImportError(
            f"mpi4jax_tpu requires jax >= {'.'.join(map(str, MIN_JAX_VERSION))}, "
            f"found {jax.__version__}"
        )
    if found > LATEST_TESTED_JAX_VERSION and not config.flag(
        "MPI4JAX_TPU_NO_WARN_JAX_VERSION"
    ):
        warnings.warn(
            f"jax {jax.__version__} is newer than the latest version tested "
            f"with mpi4jax_tpu "
            f"({'.'.join(map(str, LATEST_TESTED_JAX_VERSION))}). "
            "If you encounter problems, pin jax or set "
            "MPI4JAX_TPU_NO_WARN_JAX_VERSION=1 to silence this warning.",
            UserWarning,
        )


def vma_check_mode():
    """Whether shard_map tracks varying-manual-axes (``check_vma=True``).

    Returns True/False, or ``None`` when the probe fails — the switch is
    private jax API (``jax._src.config._check_vma``), and this is the one
    place that reads it, so a future rename is a one-line fix.  On None,
    callers choose their own failure mode: loud where a wrong guess would
    corrupt results (``as_varying``), soft where a fallback is harmless
    (Pallas out-structs).
    """
    try:
        from jax._src import config as _jcfg

        return bool(_jcfg._check_vma.value)
    except Exception:
        return None
