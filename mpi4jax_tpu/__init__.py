"""mpi4jax_tpu — TPU-native MPI-style primitives for JAX.

The capability contract of mpi4jax (see SURVEY.md), rebuilt TPU-first: the
twelve point-to-point and collective operations callable inside ``jax.jit``,
with SPMD/ordered-effect execution ordering, autodiff and batching for the
differentiable collectives, debug tracing, and fail-fast error handling.

Two tiers behind one API (DESIGN.md):
- **mesh tier**: ops compile to XLA collectives over ICI inside
  ``shard_map`` — the TPU fast path (``spmd``, ``make_mesh``, ``MeshComm``);
- **world tier**: one process per rank over the native C++ transport
  (``mpi4jax_tpu.runtime``), for MPMD programs and DCN-scale jobs.

Public API parity with /root/reference/mpi4jax/__init__.py:9-39 (12 ops +
capability probe), with ReduceOps as framework objects instead of mpi4py
handles.
"""

from .utils import jax_compat as _jax_compat

_jax_compat.check_jax_version()

from .ops import (  # noqa: E402
    ALL_OPS,
    BAND,
    BOR,
    BXOR,
    LAND,
    LOR,
    LXOR,
    MAX,
    MIN,
    PROD,
    SUM,
    ReduceOp,
    allgather,
    allreduce,
    alltoall,
    alltoallv,
    as_reduce_op,
    barrier,
    bcast,
    create_token,
    custom_op,
    gather,
    neighbor_exchange,
    permute,
    recv,
    reduce,
    scan,
    scatter,
    send,
    sendrecv,
)
from .ops._world_impl import explicit_token_ordering  # noqa: E402
from .parallel import (  # noqa: E402
    MeshComm,
    current_comm,
    get_default_comm,
    make_mesh,
    spmd,
)
from . import elastic  # noqa: E402
from .elastic import RankFailure  # noqa: E402
from .runtime.transport import WorldComm  # noqa: E402
from .utils.status import ANY_SOURCE, ANY_TAG, Status  # noqa: E402
from .utils.tracing import set_logging  # noqa: E402

__version__ = "0.1.0"


def has_ici_support() -> bool:
    """True when a TPU/accelerator backend with >1 addressable device (an ICI
    domain a mesh can span) is present.  The spiritual analog of the
    reference's ``has_cuda_support`` (_src/utils.py:158-164)."""
    import jax

    try:
        return len(jax.devices()) > 1 or jax.devices()[0].platform != "cpu"
    except RuntimeError:
        return False


def _flush(timeout=None):
    """Block until all pending communication effects have executed.

    Parity with the reference's ``flush`` / atexit barrier
    (_src/flush.py:4-6): pending async dispatch at interpreter teardown can
    deadlock multi-process jobs.
    """
    import jax

    jax.effects_barrier()


import atexit as _atexit  # noqa: E402

_atexit.register(_flush)

__all__ = [
    "allgather",
    "allreduce",
    "alltoall",
    "alltoallv",
    "barrier",
    "bcast",
    "create_token",
    "gather",
    "permute",
    "neighbor_exchange",
    "recv",
    "reduce",
    "scan",
    "scatter",
    "send",
    "sendrecv",
    "ReduceOp",
    "as_reduce_op",
    "custom_op",
    "ALL_OPS",
    "SUM",
    "PROD",
    "MAX",
    "MIN",
    "LAND",
    "LOR",
    "LXOR",
    "BAND",
    "BOR",
    "BXOR",
    "MeshComm",
    "current_comm",
    "get_default_comm",
    "WorldComm",
    "elastic",
    "RankFailure",
    "make_mesh",
    "spmd",
    "set_logging",
    "has_ici_support",
    "explicit_token_ordering",
    "Status",
    "ANY_TAG",
    "ANY_SOURCE",
    "__version__",
]
