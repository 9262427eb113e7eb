from .allgather import allgather
from .allreduce import allreduce
from .alltoall import alltoall
from .alltoallv import alltoallv
from .barrier import barrier
from .bcast import bcast
from .gather import gather
from .recv import recv
from .reduce import reduce
from .reduce_ops import (
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
    as_reduce_op,
    custom_op,
)
from .scan import scan
from .scatter import scatter
from .send import send
from .neighbor import neighbor_exchange
from .sendrecv import permute, sendrecv
from ._dispatch import create_token

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
]
