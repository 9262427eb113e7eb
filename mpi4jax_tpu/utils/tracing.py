"""Debug tracing: per-call entry/exit log lines with rank, call id, timing.

Parity with the reference's single observability mechanism
(/root/reference/mpi4jax/_src/xla_bridge/mpi_xla_bridge.pyx:38-60,100-112 and
SURVEY.md §5.1): when enabled (``MPI4JAX_TPU_DEBUG=1`` or ``set_logging``),
every communicating call emits

    r<rank> | <id8> | <Op> <details>
    r<rank> | <id8> | <Op> done with code 0 (<dt> s)

Lines go to **stderr** in one atomic ``write`` each: stdout belongs to
programs' own output (benchmarks emit JSON results there — a debug line
spliced into a JSON record corrupts it), and per-line atomicity keeps
multi-rank output from interleaving mid-line (the native transport's
mirror of this format, ``native/tpucomm.cc``, already behaves this way).

The world tier logs at execution time from the host side.  Mesh-tier
ops run on the device inside one compiled program and write no lines; a
profiler trace shows them (docs/observability.md, "Device scopes").

``CallTrace`` additionally feeds the structured observability recorder
(``mpi4jax_tpu.obs``) when it is armed: every traced op becomes a span
with peer/bytes/tag fields in the recording, independent of whether the
debug *lines* are enabled.
"""

from __future__ import annotations

import itertools
import sys
import time

from . import config

_PRINT_DEBUG: bool | None = None


def set_logging(enabled: bool) -> None:
    global _PRINT_DEBUG
    _PRINT_DEBUG = bool(enabled)


def logging_enabled() -> bool:
    if _PRINT_DEBUG is not None:
        return _PRINT_DEBUG
    return config.debug_enabled()


# Monotonic per-rank call counter: the previous implementation drew
# secrets.token_hex(4) — an os.urandom syscall — on EVERY traced call,
# measurable on microsecond-scale ops.  The 8-hex-digit line format is
# unchanged; ids now count up (and are trivially sortable in logs).
_CALL_COUNTER = itertools.count()


def new_call_id() -> str:
    return f"{next(_CALL_COUNTER) & 0xFFFFFFFF:08x}"


def log_line(rank, call_id: str, message: str) -> None:
    # one write() per line: atomic up to PIPE_BUF, so concurrent ranks
    # sharing the launcher's stderr cannot interleave mid-line
    sys.stderr.write(f"r{rank} | {call_id} | {message}\n")
    sys.stderr.flush()


_obs_state = None  # lazily-bound obs._recorder module (import once)


def _obs_enabled() -> bool:
    # disabled-path cost: one global check + one module-attribute read
    # (the import runs once, on the first traced call ever)
    global _obs_state
    if _obs_state is None:
        from ..obs import _recorder

        _obs_state = _recorder
    return _obs_state._ENABLED


class CallTrace:
    """Context manager for host-side op tracing (world tier).

    ``details`` may be a zero-arg callable, evaluated only when logging
    is enabled — hot-path callers (e.g. the collective-algorithm name
    lookup, a native call per op) pay nothing when tracing is off.

    ``peer``/``nbytes``/``tag``/``algo`` label the recorded span when
    the observability recorder (``mpi4jax_tpu.obs``) is armed; they are
    never formatted into the debug lines.

    The disabled path is deliberately thin (slots, no call-id draw, no
    clock reads): this wrapper sits on every world-tier op, where the
    whole dispatch budget is a few microseconds (the async-progress-
    engine PR measured the old ~3 us disabled cost as a visible share
    of the 1 KB in-jit latency).
    """

    __slots__ = ("rank", "opname", "details", "call_id", "peer", "nbytes",
                 "tag", "algo", "_t0", "_t0_unix", "_log", "_obs")

    def __init__(self, rank: int, opname: str, details="", *, peer=-1,
                 nbytes=0, tag=0, algo=None):
        self.rank = rank
        self.opname = opname
        self.details = details
        self.peer = peer
        self.nbytes = nbytes
        self.tag = tag
        self.algo = algo

    def __enter__(self):
        self._log = logging_enabled()
        self._obs = _obs_enabled()
        if self._log:
            self.call_id = new_call_id()
            details = self.details() if callable(self.details) else self.details
            log_line(
                self.rank, self.call_id, f"{self.opname} {details}".rstrip()
            )
        if self._log or self._obs:
            if self._obs:
                self._t0_unix = time.time()
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None and (self._log or self._obs):
            dt = time.perf_counter() - self._t0
            if self._log:
                log_line(
                    self.rank,
                    self.call_id,
                    f"{self.opname} done with code 0 ({dt:.6f} s)",
                )
            if self._obs:
                _obs_state.record_span(
                    self.opname, self._t0_unix, dt, peer=self.peer,
                    nbytes=self.nbytes, tag=self.tag, algo=self.algo,
                )
        return False
