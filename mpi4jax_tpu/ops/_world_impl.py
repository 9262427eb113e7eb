"""World-tier op implementations: JAX primitives over the native transport.

This is the ordered-effects core design the reference's experimental notoken
layer pioneered (SURVEY.md §2.2, notoken/collective_ops/allreduce.py:94-187
there) promoted to first-class, on jax 0.9 APIs: every op is a JAX primitive
that

- declares the framework's ordered ``CommEffect`` (utils/effects.py) in its
  abstract eval — the compiler threads a runtime token through all world ops
  in program order, which *is* the deadlock-freedom contract
  (docs/sharp-bits.rst of the reference);
- lowers to a host callback via ``emit_python_callback`` with explicit
  ``ctx.tokens_in``/``set_tokens_out`` plumbing — on TPU this callback is
  the HBM→TPU-VM-host staging path over DCN, the structural twin of the
  reference GPU bridge's sync → copy-to-host → MPI → copy-back
  (mpi_xla_bridge_gpu.pyx:233-251);
- executes the native C++ transport (runtime/bridge.py → native/tpucomm.cc)
  on the host buffers;
- carries reference-parity AD rules registered directly on the primitive:
  allreduce(SUM) JVP + identity transpose (allreduce.py:188-218 there),
  sendrecv JVP + source/dest-swapping transpose (sendrecv.py:390-409), and
  elementwise batching where semantics allow.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax._src import core
from jax._src import callback as _jax_callback
from jax._src import dispatch as _jax_dispatch
from jax._src.interpreters import mlir
from jax._src.lib.mlir import ir
from jax.interpreters import ad, batching

from ..utils import tracing
from ..utils.effects import comm_effect, unordered_comm_effect
from .reduce_ops import ALL_OPS, ReduceOp

_OP_CODE = {op.name: i for i, op in enumerate(ALL_OPS)}

# ---------------- static-analysis hooks (mpi4jax_tpu.analysis) ----------
#
# Each primitive exports its *schedule signature* — how to read one
# communication event off its params — and every eager impl offers itself
# to an installed analysis executor before touching the real transport.
# The executor (analysis._sim.VirtualWorld) owns ops whose comm is an
# AbstractComm; with none installed the hooks are two predicate checks.

#: primitive base name -> param roles for the communication verifier.
#: "peer"-valued entries name primitive params holding comm-local ranks;
#: token variants ("mpi4jax_tpu_<name>_t") share the base signature.
SCHEDULE_SIGNATURES = {
    "allreduce": {"kind": "allreduce", "reduce_op": "op"},
    "reduce": {"kind": "reduce", "reduce_op": "op", "root": "root"},
    "scan": {"kind": "scan", "reduce_op": "op"},
    "bcast": {"kind": "bcast", "root": "root"},
    "allgather": {"kind": "allgather"},
    "gather": {"kind": "gather", "root": "root"},
    "scatter": {"kind": "scatter", "root": "root"},
    "alltoall": {"kind": "alltoall"},
    "barrier": {"kind": "barrier"},
    "send": {"kind": "send", "dest": "dest", "tag": "tag"},
    "recv": {"kind": "recv", "source": "source", "tag": "tag"},
    "sendrecv": {"kind": "sendrecv", "source": "source", "dest": "dest",
                 "sendtag": "sendtag", "recvtag": "recvtag"},
    "shift2": {"kind": "shift2", "lo": "lo", "hi": "hi", "tag": "tag"},
}


def schedule_signature(prim_name: str):
    """(base_name, signature, is_token_variant) for a world primitive
    name, or None for foreign primitives."""
    if not prim_name.startswith("mpi4jax_tpu_"):
        return None
    base = prim_name[len("mpi4jax_tpu_"):]
    token_variant = base.endswith("_t")
    if token_variant:
        base = base[:-2]
    sig = SCHEDULE_SIGNATURES.get(base)
    if sig is None:
        return None
    return base, sig, token_variant


_analysis_executor = None


def _set_analysis_executor(executor):
    """Install (or with None remove) the virtual-world executor that
    serves world-tier impls during program analysis."""
    global _analysis_executor
    _analysis_executor = executor


def _analysis_intercept(prim_name, args, params):
    """Route an eager bind to the analysis executor when one is installed
    and owns the op's comm.  Returns None when the op should execute
    normally."""
    ex = _analysis_executor
    if ex is not None and ex.owns(params.get("comm")):
        return ex.run_primitive(prim_name, args, params)
    return None


# During virtual-world analysis everything executes eagerly, so the token
# chain guard below — which normally watches tracers — is handed a
# per-rank-thread pseudo-trace to key its state on, plus a hook that turns
# its warnings into structured findings.  Both are None outside analysis.
_analysis_token_trace = None   # fn(tok=None) -> pseudo-trace object
_analysis_warn_hook = None     # fn(comm, n_heads, how) -> None


def _set_analysis_token_hooks(token_trace, warn_hook):
    global _analysis_token_trace, _analysis_warn_hook
    _analysis_token_trace = token_trace
    _analysis_warn_hook = warn_hook

# ---------------- ordering mode ----------------
#
# JAX refuses ORDERED effects in computations spanning more than one
# device ("ordered effects are not supported for more than 1 device"),
# so a jit that mixes mesh-tier shard_map collectives with world-tier
# ops — the TPU-pod composition shape, SURVEY §7 hard part 4 — cannot
# carry the ordered CommEffect.  Inside ``explicit_token_ordering()``
# world primitives bind with the UNORDERED effect instead, and ordering
# becomes the caller's explicit token chain (the reference's primary L1
# design: tokens as data dependencies, docs/sharp-bits.rst there).  Use
# the ``compat.token_api`` signatures, threading every token.

# A jax config state (not a bare global) so the mode participates in the
# jit cache key and trace context: a function traced inside the context
# must never be silently reused outside it (and vice versa).
from jax._src import config as _jax_config  # noqa: E402

_explicit_tokens_cfg = _jax_config.bool_state(
    name="mpi4jax_tpu_explicit_tokens",
    default=False,
    help=(
        "world-tier ops trace with the unordered effect; ordering is the "
        "caller's explicit token chain (multi-device composition mode)"
    ),
    include_in_jit_key=True,
    include_in_trace_context=True,
)


def _ordered_now() -> bool:
    return not _explicit_tokens_cfg.value


import contextlib  # noqa: E402


@contextlib.contextmanager
def explicit_token_ordering():
    """Context manager: world ops trace with the unordered effect.

    Required for jitted programs that span multiple local devices (e.g.
    mesh-tier ``shard_map`` collectives composed with world-tier ops in
    one step).  Ordering of world ops is then carried ONLY by explicit
    token chains (``mpi4jax_tpu.compat.token_api``) or value dataflow —
    exactly the reference's token contract: unthreaded tokens mean
    undefined order.

    Backed by a jax config state, so the mode is part of the jit cache
    key — a function jitted under the context retraces (with ordered
    effects) when later called outside it.

    While the context is active, a trace-time chain guard watches for
    the reference's sharpest bit: a world op binding a FRESH token while
    other ops on the same comm chain theirs in the same trace (undefined
    order → deadlock at run time).  Default: warn; set
    ``MPI4JAX_TPU_STRICT_TOKENS=1`` to raise at trace time instead.
    """
    with _explicit_tokens_cfg(True):
        _chain_guard.enter()
        try:
            yield
        finally:
            _chain_guard.exit()


class _TokenChainGuard:
    """Trace-time detector for unthreaded/forked token chains.

    Live chain heads (tokens returned by world ops, not yet consumed)
    are tracked per ``(comm, trace)`` while ``explicit_token_ordering``
    is active.  Only *tracers* are tracked: a concrete (eager) token
    executes in Python order, where no reordering hazard exists; and
    keying by the tracer's trace object keeps separate jit traces (and
    scan bodies, which trace inner) from cross-polluting.

    ``create_token(x)`` with a data tie registers a *rooted* token —
    starting a new chain from one is legitimate (ordering rides the
    dataflow, e.g. a scan carry); a bare ``create_token()`` registers a
    *fresh* token.  Binding a KNOWN-fresh token while the same comm has
    a live head in the same trace is the footgun the reference can only
    document (docs/sharp-bits.rst:6-34 there).  Tokens the guard has
    never seen (e.g. a chained token that passed through ``lax.cond`` or
    a remat boundary and re-emerged as a new tracer) are NOT flagged —
    zero false positives on correct programs beats flagging every
    transform boundary — and the bind-side side chain orders them
    safely regardless.
    """

    def __init__(self):
        self._depth = 0
        # (id(comm), id(trace)) -> [weakref(trace), set of id(token)].
        # Only token IDS are stored (the jaxpr under construction keeps
        # the tracers — and therefore their ids — alive for the trace's
        # lifetime); the trace weakref prunes a bucket once its trace is
        # collected, so a long-lived explicit_token_ordering() context
        # does not accumulate state across retraces.
        self._heads = {}
        self._rooted = {}   # id(trace) -> [weakref(trace), set of id(tok)]
        self._fresh = {}    # id(trace) -> [weakref(trace), set of id(tok)]

    def enter(self):
        self._depth += 1
        self._prune()

    def exit(self):
        self._depth -= 1
        if self._depth <= 0:
            self._depth = 0
            self._heads.clear()
            self._rooted.clear()
            self._fresh.clear()

    @property
    def active(self):
        return self._depth > 0

    def _prune(self):
        for store in (self._heads, self._rooted, self._fresh):
            dead = [k for k, v in store.items() if v[0]() is None]
            for k in dead:
                del store[k]

    @staticmethod
    def _wref(trace):
        import weakref

        try:
            return weakref.ref(trace)
        except TypeError:
            return lambda: trace  # unweakrefable: keep (bounded by prune)

    @staticmethod
    def _trace_of(tok):
        import jax

        if isinstance(tok, jax.core.Tracer):
            return getattr(tok, "_trace", None)
        if _analysis_token_trace is not None:
            # virtual-world analysis: concrete tokens, Python-ordered per
            # rank thread — key chain state on the thread's pseudo-trace
            return _analysis_token_trace(tok)
        return None

    def note_rooted(self, tok):
        trace = self._trace_of(tok) if self.active else None
        if trace is None:
            return
        ent = self._rooted.setdefault(id(trace), [self._wref(trace), set()])
        ent[1].add(id(tok))

    def note_fresh(self, tok):
        trace = self._trace_of(tok) if self.active else None
        if trace is None:
            return
        ent = self._fresh.setdefault(id(trace), [self._wref(trace), set()])
        ent[1].add(id(tok))

    def _is_rooted(self, trace, tok):
        ent = self._rooted.get(id(trace))
        return ent is not None and id(tok) in ent[1]

    def _is_fresh(self, trace, tok):
        ent = self._fresh.get(id(trace))
        return ent is not None and id(tok) in ent[1]

    def note_op(self, comm, tok_in, tok_out):
        if not self.active:
            return
        trace = self._trace_of(tok_in)
        if trace is None:
            return
        if len(self._heads) > 32:
            self._prune()
        key = (id(comm), id(trace))
        ent = self._heads.setdefault(key, [self._wref(trace), set()])
        heads = ent[1]
        if id(tok_in) in heads:
            heads.discard(id(tok_in))       # chain continues
        elif heads and self._is_fresh(trace, tok_in):
            self._warn(comm, len(heads), "binding a fresh (unrooted) token")
        heads.add(id(tok_out))

    def note_unthreaded(self, comm):
        """A world op traced with NO token at all (primary tokenless
        signature) inside explicit mode: undefined order against any
        live chain on the same comm in the current trace."""
        if not self.active:
            return
        trace = getattr(core.trace_ctx, "trace", None)
        if trace is None or type(trace).__name__ == "EvalTrace":
            if _analysis_token_trace is None:
                return
            trace = _analysis_token_trace()
        ent = self._heads.get((id(comm), id(trace)))
        if ent and ent[1]:
            self._warn(comm, len(ent[1]), "traced with no token")

    def _warn(self, comm, n_heads, how):
        import warnings

        from ..utils import config as _config

        if _analysis_warn_hook is not None:
            _analysis_warn_hook(comm, n_heads, how)

        msg = (
            f"explicit_token_ordering: a world op on comm {comm!r} is "
            f"{how} while {n_heads} other "
            "token chain(s) on the same comm are live in this trace — "
            "the ops' relative order is UNDEFINED and can deadlock at "
            "run time.  Thread the previous op's token (or root a new "
            "chain with create_token(x) tied to a value that depends on "
            "it).  Set MPI4JAX_TPU_STRICT_TOKENS=1 to make this an "
            "error, or =0 to silence it."
        )
        strict = _config.flag("MPI4JAX_TPU_STRICT_TOKENS", None)
        if strict:
            raise RuntimeError(msg)
        if strict is None:
            warnings.warn(msg, stacklevel=4)


_chain_guard = _TokenChainGuard()


def _contig(x) -> np.ndarray:
    # NB: np.ascontiguousarray promotes 0-d to 1-d; np.asarray + explicit
    # copy preserves shape
    a = np.asarray(x)
    return a if a.flags.c_contiguous else a.copy(order="C")


def _np(x, aval):
    return _contig(np.asarray(x, dtype=aval.dtype))


def _emit_unordered_callback(ctx, callback, args):
    """Side-effecting host callback with no compiler token (explicit-token
    mode): the SPMD partitioner requires a sharding on side-effecting
    custom calls; MAXIMAL-on-device-0 runs the transport once per process
    (jax's own pure_callback convention).  Ordering is the caller's
    token/dataflow chain."""
    op_sharding = _jax_callback._callback_op_sharding(
        ctx.module_context.axis_context, None, ctx.avals_out
    )
    results, _, _ = _jax_callback.emit_python_callback(
        ctx, callback, None, list(args), ctx.avals_in, ctx.avals_out,
        has_side_effect=True, returns_token=False, sharding=op_sharding,
    )
    return results


def _eager_impl(p):
    """Eager impl: offer the call to an installed analysis executor,
    else compile the primitive alone (the ordered-callback or FFI
    lowering below)."""

    def eager_impl(*args, **params):
        analyzed = _analysis_intercept(p.name, args, params)
        if analyzed is not None:
            return analyzed
        return _jax_dispatch.apply_primitive(p, *args, **params)

    return eager_impl


def _make_primitive(name, out_aval_fn, host_fn):
    """A world-tier primitive: ordered effect + host-callback lowering.

    ``host_fn(*np_args, **params) -> np.ndarray`` runs on the host;
    ``out_aval_fn(*avals, **params) -> ShapedArray`` declares the result.
    """
    p = core.Primitive(f"mpi4jax_tpu_{name}")
    p.def_impl(_eager_impl(p))

    def abstract_eval(*avals, **params):
        ordered = params.pop("ordered", True)
        eff = comm_effect if ordered else unordered_comm_effect
        return out_aval_fn(*avals, **params), {eff}

    p.def_effectful_abstract_eval(abstract_eval)

    def lowering(ctx, *args, **params):
        ordered = params.pop("ordered", True)
        out_aval = ctx.avals_out[0]

        def _callback(*flat):
            result = host_fn(
                *[_np(a, av) for a, av in zip(flat, ctx.avals_in)], **params
            )
            return (_contig(np.asarray(result, dtype=out_aval.dtype)),)

        if not ordered:
            return _emit_unordered_callback(ctx, _callback, args)
        token = ctx.tokens_in.get(comm_effect)
        results, token, _ = _jax_callback.emit_python_callback(
            ctx,
            _callback,
            token,
            list(args),
            ctx.avals_in,
            ctx.avals_out,
            has_side_effect=True,
            returns_token=True,
        )
        ctx.set_tokens_out(mlir.TokenSet({comm_effect: token}))
        return results

    mlir.register_lowering(p, lowering)
    p._callback_lowering = lowering
    return p


# ---------------- native FFI fast path (cpu platform) ----------------
#
# On cpu the primitives lower to typed XLA FFI custom calls handled
# natively (native/tpucomm_ffi.cc) — the modern analog of the reference's
# Cython custom-call decoders (mpi_xla_bridge_cpu.pyx:20-209 there), with
# scalar params as custom-call attributes instead of operand buffers.  The
# ordered-effect token rides the call as a real operand/result, so ordering
# is identical to the callback path.  On tpu the host-callback lowering
# (HBM→host staging) remains in force.


def _i64_attr(v):
    return ir.IntegerAttr.get(ir.IntegerType.get_signless(64), int(v))


def _i32_attr(v):
    return ir.IntegerAttr.get(ir.IntegerType.get_signless(32), int(v))


def _ffi_attrs(comm=None, op=None, **scalars):
    attrs = {"comm": _i64_attr(comm.handle)}
    if op is not None:
        attrs["op"] = _i32_attr(_OP_CODE[op.name])
    for name, value in scalars.items():
        attrs[name] = _i32_attr(value)
    return attrs


def _emit_ffi_call(ctx, target, args, attrs, alias_in_out=False):
    token = ctx.tokens_in.get(comm_effect)
    result_types = [mlir.token_type()] + [
        mlir.aval_to_ir_type(a) for a in ctx.avals_out
    ]
    call = mlir.custom_call(
        target,
        result_types=result_types,
        operands=[token, *args],
        backend_config=attrs,
        has_side_effect=True,
        api_version=4,
        # in-place ops (same-shape, handler tolerates in == out) alias the
        # data operand onto the result so XLA reuses the buffer instead of
        # materializing a copy — per-op payload-sized savings inside jit
        # (measured ~9 ms/op at 16 MB before aliasing)
        operand_output_aliases={1: 1} if alias_in_out else None,
    )
    token_out, *results = call.results
    ctx.set_tokens_out(mlir.TokenSet({comm_effect: token_out}))
    return results


def _ici_leg_blocks_ffi() -> bool:
    """True when the ICI data-plane leg (topo/_ici_leg.py) could claim
    allreduce calls at runtime: those must keep the host-callback route
    — the leg hooks ``bridge.allreduce_raw``, which the native FFI
    custom call bypasses.  Conservative by design (``force``, or
    ``auto`` with TPU chips present): the per-call dtype/op/topology
    gates live in the bridge hook, and a callback-routed allreduce the
    leg then declines still runs the identical native schedule."""
    from ..utils import config

    mode = config.ici_leg_mode()
    if mode == "off":
        return False
    if mode == "force":
        return True
    from .. import topo

    return topo._tpu_chip_count() > 0


def _register_ffi_lowering(p, target, identity_param=None,
                           alias_in_out=False):
    """cpu lowering: native FFI custom call, falling back to the host
    callback when the fast path is unavailable or disabled.

    ``identity_param`` names a boolean primitive param that short-circuits
    the lowering to the identity (allreduce's transposed adjoint pass,
    reference allreduce.py:87-89); it is never sent as an FFI attribute.

    ``alias_in_out`` marks ops whose native handler accepts
    ``sendbuf == recvbuf`` (allreduce/reduce/scan/bcast, and recv whose
    operand is a dead shape carrier) — NOT sendrecv/alltoall, whose
    send side still reads the operand while the receive side writes.
    """

    def lowering(ctx, *args, **params):
        if identity_param is not None and params.pop(identity_param, False):
            return [args[0]]  # identity pass, no communication
        from ..runtime import bridge

        if (params.get("algo") or not params.get("ordered", True)
                or not bridge.ffi_available()
                or (target == "tpucomm_allreduce"
                    and _ici_leg_blocks_ffi())):
            # unordered (explicit-token) mode keeps the callback route
            # (the FFI call's wire format carries the compiler token),
            # and so does a forced per-call algorithm (the quantized
            # allreduce path) and an allreduce the ICI data-plane leg
            # could claim (the leg hooks the bridge funnel the FFI
            # call would bypass)
            return p._callback_lowering(ctx, *args, **params)
        params.pop("ordered", None)
        params.pop("algo", None)
        return _emit_ffi_call(ctx, target, args, _ffi_attrs(**params),
                              alias_in_out=alias_in_out)

    mlir.register_lowering(p, lowering, platform="cpu")


# ---------------- token-operand variants (explicit-token mode) ----------
#
# In unordered mode, ordering must be a REAL data edge through the op:
# XLA folds ``optimization_barrier`` value-token chains around opaque
# custom calls (observed: a scanned send/recv pair compiled with the
# recv's operand reduced to its zeros initializer — the send dropped out
# of the dependency cone and the scheduler ran recv first).  These
# variants are the reference's L1 wire format (tokens as real
# custom-call operands/results, allreduce.py:101-104 there): each takes
# ``(*data, token)`` and returns ``(out, token')``, with the token
# passed through the host callback itself, so no XLA pass can separate
# the chain from the call.  allreduce carries JVP + transpose (SUM only,
# flag-flip identity) and sendrecv JVP + source/dest-swap transpose —
# the reference's L1 AD contract (allreduce.py:188-218, sendrecv.py:
# 355-409 there) — so the composition shape (mesh collectives + world
# ops in one jitted step) can train, not just infer (VERDICT r4 #2).

_TOKEN_AVAL = core.ShapedArray((), np.dtype(np.uint32))
_token_variants = {}


def _token_ffi_attrs(name, params):
    """FFI attrs for a token-variant bind, or None when this bind cannot
    take the native wire (Status fill is a Python-side effect; split
    send/recv tags and custom reduction ops have no native encoding)."""
    params = dict(params)
    if params.pop("status", None) is not None:
        return None
    if params.pop("algo", None) is not None:
        return None  # forced (quantized) algorithm: callback route only
    if name == "allreduce" and _ici_leg_blocks_ffi():
        return None  # the ICI leg hooks the bridge funnel, not the wire
    op = params.get("op")
    if op is not None and op.name not in _OP_CODE:
        return None  # custom ReduceOp: the fold runs in Python
    if name == "sendrecv":
        if params["sendtag"] != params["recvtag"]:
            return None
        params["tag"] = params.pop("sendtag")
        params.pop("recvtag")
    return _ffi_attrs(**params)


def _single_partition(ctx) -> bool:
    """True when this lowering targets ONE device, where the SPMD
    partitioner (which strips sharding annotations from custom-call
    targets it doesn't special-case — ours included, measured) never
    runs.  Multi-device (composition) lowerings keep the host-callback
    wire, whose targets the Shardy bridge does preserve shardings for.
    """
    platforms = tuple(getattr(ctx.module_context, "platforms", ()) or ())
    if not platforms or any(p != "cpu" for p in platforms):
        return False  # FFI targets are registered for cpu only
    ac = ctx.module_context.axis_context
    n = getattr(ac, "num_devices", None)
    if n is not None:
        return n == 1
    mesh = getattr(ac, "mesh", None)
    if mesh is not None:
        return getattr(mesh, "size", 2) == 1
    # unknown axis context (e.g. pmap replicas): the callback route's
    # MAXIMAL pinning is the only safe once-per-process guarantee
    return False


def _emit_token_ffi(ctx, target, args, attrs, n_data, alias_data=False):
    """Native custom call carrying the u32 ordering token as a REAL
    operand/result (the reference L1 wire format) — the explicit-token
    mode analog of _emit_ffi_call, replacing the per-op Python callback
    (~150 us) with the ~1 us native path.  The token operand aliases the
    token result: the chain costs no copies."""
    result_types = [mlir.aval_to_ir_type(a) for a in ctx.avals_out]
    aliases = {n_data: 1}  # token operand -> token result: chain is free
    if alias_data:
        # in-place-safe handlers (sendbuf == recvbuf tolerated): alias
        # the payload too — the value path measured ~9 ms/op at 16 MB
        # without it (_emit_ffi_call)
        aliases[0] = 0
    call = mlir.custom_call(
        target,
        result_types=result_types,
        operands=list(args),
        backend_config=attrs,
        has_side_effect=True,
        api_version=4,
        operand_output_aliases=aliases,
    )
    return list(call.results)


def _make_token_variant(name, out_aval_fn, host_fn, n_data=1,
                        identity_param=None):
    """``identity_param`` names a bool param that short-circuits the op
    to a pure ``(x, token)`` passthrough — no effect, no callback (the
    allreduce transposed-adjoint pass, reference allreduce.py:87-89)."""
    p = core.Primitive(f"mpi4jax_tpu_{name}_t")
    p.multiple_results = True

    def _is_identity(params):
        return identity_param is not None and params.get(identity_param)

    def _host_params(params):
        if identity_param is None:
            return params
        params = dict(params)
        params.pop(identity_param, None)
        return params

    def impl(*args, **params):
        if _is_identity(params):
            return args[0], args[n_data]
        analyzed = _analysis_intercept(
            p.name, args[:n_data], _host_params(params))
        if analyzed is not None:
            return analyzed, args[n_data]
        return _jax_dispatch.apply_primitive(p, *args, **params)

    p.def_impl(impl)

    def abstract_eval(*avals, **params):
        out = out_aval_fn(*avals[:n_data], **params)
        if _is_identity(params):
            return (out, _TOKEN_AVAL), set()
        return (out, _TOKEN_AVAL), {unordered_comm_effect}

    p.def_effectful_abstract_eval(abstract_eval)

    def lowering(ctx, *args, **params):
        if _is_identity(params):
            return list(args)
        from ..runtime import bridge

        host_params = _host_params(params)
        attrs = (_token_ffi_attrs(name, host_params)
                 if bridge.ffi_available() and _single_partition(ctx)
                 else None)
        if attrs is not None:
            return _emit_token_ffi(
                ctx, f"tpucomm_{name}_t", args, attrs, n_data,
                alias_data=name in ("allreduce", "reduce", "scan",
                                    "bcast", "recv"))
        data_avals = ctx.avals_in[:n_data]
        out_aval = ctx.avals_out[0]

        def _callback(*flat):
            data, tok = flat[:n_data], flat[n_data]
            result = host_fn(
                *[_np(a, av) for a, av in zip(data, data_avals)],
                **host_params
            )
            return (_contig(np.asarray(result, dtype=out_aval.dtype)),
                    np.asarray(tok, np.uint32))

        return _emit_unordered_callback(ctx, _callback, args)

    mlir.register_lowering(p, lowering)
    _token_variants[name] = p
    return p


def _bind_token_variant(name, x, token, **params):
    """(result, token') through the token-operand primitive.

    The wire token is the per-trace side chain's head when one exists
    (falling back to the caller's token): every world op in a trace —
    user-chained, tangent, or transposed — then sits on ONE token chain,
    so AD-introduced ops and later user ops can never be mutually
    unordered (the side chain only ever ADDS ordering edges: its head is
    always downstream of the user's chain).  The chain guard still sees
    the caller's ORIGINAL token for footgun detection."""
    p = _token_variants[name]
    wire_tok = _ad_chain_token(token)
    tok = jnp.asarray(wire_tok, jnp.uint32)
    args = (tok,) if x is None else (jnp.asarray(x), tok)
    out, tok2 = p.bind(*args, **params)
    _chain_guard.note_op(params.get("comm"), token, tok2)
    _ad_chain_set(tok2)
    return out, tok2


def token_variant_fn(name, **params):
    """A ``token_fn`` for :func:`.._dispatch.maybe_tokenized`: routes the
    op through its token-operand variant in explicit-token mode.
    Validation happens in the ops-layer entry before dispatch (both
    routes share it)."""

    def fn(x, token):
        return _bind_token_variant(name, x, token, **params)

    fn.comm = params.get("comm")  # for the unthreaded-op chain guard
    return fn


def custom_fold_token_fn(op, comm, root=None, prefix=False):
    """Token-chained composite for user-defined reduction operators:
    the wire carries no user code, so the data moves via the token-
    operand allgather/gather and the fold runs locally — the same
    composite as the value path, but with the token riding the
    communication op so explicit-token mode keeps its ordering."""

    def fn(x, token):
        x = jnp.asarray(x)
        if root is not None:  # noqa: E306
            rows, tok = _bind_token_variant("gather", x, token, comm=comm,
                                            root=root)
            if comm.rank() == root:
                return op.reduce(rows).astype(x.dtype), tok
            return rows, tok
        rows, tok = _bind_token_variant("allgather", x, token, comm=comm)
        if prefix:
            return op.reduce(rows[: comm.rank() + 1]).astype(x.dtype), tok
        return op.reduce(rows).astype(x.dtype), tok

    fn.comm = comm  # for the unthreaded-op chain guard
    return fn


def _same_aval(x_aval, **params):
    return core.ShapedArray(x_aval.shape, x_aval.dtype)


def _scalar_aval(*avals, **params):
    return core.ShapedArray((), np.dtype(np.int32))


def _elementwise_batching(p):
    def rule(batched_args, batch_dims, **params):
        (x,), (bd,) = batched_args, batch_dims
        return p.bind(x, **params), bd

    batching.primitive_batchers[p] = rule


# ---------------- host-side executors ----------------
#
# Every executor consults the schedule-plan runner (runtime/planrt.py)
# first: with MPI4JAX_TPU_PLAN off (the default) that is one module-
# global boolean; with a verified plan installed, sends/recvs may post
# as non-blocking tickets on the progress engine (deferred completions,
# pre-posted hoisted receives) and every other op is signature-checked
# against the plan before running its historic path — a diverging op
# stream disables the plan loudly and falls back.


_planrt = None


def _plan_runner(comm):
    # module reference cached after the first call: this sits on the
    # per-op dispatch path whose microseconds PR 5 fought for, and with
    # plans off the whole detour is one cached-attribute + one cached-
    # env check inside planrt.get
    global _planrt
    if _planrt is None:
        from ..runtime import planrt as _p

        _planrt = _p
    return _planrt.get(comm)


def _plan_sync(comm, kind, execute, **sig):
    """Run a non-accelerated op under the plan runner's cursor (or
    directly when no plan serves this comm)."""
    rt = _plan_runner(comm)
    if rt is None:
        return execute()
    return rt.run_sync(kind, execute, **sig)


def _coll_algo_detail(comm, opname, nbytes):
    """Algorithm name for a trace line; never let the observability
    probe take down the op itself."""
    try:
        return comm.coll_algo(opname, nbytes)
    except Exception:
        return "?"


def _host_allreduce(x, *, comm, op, algo=None):
    from ..runtime import bridge

    if algo is not None:
        from .. import tune as _tune

        algo_code = _tune.ALGO_CODES[algo]
        detail = f"op {op.name} algo {algo} (forced)"
    else:
        algo_code = None
        detail = None
    with tracing.CallTrace(
        comm.rank(), "Allreduce",
        (lambda: detail) if detail is not None else
        (lambda: f"op {op.name} algo "
                 f"{_coll_algo_detail(comm, 'allreduce', x.nbytes)}"),
        nbytes=x.nbytes,
    ):
        # the plan signature stays ("allreduce", reduce_op, nbytes):
        # a quantized call IS an allreduce to the verifier and the
        # schedule compiler — only the wire encoding differs
        return _plan_sync(
            comm, "allreduce",
            lambda: bridge.allreduce(comm.handle, x, _OP_CODE[op.name],
                                     algo=algo_code, reuse=True),
            reduce_op=op.name, nbytes=x.nbytes,
        )


def _host_reduce(x, *, comm, op, root):
    from ..runtime import bridge

    with tracing.CallTrace(comm.rank(), "Reduce", f"op {op.name} root {root}",
                           peer=root, nbytes=x.nbytes):
        return _plan_sync(
            comm, "reduce",
            lambda: bridge.reduce(comm.handle, x, _OP_CODE[op.name], root,
                                  reuse=True),
            reduce_op=op.name, root=root, nbytes=x.nbytes,
        )


def _host_scan(x, *, comm, op):
    from ..runtime import bridge

    with tracing.CallTrace(comm.rank(), "Scan", f"op {op.name}",
                           nbytes=x.nbytes):
        return _plan_sync(
            comm, "scan",
            lambda: bridge.scan(comm.handle, x, _OP_CODE[op.name],
                                reuse=True),
            reduce_op=op.name, nbytes=x.nbytes,
        )


def _host_bcast(x, *, comm, root):
    from ..runtime import bridge

    with tracing.CallTrace(comm.rank(), "Bcast", f"root {root}",
                           peer=root, nbytes=x.nbytes):
        return _plan_sync(comm, "bcast",
                          lambda: bridge.bcast(comm.handle, x, root),
                          root=root, nbytes=x.nbytes)


def _host_allgather(x, *, comm):
    from ..runtime import bridge

    with tracing.CallTrace(
        comm.rank(), "Allgather",
        lambda: f"algo {_coll_algo_detail(comm, 'allgather', x.nbytes)}",
        nbytes=x.nbytes,
    ):
        return _plan_sync(
            comm, "allgather",
            lambda: bridge.allgather(comm.handle, x, comm.size(),
                                     reuse=True),
            nbytes=x.nbytes,
        )


def _host_gather(x, *, comm, root):
    from ..runtime import bridge

    with tracing.CallTrace(comm.rank(), "Gather", f"root {root}",
                           peer=root, nbytes=x.nbytes):
        # root gets (size, *x.shape); non-root sends and gets x back
        # (exact reference contract, gather.py:86-96,213-226 there)
        return _plan_sync(
            comm, "gather",
            lambda: bridge.gather(comm.handle, x, comm.size(), root,
                                  comm.rank()),
            root=root, nbytes=x.nbytes,
        )


def _host_scatter(x, *, comm, root):
    from ..runtime import bridge

    with tracing.CallTrace(comm.rank(), "Scatter", f"root {root}",
                           peer=root, nbytes=x.nbytes):
        return _plan_sync(comm, "scatter",
                          lambda: bridge.scatter(comm.handle, x, root),
                          root=root, nbytes=x.nbytes)


def _host_alltoall(x, *, comm, algo=None):
    from ..runtime import bridge

    if algo is not None:
        from .. import tune as _tune

        algo_code = _tune.ALGO_CODES[algo]
        detail = f"algo {algo} (forced)"
    else:
        algo_code = None
        detail = ""
    with tracing.CallTrace(comm.rank(), "Alltoall", detail,
                           nbytes=x.nbytes):
        # the plan signature stays ("alltoall", nbytes): a quantized or
        # hierarchical exchange IS an alltoall to the verifier and the
        # schedule compiler — only the wire encoding/routing differs
        return _plan_sync(comm, "alltoall",
                          lambda: bridge.alltoall(comm.handle, x,
                                                  algo=algo_code),
                          nbytes=x.nbytes)


def _host_shift2(x, *, comm, lo, hi, tag):
    from ..runtime import bridge

    with tracing.CallTrace(comm.rank(), "Shift2", f"lo {lo} hi {hi}",
                           peer=hi, nbytes=x.nbytes, tag=tag):
        return _plan_sync(comm, "shift2",
                          lambda: bridge.shift2(comm.handle, x, lo, hi, tag),
                          lo=lo, hi=hi, tag=tag, nbytes=x.nbytes)


def _host_barrier(*, comm):
    from ..runtime import bridge

    with tracing.CallTrace(comm.rank(), "Barrier", ""):
        _plan_sync(comm, "barrier", lambda: bridge.barrier(comm.handle))
    return np.zeros((), np.int32)


def _host_send(x, *, comm, dest, tag):
    from ..runtime import bridge

    with tracing.CallTrace(comm.rank(), "Send", f"to {dest} tag {tag}",
                           peer=dest, nbytes=x.nbytes, tag=tag):
        rt = _plan_runner(comm)
        if rt is None or not rt.run_send(x, dest, tag):
            bridge.send(comm.handle, x, dest, tag)
    return np.zeros((), np.int32)


def _host_recv(x, *, comm, source, tag, status=None):
    from ..runtime import bridge

    with tracing.CallTrace(comm.rank(), "Recv", f"from {source} tag {tag}",
                           peer=source, nbytes=x.nbytes, tag=tag):
        rt = _plan_runner(comm)
        if status is None:
            if rt is not None:
                out = rt.run_recv(x.shape, x.dtype, source, tag,
                                  reuse=True)
                if out is not None:
                    return out
            # strict path: arrived size must equal the buffer exactly
            return bridge.recv(comm.handle, x.shape, x.dtype, source, tag,
                               reuse=True)
        def _ex():
            return bridge.recv_status(
                comm.handle, x.shape, x.dtype, source, tag
            )
        if rt is not None:
            out, src, tg, cnt = rt.run_sync("recv", _ex, source=source,
                                            tag=tag)
        else:
            out, src, tg, cnt = _ex()
    status.obj._fill(src, tg, cnt)
    return out


def _host_sendrecv(x, *, comm, source, dest, sendtag, recvtag, status=None):
    from ..runtime import bridge

    with tracing.CallTrace(
        comm.rank(), "Sendrecv", f"to {dest} from {source}",
        peer=dest, nbytes=2 * x.nbytes, tag=sendtag,
    ):
        if status is None and sendtag == recvtag:
            return _plan_sync(
                comm, "sendrecv",
                lambda: bridge.sendrecv(
                    comm.handle, x, x.shape, x.dtype, source, dest,
                    sendtag, reuse=True),
                dest=dest, source=source, sendtag=sendtag,
                recvtag=recvtag,
            )
        out, src, tg, cnt = _plan_sync(
            comm, "sendrecv",
            lambda: bridge.sendrecv_status(
                comm.handle, x, x.shape, x.dtype, source, dest, sendtag,
                recvtag),
            dest=dest, source=source, sendtag=sendtag, recvtag=recvtag,
        )
    if status is None:
        # no status to report a short message through: keep the strict
        # exact-size fail-fast contract of the plain path
        if cnt != out.nbytes:
            import sys

            print(
                f"tpucomm_Sendrecv: size mismatch from rank {source}: "
                f"expected {out.nbytes} bytes, got {cnt}",
                file=sys.stderr, flush=True,
            )
            import os

            os._exit(1)
    else:
        status.obj._fill(src, tg, cnt)
    return out


# ---------------- primitives ----------------

# allreduce carries a `transpose` flag (reference allreduce.py:80-89,215-217):
# the transposed pass lowers to identity — cotangents of the replicated
# output pass through per rank — and transposing it again flips the flag
# back, so double-transpose ≡ allreduce.  Built by hand (not the factory)
# because the transposed pass carries no effect and no callback.
allreduce_p = core.Primitive("mpi4jax_tpu_allreduce")


def _host_allreduce_or_identity(x, *, comm, op, transpose=False,
                                algo=None):
    # the transposed pass is a communication-free identity (reference
    # allreduce.py:87-89 there)
    return x if transpose else _host_allreduce(x, comm=comm, op=op,
                                               algo=algo)


_allreduce_eager = _eager_impl(allreduce_p)


def _allreduce_impl(x, *, comm, op, transpose=False, ordered=True,
                    algo=None):
    if transpose:
        return x  # identity: no effect, nothing to compile
    # (_allreduce_eager performs the analysis intercept)
    return _allreduce_eager(x, comm=comm, op=op, transpose=transpose,
                             ordered=ordered, algo=algo)


allreduce_p.def_impl(_allreduce_impl)


def _allreduce_abstract_eval(x_aval, *, comm, op, transpose=False,
                             ordered=True, algo=None):
    if transpose:
        effects = set()
    else:
        effects = {comm_effect if ordered else unordered_comm_effect}
    return core.ShapedArray(x_aval.shape, x_aval.dtype), effects


allreduce_p.def_effectful_abstract_eval(_allreduce_abstract_eval)


def _allreduce_lowering(ctx, x, *, comm, op, transpose=False, ordered=True,
                        algo=None):
    if transpose:
        return [x]  # identity pass, no communication
    out_aval = ctx.avals_out[0]

    def _callback(*flat):
        result = _host_allreduce(
            *[_np(a, av) for a, av in zip(flat, ctx.avals_in)],
            comm=comm, op=op, algo=algo,
        )
        return (_contig(np.asarray(result, dtype=out_aval.dtype)),)

    if not ordered:
        return _emit_unordered_callback(ctx, _callback, [x])
    token = ctx.tokens_in.get(comm_effect)
    results, token, _ = _jax_callback.emit_python_callback(
        ctx, _callback, token, [x], ctx.avals_in, ctx.avals_out,
        has_side_effect=True, returns_token=True,
    )
    ctx.set_tokens_out(mlir.TokenSet({comm_effect: token}))
    return results


mlir.register_lowering(allreduce_p, _allreduce_lowering)
allreduce_p._callback_lowering = _allreduce_lowering
_register_ffi_lowering(
    allreduce_p, "tpucomm_allreduce", identity_param="transpose",
    alias_in_out=True,
)
reduce_p = _make_primitive("reduce", _same_aval, _host_reduce)
scan_p = _make_primitive("scan", _same_aval, _host_scan)
bcast_p = _make_primitive("bcast", _same_aval, _host_bcast)
alltoall_p = _make_primitive("alltoall", _same_aval, _host_alltoall)
sendrecv_p = _make_primitive("sendrecv", _same_aval, _host_sendrecv)
recv_p = _make_primitive("recv", _same_aval, _host_recv)
send_p = _make_primitive("send", _scalar_aval, _host_send)
barrier_p = _make_primitive("barrier", _scalar_aval, _host_barrier)


def _stacked_aval(x_aval, *, comm, **params):
    return core.ShapedArray((comm.size(),) + x_aval.shape, x_aval.dtype)


def _gather_aval(x_aval, *, comm, root, **_):
    # rank-dependent output, possible because each world process traces
    # its own program: root (size, *in), others the input back (exact
    # reference contract, gather.py:86-96,213-226 there)
    if comm.rank() == root:
        return core.ShapedArray((comm.size(),) + x_aval.shape, x_aval.dtype)
    return core.ShapedArray(x_aval.shape, x_aval.dtype)


def _unstacked_aval(x_aval, *, comm, **params):
    return core.ShapedArray(x_aval.shape[1:], x_aval.dtype)


# one-op bidirectional neighbor exchange (MPI_Neighbor_alltoall on a
# 1-D ring segment): the halo-exchange hot path — a single blocking
# point per direction-dim instead of two sequential sendrecvs (each
# blocking wait costs a scheduler quantum when ranks share cores)
shift2_p = _make_primitive("shift2", _same_aval, _host_shift2)
allgather_p = _make_primitive("allgather", _stacked_aval, _host_allgather)
gather_p = _make_primitive("gather", _gather_aval, _host_gather)
scatter_p = _make_primitive("scatter", _unstacked_aval, _host_scatter)

for _p, _target, _alias in (
    (shift2_p, "tpucomm_shift2", False),  # send half reads while recv writes
    (reduce_p, "tpucomm_reduce", True),
    (scan_p, "tpucomm_scan", True),
    (bcast_p, "tpucomm_bcast", True),
    (alltoall_p, "tpucomm_alltoall", False),
    (send_p, "tpucomm_send", False),
    (barrier_p, "tpucomm_barrier", False),
    (allgather_p, "tpucomm_allgather", False),
    (gather_p, "tpucomm_gather", False),
    (scatter_p, "tpucomm_scatter", False),
):
    _register_ffi_lowering(_p, _target, alias_in_out=_alias)


# recv/sendrecv route around the FFI fast path when the call carries a
# Status (the fill is a host-side effect the Python callback performs) or
# split send/recv tags (the strict native sendrecv takes one tag).
def _recv_ffi_lowering(ctx, *args, **params):
    from ..runtime import bridge

    if (params.get("status") is not None
            or not params.get("ordered", True)
            or not bridge.ffi_available()):
        return recv_p._callback_lowering(ctx, *args, **params)
    params.pop("status", None)
    params.pop("ordered", None)
    # the operand is only a shape carrier — its buffer is dead, safe to
    # write the received bytes straight into it
    return _emit_ffi_call(ctx, "tpucomm_recv", args, _ffi_attrs(**params),
                          alias_in_out=True)


def _sendrecv_ffi_lowering(ctx, *args, **params):
    from ..runtime import bridge

    if (
        params.get("status") is not None
        or params["sendtag"] != params["recvtag"]
        or not params.get("ordered", True)
        or not bridge.ffi_available()
    ):
        return sendrecv_p._callback_lowering(ctx, *args, **params)
    params.pop("status", None)
    params.pop("ordered", None)
    tag = params.pop("sendtag")
    params.pop("recvtag")
    return _emit_ffi_call(
        ctx, "tpucomm_sendrecv", args, _ffi_attrs(tag=tag, **params)
    )


mlir.register_lowering(recv_p, _recv_ffi_lowering, platform="cpu")
mlir.register_lowering(sendrecv_p, _sendrecv_ffi_lowering, platform="cpu")

# token-operand variants for every op (explicit-token mode wire format)
_make_token_variant("shift2", _same_aval, _host_shift2)
_make_token_variant("allreduce", _same_aval, _host_allreduce,
                    identity_param="transpose")
_make_token_variant("reduce", _same_aval, _host_reduce)
_make_token_variant("scan", _same_aval, _host_scan)
_make_token_variant("bcast", _same_aval, _host_bcast)
_make_token_variant("alltoall", _same_aval, _host_alltoall)
_make_token_variant("sendrecv", _same_aval, _host_sendrecv)
_make_token_variant("recv", _same_aval, _host_recv)
_make_token_variant("send", _scalar_aval, _host_send)
_make_token_variant("barrier", _scalar_aval, _host_barrier, n_data=0)
_make_token_variant("allgather", _stacked_aval, _host_allgather)
_make_token_variant("gather", _gather_aval, _host_gather)
_make_token_variant("scatter", _unstacked_aval, _host_scatter)


# ---- AD for the token-operand variants (the composition mode) ----
#
# Token-threading conventions mirror the reference L1 exactly
# (allreduce.py:186-217, sendrecv.py:350-409 there): the tangent op
# chains off the PRIMAL's output token but the primal's token is what
# flows downstream (the tangent's is Zeroed, jax#6285); the transpose
# binds through the primal INPUT token.  Every rank traces the same
# doubled schedule, so the extra tangent op cannot skew cross-rank
# collective order.


def _token_or_fresh(token):
    # transpose rules receive primal inputs that can be UndefinedPrimal;
    # any uint32 works as the wire token (its only role is the data
    # edge), so a fresh zero keeps the op bindable
    if ad.is_undefined_primal(token):
        return jnp.zeros((), jnp.uint32)
    return token


# AD-introduced world ops (tangent binds, transposed binds) are not part
# of the USER's token chain, and with fake (uint32) tokens two of them
# with no chain between each other have undefined relative order — the
# exact hazard the chain guard flags for user code.  A per-trace SIDE
# CHAIN fixes it: the first AD-introduced op in a trace anchors to its
# forward op's token (part of the user chain), and every subsequent one
# chains off the previous AD op's output token, giving all
# AD-introduced world ops in one trace a total order that is identical
# on every rank (same transposition order for matching programs).
# Entries are capped and liveness-pruned; an evicted entry only costs
# the next AD op its chain link (it re-anchors to its hint), never
# correctness of values.
_ad_side_chain = {}  # id(trace) -> [weakref(trace), token]


def _ad_current_trace():
    trace = getattr(core.trace_ctx, "trace", None)
    if trace is None or type(trace).__name__ == "EvalTrace":
        return None  # eager: Python order IS execution order
    return trace


def _ad_chain_token(hint):
    trace = _ad_current_trace()
    if trace is None:
        return hint
    ent = _ad_side_chain.get(id(trace))
    # identity check, not liveness: a dict key is id(trace), which a
    # LATER trace can reuse after the first is collected — a stale
    # entry's token must never leak into a different trace
    if ent is not None and ent[0]() is trace:
        return ent[1]
    return hint


def _ad_chain_set(tok):
    import weakref

    trace = _ad_current_trace()
    if trace is None:
        return
    if len(_ad_side_chain) > 64:
        for k in [k for k, v in _ad_side_chain.items() if v[0]() is None]:
            del _ad_side_chain[k]
        while len(_ad_side_chain) > 64:  # all live: evict oldest
            del _ad_side_chain[next(iter(_ad_side_chain))]
    try:
        wr = weakref.ref(trace)
    except TypeError:
        wr = (lambda t: (lambda: t))(trace)
    _ad_side_chain[id(trace)] = [wr, tok]


def _allreduce_t_jvp(primals, tangents, *, comm, op, transpose=False,
                     algo=None):
    x, token = primals
    x_tan, _token_tan = tangents
    p = _token_variants["allreduce"]
    val, tok = p.bind(x, token, comm=comm, op=op, transpose=transpose,
                      algo=algo)
    if type(x_tan) is ad.Zero:
        # a symbolically-zero tangent differentiates nothing — legal for
        # any op (a non-SUM op behind stop_gradient must not raise)
        jvp = ad.Zero.from_primal_value(val)
    elif op.name != "SUM":
        raise NotImplementedError(
            f"world-tier allreduce is differentiable for SUM only, got "
            f"{op.name}"
        )
    else:
        jvp, tok_jvp = p.bind(x_tan, _ad_chain_token(tok), comm=comm,
                              op=op, transpose=transpose, algo=algo)
        _ad_chain_set(tok_jvp)
    return (val, tok), (jvp, ad.Zero.from_primal_value(tok))


def _allreduce_t_transpose(cts, x, token, *, comm, op, transpose=False,
                           algo=None):
    ct_out, ct_tok = cts
    if op.name != "SUM":
        raise NotImplementedError(
            "the linear transpose of allreduce is only defined for SUM"
        )
    p = _token_variants["allreduce"]
    # always bind (materializing a Zero cotangent): world programs are
    # per-rank, so a rank silently skipping a communicating transposed
    # op could deadlock peers that did not
    ct_out = ad.instantiate_zeros(ct_out)
    res, tok_out = p.bind(ct_out,
                          _ad_chain_token(_token_or_fresh(token)),
                          comm=comm, op=op, transpose=not transpose,
                          algo=algo)
    _ad_chain_set(tok_out)
    return res, ct_tok


_t_allreduce_p = _token_variants["allreduce"]
ad.primitive_jvps[_t_allreduce_p] = _allreduce_t_jvp
ad.primitive_transposes[_t_allreduce_p] = _allreduce_t_transpose


def _sendrecv_t_jvp(primals, tangents, *, comm, source, dest, sendtag,
                    recvtag, status=None):
    # same contract as the ordered-mode rule (a working JVP, superset of
    # the reference's fwd-mode raise): tangents ride the same edge,
    # chained off the primal's token; only the primal fills a Status
    x, token = primals
    x_tan, _token_tan = tangents
    p = _token_variants["sendrecv"]
    val, tok = p.bind(x, token, comm=comm, source=source, dest=dest,
                      sendtag=sendtag, recvtag=recvtag, status=status)
    if type(x_tan) is ad.Zero:
        jvp = ad.Zero.from_primal_value(val)
    else:
        jvp, tok_jvp = p.bind(x_tan, _ad_chain_token(tok), comm=comm,
                              source=source, dest=dest, sendtag=sendtag,
                              recvtag=recvtag, status=None)
        _ad_chain_set(tok_jvp)
    return (val, tok), (jvp, ad.Zero.from_primal_value(tok))


def _sendrecv_t_transpose(cts, x, token, *, comm, source, dest, sendtag,
                          recvtag, status=None):
    # cotangent flows backward along the message edge — swap source/dest
    # with the ordered rule's tag-swap semantics (see
    # _sendrecv_transpose below)
    from ..utils.status import ANY_TAG

    ct_out, ct_tok = cts
    if recvtag == ANY_TAG:
        t_send, t_recv = sendtag, ANY_TAG
    else:
        t_send, t_recv = recvtag, sendtag
    p = _token_variants["sendrecv"]
    ct_out = ad.instantiate_zeros(ct_out)
    res, tok_out = p.bind(ct_out,
                          _ad_chain_token(_token_or_fresh(token)),
                          comm=comm, source=dest, dest=source,
                          sendtag=t_send, recvtag=t_recv, status=None)
    _ad_chain_set(tok_out)
    return res, ct_tok


_t_sendrecv_p = _token_variants["sendrecv"]
ad.primitive_jvps[_t_sendrecv_p] = _sendrecv_t_jvp
ad.primitive_transposes[_t_sendrecv_p] = _sendrecv_t_transpose


# ---------------- AD rules (reference parity) ----------------


def _allreduce_jvp(primals, tangents, *, comm, op, transpose=False,
                   ordered=True, algo=None):
    # reference: JVP defined for SUM only (allreduce.py:192-195 there);
    # a symbolically-zero tangent short-circuits first, so a non-SUM op
    # behind stop_gradient is legal.  A forced (quantized) algorithm
    # rides along: the tangent sync compresses exactly like the primal
    # (the reference DP recipe quantizes gradients, not just values).
    (x,), (t,) = primals, tangents
    primal_out = allreduce_p.bind(x, comm=comm, op=op, transpose=transpose,
                                  ordered=ordered, algo=algo)
    if type(t) is ad.Zero:
        tangent_out = ad.Zero.from_primal_value(primal_out)
    elif op.name != "SUM":
        raise NotImplementedError(
            f"world-tier allreduce is differentiable for SUM only, got "
            f"{op.name}"
        )
    else:
        tangent_out = allreduce_p.bind(
            t, comm=comm, op=op, transpose=transpose, ordered=ordered,
            algo=algo
        )
    return primal_out, tangent_out


def _allreduce_transpose(ct, x, *, comm, op, transpose=False,
                         ordered=True, algo=None):
    # flip the flag: transpose(allreduce) is the identity pass, and
    # transpose of that is allreduce again (reference allreduce.py:206-218)
    return (
        allreduce_p.bind(ct, comm=comm, op=op, transpose=not transpose,
                         ordered=ordered, algo=algo),
    )


ad.primitive_jvps[allreduce_p] = _allreduce_jvp
ad.primitive_transposes[allreduce_p] = _allreduce_transpose


def _sendrecv_jvp(primals, tangents, *, comm, source, dest, sendtag,
                  recvtag, status=None, ordered=True):
    # improvement over the reference (which raises for fwd mode,
    # sendrecv.py:150-155): tangents ride the same message edge.  Only the
    # primal pass fills a Status — one receive, one record.
    (x,), (t,) = primals, tangents
    primal_out = sendrecv_p.bind(x, comm=comm, source=source, dest=dest,
                                 sendtag=sendtag, recvtag=recvtag,
                                 status=status, ordered=ordered)
    if type(t) is ad.Zero:
        tangent_out = ad.Zero.from_primal_value(primal_out)
    else:
        tangent_out = sendrecv_p.bind(
            t, comm=comm, source=source, dest=dest, sendtag=sendtag,
            recvtag=recvtag, status=None, ordered=ordered,
        )
    return primal_out, tangent_out


def _sendrecv_transpose(ct, x, *, comm, source, dest, sendtag, recvtag,
                        status=None, ordered=True):
    # the cotangent flows backward along the message edge: swap source/dest
    # (reference sendrecv.py:390-409).  Tags swap with the direction: the
    # forward edge matched because sendtag(sender) == recvtag(receiver),
    # so the reversed edge must send with the old recvtag and expect the
    # old sendtag.  A wildcard recvtag can't be sent on the wire — keep
    # the own sendtag and accept any, which is consistent on every edge
    # whose forward recv was also a wildcard.
    from ..utils.status import ANY_TAG

    if recvtag == ANY_TAG:
        t_send, t_recv = sendtag, ANY_TAG
    else:
        t_send, t_recv = recvtag, sendtag
    return (
        sendrecv_p.bind(ct, comm=comm, source=dest, dest=source,
                        sendtag=t_send, recvtag=t_recv, status=None,
                        ordered=ordered),
    )


ad.primitive_jvps[sendrecv_p] = _sendrecv_jvp
ad.primitive_transposes[sendrecv_p] = _sendrecv_transpose

# batching where the op is elementwise across the batch axis (reference
# scope: allreduce/barrier/sendrecv, allreduce.py:182-185, barrier.py:120-123,
# sendrecv.py:316-343; bcast/reduce/scan are elementwise too and included)
for _p in (allreduce_p, reduce_p, scan_p, bcast_p, sendrecv_p, recv_p):
    _elementwise_batching(_p)


# shape-changing ops batch too (the reference supports none of these —
# SURVEY.md §2.1 lists batching only for allreduce/barrier/sendrecv).  The
# batch axis rides inside the communicated payload, so one message still
# moves the whole batch:


def _stacking_batching(p):
    # out = (size, *in): the stacking axis is prepended, pushing the batch
    # axis one position right
    def rule(batched_args, batch_dims, **params):
        (x,), (bd,) = batched_args, batch_dims
        return p.bind(x, **params), bd + 1

    batching.primitive_batchers[p] = rule


def _leading_axis_batching(p, out_bd):
    # ops constrained to a (size, ...) leading axis: move the batch axis to
    # position 1 so the per-rank slicing on axis 0 is undisturbed
    def rule(batched_args, batch_dims, **params):
        (x,), (bd,) = batched_args, batch_dims
        x = jnp.moveaxis(x, bd, 1)
        return p.bind(x, **params), out_bd

    batching.primitive_batchers[p] = rule


_stacking_batching(allgather_p)


def _gather_batching(batched_args, batch_dims, *, comm, root, **params):
    # root output gains the stacking axis in front (batch axis shifts
    # right); non-root output is the input unchanged
    (x,), (bd,) = batched_args, batch_dims
    out = gather_p.bind(x, comm=comm, root=root, **params)
    return out, (bd + 1 if comm.rank() == root else bd)


batching.primitive_batchers[gather_p] = _gather_batching
_leading_axis_batching(alltoall_p, out_bd=1)  # out same shape as in
_leading_axis_batching(scatter_p, out_bd=0)   # out drops axis 0


def _send_batching(batched_args, batch_dims, **params):
    # the batch rides inside the one message; the scalar completion value
    # is unbatched
    (x,), (_,) = batched_args, batch_dims
    return send_p.bind(x, **params), batching.not_mapped


batching.primitive_batchers[send_p] = _send_batching


# ---------------- public entry points (called from op modules) -----------


def allreduce(x, op: ReduceOp, comm, algo=None):
    """``algo`` forces a collective algorithm name for this one call —
    the quantized-compression route passes "qring"/"qrd" here; None
    (the default) keeps engine selection.  Not meaningful for custom
    reduce ops (their fold rides allgather)."""
    x = jnp.asarray(x)  # dtype validated at the ops-layer entry
    if op.custom:
        # user-defined op: the wire protocol carries no user code, so
        # compose from allgather + a local jax fold (the analog of the
        # reference handing a user MPI_Op to libmpi, utils.py:133-152)
        rows = allgather_p.bind(x, comm=comm, ordered=_ordered_now())
        return op.reduce(rows).astype(x.dtype)
    return allreduce_p.bind(x, comm=comm, op=op, transpose=False,
                            ordered=_ordered_now(), algo=algo)


def reduce(x, op: ReduceOp, root, comm):
    x = jnp.asarray(x)  # dtype validated at the ops-layer entry
    if op.custom:
        # rank-dependent result (root reduces, others pass through) is
        # fine here: world programs are per-rank (reference
        # reduce.py:71-80 has the same contract)
        rows = gather_p.bind(x, comm=comm, root=root,
                             ordered=_ordered_now())
        if comm.rank() == root:
            return op.reduce(rows).astype(x.dtype)
        return rows
    return reduce_p.bind(x, comm=comm, op=op, root=root,
                         ordered=_ordered_now())


def scan(x, op: ReduceOp, comm):
    x = jnp.asarray(x)  # dtype validated at the ops-layer entry
    if op.custom:
        rows = allgather_p.bind(x, comm=comm, ordered=_ordered_now())
        return op.reduce(rows[: comm.rank() + 1]).astype(x.dtype)
    return scan_p.bind(x, comm=comm, op=op, ordered=_ordered_now())


def bcast(x, root, comm):
    return bcast_p.bind(jnp.asarray(x), comm=comm, root=root,
                        ordered=_ordered_now())


def allgather(x, comm):
    return allgather_p.bind(jnp.asarray(x), comm=comm,
                            ordered=_ordered_now())


def gather(x, root, comm):
    return gather_p.bind(jnp.asarray(x), comm=comm, root=root,
                         ordered=_ordered_now())


def scatter(x, root, comm):
    x = jnp.asarray(x)
    if x.ndim < 1 or x.shape[0] != comm.size():
        raise ValueError(
            f"scatter requires input shape (size, ...) = ({comm.size()}, "
            f"...), got {x.shape} [scatter, rank "
            f"{comm.rank()}/{comm.size()}, dtype {x.dtype}]"
        )
    return scatter_p.bind(x, comm=comm, root=root, ordered=_ordered_now())


def alltoall(x, comm, algo=None):
    """``algo`` forces an alltoall schedule name for this one call —
    the quantized-compression route passes "qalltoall" here; None (the
    default) keeps engine selection."""
    x = jnp.asarray(x)
    if x.ndim < 1 or x.shape[0] != comm.size():
        raise ValueError(
            f"alltoall requires leading axis == communicator size "
            f"({comm.size()}), got shape {x.shape} [alltoall, rank "
            f"{comm.rank()}/{comm.size()}, dtype {x.dtype}]"
        )
    return alltoall_p.bind(x, comm=comm, ordered=_ordered_now(),
                           algo=algo)


def _note_if_unthreaded(comm, token):
    """Direct-path ops (send/recv/sendrecv/neighbor/barrier) bypass
    maybe_tokenized; flag a tokenless bind in explicit mode here."""
    if token is None and not _ordered_now():
        _chain_guard.note_unthreaded(comm)


def neighbor_exchange(to_lo, to_hi, *, lo, hi, comm, tag=60, token=None):
    """(from_lo, from_hi) strips from the 1-D ring neighbors, one op.

    ``lo``/``hi`` are neighbor ranks or None for a wall (the returned
    strip on a wall side is the opposite input, passthrough — callers
    treating walls specially just ignore it).  Self-wrap (both
    neighbors == own rank) is a local rotation.  Deadlock-free for any
    chain/ring when every member calls at the same program position —
    the one-op replacement for the two-shift halo schedule.
    """
    _note_if_unthreaded(comm, token)
    lo_i = -1 if lo is None else int(lo)
    hi_i = -1 if hi is None else int(hi)
    x = jnp.stack([jnp.asarray(to_lo), jnp.asarray(to_hi)])
    if token is not None and not _ordered_now():
        out, tok = _bind_token_variant("shift2", x, token, comm=comm,
                                       lo=lo_i, hi=hi_i, tag=int(tag))
        return (out[0], out[1]), tok
    from . import _dispatch as _disp

    x = _disp.token_in(token, x)
    out = shift2_p.bind(x, comm=comm, lo=lo_i, hi=hi_i, tag=int(tag),
                        ordered=_ordered_now())
    if token is not None:
        return (out[0], out[1]), _disp.token_out(token, out)
    return out[0], out[1]


def barrier(comm, token):
    _note_if_unthreaded(comm, token)
    if token is not None and not _ordered_now():
        _, tok = _bind_token_variant("barrier", None, token, comm=comm)
        return tok
    del token  # ordering comes from the ordered effect
    return barrier_p.bind(comm=comm, ordered=_ordered_now())


def send(x, dest, tag, comm, token):
    _note_if_unthreaded(comm, token)
    from . import _dispatch

    if token is not None and not _ordered_now():
        _, tok = _bind_token_variant("send", x, token, comm=comm,
                                     dest=dest, tag=tag)
        return tok
    x = _dispatch.token_in(token, jnp.asarray(x))  # token ties the input
    done = send_p.bind(jnp.asarray(x), comm=comm, dest=dest, tag=tag,
                       ordered=_ordered_now())
    if token is not None:
        return _dispatch.token_out(token, done)
    return None


def recv(x, source, tag, comm, token, status=None):
    from ..utils.status import HashableStatus, Status

    _note_if_unthreaded(comm, token)

    if isinstance(status, Status):
        status = HashableStatus(status)
    from . import _dispatch as _disp

    if token is not None and not _ordered_now():
        return _bind_token_variant("recv", x, token, comm=comm,
                                   source=source, tag=tag, status=status)
    x = _disp.token_in(token, jnp.asarray(x))  # token ties the dummy input
    result = recv_p.bind(jnp.asarray(x), comm=comm, source=source, tag=tag,
                         status=status, ordered=_ordered_now())
    if token is not None:
        return result, _disp.token_out(token, result)
    return result


def sendrecv_dispatch(x, *, perm, shift, wrap, comm, token,
                      source=None, dest=None, sendtag=0, recvtag=None,
                      status=None):
    """World-tier sendrecv: per-rank explicit source/dest (reference style).

    Accepts explicit ``source``/``dest`` ints, or the mesh-tier
    ``perm``/``shift`` conveniences resolved against this process's rank.
    """
    _note_if_unthreaded(comm, token)
    from ..utils.status import ANY_TAG, HashableStatus, Status

    if recvtag is None:
        recvtag = ANY_TAG if status is not None else sendtag
    if isinstance(status, Status):
        status = HashableStatus(status)
    rank, size = comm.rank(), comm.size()
    if source is None or dest is None:
        if shift is not None:
            dest = (rank + shift) % size if wrap else rank + shift
            source = (rank - shift) % size if wrap else rank - shift
            if not (0 <= dest < size) or not (0 <= source < size):
                raise ValueError(
                    "shift moves past the edge with wrap=False; world-tier "
                    "sendrecv needs a valid partner on every rank — use "
                    "send/recv for edge ranks"
                )
        elif perm is not None:
            src_map = {d: s for s, d in perm}
            dst_map = {s: d for s, d in perm}
            if rank not in src_map or rank not in dst_map:
                raise ValueError(
                    f"perm must cover rank {rank} as both source and dest "
                    "on the world tier; use send/recv for one-sided edges"
                )
            source, dest = src_map[rank], dst_map[rank]
        else:
            raise ValueError("pass source/dest, perm=, or shift=")

    from . import _dispatch as _disp

    if token is not None and not _ordered_now():
        return _bind_token_variant(
            "sendrecv", x, token, comm=comm, source=source, dest=dest,
            sendtag=sendtag, recvtag=recvtag, status=status)
    x = _disp.token_in(token, jnp.asarray(x))
    result = sendrecv_p.bind(
        jnp.asarray(x), comm=comm, source=source, dest=dest,
        sendtag=sendtag, recvtag=recvtag, status=status,
        ordered=_ordered_now(),
    )
    if token is not None:
        return result, _disp.token_out(token, result)
    return result
