"""The ICI data plane of the hierarchical schedules (``hring``/``htree``).

PR 10's topology subsystem routes the intra-island phase of a
hierarchical allreduce over the native shm arena or TCP; on a TPU slice
that leaves the one physically fastest wire — inter-chip ICI — out of
the data plane.  This module promotes ``ops/pallas_collectives.py``
from a mesh-tier novelty into that data plane: when every member of an
island sits on an ici-tier TPU slice (or ``MPI4JAX_TPU_ICI_LEG=force``),
the intra-island leg of an f32 SUM allreduce runs as the fused Pallas
ring — double-buffered async remote DMA, the next hop in flight while
the current chunk folds — and, under quantized wire formats
(``MPI4JAX_TPU_COLL_QUANT=force``), the island sum is packed to the
native int8 wire frame IN KERNEL (``quant_pack_pallas``, bit-compatible
with ``tpucomm_quant_pack``) so the leader leg exchanges pre-quantized
bytes with no host-side pack/unpack.

Dispatch contract (hooked from ``bridge.allreduce_raw`` BEFORE both
native paths, so the descriptor/io_uring fast path is bypassed only
when the leg actually runs):

- f32 SUM only — every other (dtype, op) falls through to the native
  schedules untouched (they are association-free there anyway);
- the resolved algorithm must be ``hring``/``htree`` (explicitly forced
  or the engine's own pick via ``coll_algo_for``) on a multi-island
  comm with cached sub-comms, ``MPI4JAX_TPU_HIER`` not ``deny`` (deny
  must keep degrading to the flat twins) and plan execution off;
- ``auto`` additionally requires EVERY multi-member island to be
  ici-tier: the leg exchanges different frames than the native intra
  paths, so a half-activated world would deadlock — all or nothing;
  ``force`` skips only the tier check (the off-TPU dryrun/tier-1 axis).

Schedule (phases mirror the native ``hier_allreduce``):

1. intra: allgather the members' payloads over the intra sub-comm and
   fold them with the ring association — the Pallas fused-ring kernel
   where this process runs jax on a TPU holding the island's chips,
   else its numpy reference (``simulate_ring_sum``'s arithmetic; the
   kernel is verified against it).  Either way the result is EXACTLY
   ``topo.simulate_hring_sum(..., intra="ring")``'s phase 1;
2. leaders: exact mode forces the flat ``ring``/``rd`` twin of the
   requested hierarchical algorithm over the leaders sub-comm; quant
   mode allgathers the once-packed int8 frames (lossless) and EVERY
   leader dequantize-folds them in island order in f32 — one qdq per
   contribution and a rank-consistent fold order by construction
   (``topo.simulate_ici_q_sum`` is the bit-exact model);
3. intra bcast of the leader's bytes (identical on every rank).

The schedule signature stays plain ``allreduce`` — the verifier, golden
plans and analysis cache keys never see the leg, exactly as PRs 8/10
kept their upgrades below the plan layer.

Observability: the intra leg emits ONE ops-src span with ``tier="ici"``
(name ``Allreduce``, the leg's payload bytes) nested inside the whole-op
record; ``obs.stats()`` attributes it in the tier rows / ``tier_bytes``
while the tuner's ``_usable_trace_event`` keeps ignoring tier-carrying
events — zero double-counting either way.
"""

from __future__ import annotations

import sys
import time
from typing import Optional

import numpy as np

from ..utils import config

#: native wire codes (native/tpucomm.h) — the leg is f32 SUM only
_F32, _SUM = 11, 0

_RING_CACHE: dict = {}
_PACK_CACHE: dict = {}


def ici_leg_backend(members: int = 1) -> str:
    """``"pallas"`` where this process runs jax on a TPU with at least
    ``members`` local chips: the fused kernels run there, compiled.
    ``"numpy"`` elsewhere: the kernels' bit-identical reference (off the
    TPU a Pallas kernel would only run interpreted).  Reading the
    platform never imports jax: a rank that has not imported it holds
    no chip, and importing it here would claim every chip of the host."""
    jax = sys.modules.get("jax")
    if jax is None or jax.default_backend() != "tpu":
        return "numpy"
    return "pallas" if jax.local_device_count() >= members else "numpy"


def _quant_mod():
    from . import _simulate

    return _simulate._quant_refs()


def _ring_sum_numpy(rows: np.ndarray) -> np.ndarray:
    from . import _simulate

    return _simulate.simulate_ring_sum([rows[i] for i in range(len(rows))])


def _ring_sum_pallas(rows: np.ndarray) -> np.ndarray:
    """The fused kernel over ``m`` local devices (row i on device i);
    every device finishes with identical bits, row 0 is returned."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..ops import pallas_collectives as pc

    m, count = rows.shape
    key = (m, count)
    fn = _RING_CACHE.get(key)
    if fn is None:
        mesh = jax.make_mesh((m,), ("ici",),
                             devices=jax.devices()[:m])
        fn = jax.jit(shard_map(
            lambda v: pc.fused_ring_allreduce_sum(v.reshape(-1), "ici")[
                None],
            mesh=mesh, in_specs=P("ici"), out_specs=P("ici")))
        _RING_CACHE[key] = fn
    out = fn(jnp.asarray(rows, jnp.float32))
    return np.asarray(jax.device_get(out)[0])


def _island_ring_sum(rows: np.ndarray) -> np.ndarray:
    """(m, count) f32 member rows -> the island sum every member holds,
    with the EXACT ``simulate_ring_sum`` association either way."""
    if ici_leg_backend(rows.shape[0]) == "pallas":
        return _ring_sum_pallas(rows)
    return _ring_sum_numpy(rows)


def _pack_numpy(island: np.ndarray) -> np.ndarray:
    return _quant_mod().quant_pack_wire_ref(island)


def _pack_pallas(island: np.ndarray) -> np.ndarray:
    import jax

    from ..ops import pallas_collectives as pc

    fn = _PACK_CACHE.get(island.size)
    if fn is None:
        fn = jax.jit(pc.quant_pack_pallas)
        _PACK_CACHE[island.size] = fn
    return np.asarray(jax.device_get(fn(island)))


def _pack(island: np.ndarray) -> np.ndarray:
    """The native int8 wire frame of the island sum (scale bytes then
    codes) — in-kernel on a TPU, else the numpy codec reference;
    bit-identical by (test-enforced) contract."""
    if ici_leg_backend() == "pallas":
        return _pack_pallas(island)
    return _pack_numpy(island)


def _unpack_fold(frames: np.ndarray, order, count: int) -> np.ndarray:
    """Dequantize the leaders' wire frames and fold them in island
    order, f32 throughout (``simulate_ici_q_sum``'s exact arithmetic —
    no final re-quantization)."""
    q = _quant_mod()
    nb = -(-count // q.QUANT_BLOCK)
    acc = None
    for row in order:
        frame = frames[row]
        scales = frame[:4 * nb].copy().view(np.float32)
        codes = frame[4 * nb:]
        d = q.quant_unpack_ref(scales, codes)
        acc = d if acc is None else (acc + d).astype(np.float32)
    return acc


def eligible(t, *, mode: Optional[str] = None) -> bool:
    """Topology-level eligibility (the per-call dtype/op/algo gates live
    in :func:`maybe_allreduce`): multi-island, hier not denied, plan
    execution off, and — under ``auto`` — every multi-member island
    fully ici-tier."""
    mode = mode or config.ici_leg_mode()
    if mode == "off" or t is None or not t.multi:
        return False
    if config.hier_mode() == "deny":
        return False
    if config.plan_spec() is not None:
        return False
    if mode == "force":
        return True
    return all(all(t.tiers[r] == "ici" for r in members)
               for members in t.islands if len(members) > 1)


def ici_leg_status(handle=None) -> dict:
    """Resolved leg status for diagnostics: ``{"mode", "backend",
    "active"}`` — ``active`` is the topology-level eligibility of
    ``handle`` (False without one)."""
    from . import get_topology

    mode = config.ici_leg_mode()
    t = get_topology(handle) if handle is not None else None
    return {
        "mode": mode,
        "backend": ici_leg_backend(),
        "active": bool(t is not None and eligible(t, mode=mode)),
    }


def ici_leg_active(handle) -> bool:
    return ici_leg_status(handle)["active"]


def _record_leg(algo_name: str, t0: float, dur: float, nbytes: int) -> None:
    try:
        from ..obs import _recorder

        if _recorder.enabled():
            _recorder.record_span("Allreduce", t0, dur, nbytes=nbytes,
                                  algo=algo_name, tier="ici")
    except Exception:
        pass


def maybe_allreduce(handle, buf, out, dtype_code: int, op_code: int,
                    algo) -> bool:
    """Run the ICI-leg schedule for this ``allreduce_raw`` call if it is
    eligible; returns True when ``out`` has been filled (the caller
    returns immediately), False to fall through to the native paths.

    Ineligibility is always a QUIET fallthrough — the strict knob
    parser is the loud guard; a world where some ranks run the leg and
    others don't cannot happen because every gate below is a function
    of rank-agreed state (env knobs, the shared topology, the forced
    algo code)."""
    mode = config.ici_leg_mode()
    if mode == "off":
        return False
    if dtype_code != _F32 or op_code != _SUM:
        return False
    from .. import tune
    from ..runtime import bridge

    sub = bridge._topo_subcomms.get(int(handle))
    if sub is None:
        return False
    t = sub["topology"]
    if not eligible(t, mode=mode):
        return False
    code = int(algo or 0)
    if not code:
        try:
            code = int(bridge.coll_algo_for(handle, 0, buf.nbytes))
        except Exception:
            return False
    if code == tune.ALGO_CODES["hring"]:
        algo_name, leader_algo = "hring", tune.ALGO_CODES["ring"]
    elif code == tune.ALGO_CODES["htree"]:
        algo_name, leader_algo = "htree", tune.ALGO_CODES["rd"]
    else:
        return False
    if buf.dtype != np.float32:
        return False

    rank = sub["rank"]
    members = t.islands[sub["island"]]
    m = len(members)
    quant = config.quant_mode() == "force"

    # ---- phase 1: the ICI intra leg -------------------------------
    t0 = time.time()
    if m > 1:
        rows = bridge.allgather(sub["intra"], buf.reshape(-1), m)
        island = _island_ring_sum(np.ascontiguousarray(rows, np.float32))
    else:
        island = np.ascontiguousarray(buf, np.float32).reshape(-1).copy()
    packed = _pack(island) if quant else None
    _record_leg(algo_name, t0, time.time() - t0, buf.nbytes)

    # ---- phase 2: the leader leg ----------------------------------
    L = t.n_islands
    # leader-comm rank r is the r-th smallest leader world rank; the
    # fold below must run in ISLAND order (the simulator's contract)
    leader_order = sorted(range(L), key=lambda i: t.leaders[i])
    res = None
    if sub["leader"] is not None:
        if quant:
            frames = bridge.allgather(sub["leader"], packed, L)
            by_island = {isl: r for r, isl in enumerate(leader_order)}
            res = _unpack_fold(frames, [by_island[i] for i in range(L)],
                               island.size)
        else:
            res = bridge.allreduce(sub["leader"], island, _SUM,
                                   algo=leader_algo)

    # ---- phase 3: intra bcast of the leader's bytes ---------------
    if m > 1:
        root = members.index(t.leaders[sub["island"]])
        res = bridge.bcast(sub["intra"],
                           res if res is not None else island, root)
    np.copyto(out, np.asarray(res).reshape(out.shape).astype(np.float32,
                                                             copy=False))
    return True
