"""Environment-variable configuration registry.

The reference reads env vars ad hoc with a truthiness parser duplicated in two
places (see SURVEY.md §5.6, citing /root/reference/mpi4jax/_src/decorators.py:19-24
and xla_bridge/__init__.py:18-19).  Here every knob is declared once, in one
table, with one parser.

Knobs (all prefixed ``MPI4JAX_TPU_``):

- ``MPI4JAX_TPU_DEBUG``       — per-call debug tracing (rank | call-id | op | dt).
- ``MPI4JAX_TPU_NO_WARN_JAX_VERSION`` — silence the jax version check.
- ``MPI4JAX_TPU_DISABLE_FFI`` — skip the native XLA FFI custom-call fast
                                path on cpu and route world-tier ops through
                                host callbacks instead (debug aid).
- ``MPI4JAX_TPU_DISABLE_SHM`` — force TCP collectives even when every rank
                                of a communicator shares one host (the shm
                                arena is the default there; read natively in
                                native/tpucomm.cc).
- ``MPI4JAX_TPU_SHM_MB``      — shm arena slot size in MB (default 32; read
                                natively).
- ``MPI4JAX_TPU_SHM_RING_KB`` — per-directed-pair p2p ring size in KB
                                (default 1024; read natively).  Messages
                                <= ring/4 travel inline; larger ones
                                leave an ordering stub and ride TCP.
- ``MPI4JAX_TPU_DISABLE_SHM_P2P`` — keep point-to-point on TCP while
                                collectives stay on the shm arena (CI
                                axis; must agree across ranks, read
                                natively).
- ``MPI4JAX_TPU_STRICT_TOKENS`` — explicit-token chain guard: unset =
                                warn on an unthreaded/forked world-op
                                token chain at trace time, 1 = raise,
                                0 = silent (ops/_world_impl.py).
- ``MPI4JAX_TPU_RANK`` / ``MPI4JAX_TPU_SIZE`` / ``MPI4JAX_TPU_COORD`` /
  ``MPI4JAX_TPU_HOSTS`` — world job description (rank, world size,
                                rendezvous host:base-port, per-rank
                                host table); set by the launcher,
                                adopted from mpirun/srun/PMI env when
                                absent (runtime/transport.py).
- ``MPI4JAX_TPU_HOST``        — this rank's reachable address for
                                ``WorldComm.from_mpi`` bootstrap
                                (default 127.0.0.1).
- ``MPI4JAX_TPU_SHM_TIMEOUT_S`` — shm barrier timeout seconds (default 180;
                                read natively).  Capped by
                                ``MPI4JAX_TPU_TIMEOUT_S`` when that is
                                smaller.
- ``MPI4JAX_TPU_TIMEOUT_S``   — progress-based deadline (seconds) on every
                                blocking transport wait: send/recv/
                                ANY_SOURCE polls, collective frames, and
                                (as a cap) shm barrier/ring waits.  The
                                clock resets whenever any byte moves, so
                                slow-but-live bulk transfers survive
                                while a hung peer trips the deadline
                                with a diagnostic naming the op, the
                                peer, the comm, and the bytes moved.
                                Default 0 = no deadline (historic
                                behavior; read natively).
- ``MPI4JAX_TPU_CONNECT_TIMEOUT_S`` — bootstrap deadline (seconds) for
                                dialing lower ranks (exponential
                                backoff, last errno reported; default
                                30, matching the old fixed spin) AND
                                for the accept side waiting on higher
                                ranks (bounded by default since the
                                self-healing PR; 0 = explicitly
                                unbounded on both sides; read
                                natively).
- ``MPI4JAX_TPU_LAUNCH_GRACE_S`` — launcher teardown grace period
                                (seconds, default 5) between escalation
                                steps (SIGINT/SIGTERM -> SIGKILL) when
                                reaping a rank group
                                (runtime/launch.py).
- ``MPI4JAX_TPU_TEST_TIMEOUT_S`` — per-test hard deadline for the
                                world-tier suite (seconds, default 600;
                                0 disables), enforced by
                                tests/world/conftest.py via SIGALRM so
                                a hung multi-process job fails its own
                                test instead of the suite's global
                                wall clock.
- ``MPI4JAX_TPU_FAULT``       — deterministic fault injection in the
                                native transport, for exercising the
                                failure-detection paths:
                                ``rank=R,point=send|recv|connect,
                                after=N,action=hang|exit|close``.  On
                                rank R the (N+1)-th op at `point` hangs
                                forever, exits with code 17 (simulated
                                crash), or shuts down every mesh socket
                                (simulated partition).  The self-healing
                                chaos actions: ``action=reset`` closes
                                the op's link with SO_LINGER(0) (an RST
                                on the wire, the classic transient),
                                ``action=drop`` with ``bytes=N`` kills
                                the link mid-frame after writing N
                                bytes (default 20: inside the header),
                                ``action=delay`` with ``ms=T`` stalls
                                the op T milliseconds (default 100),
                                and ``action=corrupt`` flips one header
                                byte on the next frame (detected by the
                                wire CRC).  All four are one-shot and,
                                with ``MPI4JAX_TPU_RETRY`` unset,
                                degrade to a plain link reset.  A
                                malformed spec aborts the job (read
                                natively).
- ``MPI4JAX_TPU_RETRY``       — self-healing link retry budget: the
                                number of reconnect attempts allowed
                                per link failure before the link is
                                declared DEAD and escalates through the
                                poison -> abort -> elastic ladder.
                                Default 0 = the self-healing layer is
                                fully disarmed and every wire byte is
                                bit-identical to the historic transport
                                (frames gain a seq/epoch/CRC extended
                                header only when armed; read natively).
- ``MPI4JAX_TPU_RETRY_BACKOFF_MS`` — base reconnect backoff in
                                milliseconds (default 100).  Attempt
                                k>1 sleeps base * 2^(k-1) with 25 %
                                deterministic jitter, capped at 5 s;
                                attempt 1 dials immediately (read
                                natively).
- ``MPI4JAX_TPU_HEARTBEAT_S`` — idle-link heartbeat period in seconds
                                (default 0 = off).  The progress thread
                                pings links quiet for a full period and
                                starts recovery on links quiet for
                                three (half-open peer detection without
                                traffic; requires the progress thread;
                                read natively).
- ``MPI4JAX_TPU_WIRE_CRC``    — CRC32C on frame/control headers:
                                ``auto`` (default: on exactly when
                                ``MPI4JAX_TPU_RETRY`` arms the extended
                                header), ``0`` = off, ``1`` = require
                                (loud exit when the retry layer is
                                disarmed, since the unarmed wire has no
                                CRC slot; read natively).  Payload
                                bytes are NOT covered — docs/
                                sharp-bits.md § Self-healing links.
- ``MPI4JAX_TPU_RETRY_REPLAY_SLACK`` — test-only protocol exerciser:
                                replay N extra already-delivered frames
                                on every reconnect so the receiver's
                                seq dedup provably fires (dup counters
                                move, digests stay bit-identical;
                                read natively).
- ``MPI4JAX_TPU_JOBID``       — unique token for /dev/shm segment names
                                (the launcher sets a uuid per job; read
                                natively).
- ``MPI4JAX_TPU_COLL_ALGO``   — force world-tier TCP collective algorithms:
                                a bare name (``ring``/``rd``/``tree``)
                                forces every op, ``allreduce=ring,
                                allgather=tree`` forces per op.  Strongest
                                layer of the selection engine
                                (``mpi4jax_tpu/tune``); must agree across
                                ranks.  The same-host shm arena still wins
                                when active.
- ``MPI4JAX_TPU_COLL_QUANT``  — gate over the quantized (int8) collective
                                wire formats (``qring``/``qrd``, read
                                natively and by the ops layer):
                                ``allow`` (default) lets the decision
                                table / env / API select them and the
                                ``compression="int8"`` allreduce route
                                natively; ``deny`` degrades every
                                quantized pick to its exact twin (ring/
                                rd) and keeps compression on the Python
                                schedule — a numerics kill-switch that
                                never changes which frames match, only
                                their contents; ``force`` upgrades every
                                eligible (real floating dtype, SUM)
                                allreduce — and every eligible-dtype
                                alltoall — to the quantized twin of its
                                selected algorithm.  alltoall rides the
                                same gate: ``qalltoall``/``hqalltoall``
                                (the MoE dispatch wire) degrade under
                                ``deny`` to their exact twins, and
                                codec-ineligible dtypes (ints) always
                                run the exact exchange, consistently on
                                every rank.  Must agree across ranks
                                (frame sizes differ between exact and
                                quantized schedules; a divergent gate
                                fails fast on the size check).
- ``MPI4JAX_TPU_TUNE_CACHE``  — full path of the persistent autotune cache
                                (default ``~/.cache/mpi4jax_tpu/
                                tune_<world_size>.json``), written by
                                ``python -m mpi4jax_tpu.tune`` and loaded
                                at communicator creation.
- ``MPI4JAX_TPU_TUNE_MODEL``  — full path of the persistent cost-model
                                file (default ``~/.cache/mpi4jax_tpu/
                                model_<world_size>[_<topohash>].json``),
                                written by ``python -m mpi4jax_tpu.tune
                                --joint`` and consulted by the schedule
                                compiler when choosing gradient-bucket
                                sizes and concurrency-group caps
                                (docs/usage.md § Joint tuning).  The
                                compiler only probes the disk when this
                                knob is set — golden plans compiled
                                without it stay byte-stable.
- ``MPI4JAX_TPU_ANALYZE_TIMEOUT_S`` — wall-clock deadline (seconds,
                                default 120; 0 = no deadline) for one
                                virtual-world run of the static
                                communication verifier (``python -m
                                mpi4jax_tpu.analyze`` /
                                ``launch --verify``); a program that
                                spins past it fails analysis with an
                                ``analysis_timeout`` finding.
- ``MPI4JAX_TPU_ANALYZE_SYMBOLIC`` — rank-symbolic schedule analysis
                                (analysis/_symbolic.py): ``auto``
                                (default — canonicalizable schedules at
                                large world sizes verify once per rank-
                                equivalence class, with sound fallback
                                to the concrete path) or ``off`` (pin
                                the historic concrete path bit-for-
                                bit).  Strict parse: anything else
                                aborts loudly — a typo'd mode must not
                                silently change which verification
                                path produced a verdict.  Verdicts are
                                byte-identical either way (the
                                differential gate in
                                tests/test_symbolic.py enforces it);
                                the knob exists for pinning and for
                                bisection.
- ``MPI4JAX_TPU_NATIVE_LIB``  — absolute path of the native transport
                                library to load instead of the built
                                ``runtime/_native/libtpucomm.so``
                                (sanitizer builds, cross-build tests;
                                runtime/bridge.py skips the staleness
                                rebuild when set).
- ``MPI4JAX_TPU_TRACE``       — arm the observability recorder and dump
                                this rank's recording to
                                ``<value>.rank<r>.json`` at exit.  The
                                launcher's ``--trace out.json`` sets it
                                and merges the parts into one
                                Perfetto-loadable Chrome trace at
                                ``out.json`` (``mpi4jax_tpu/obs``,
                                docs/observability.md).  Must agree
                                across ranks (like the shm knobs): it
                                arms a collective clock-alignment
                                handshake at communicator creation.
- ``MPI4JAX_TPU_TRACE_BUF_KB`` — event-ring size in KB (default 256;
                                72-byte slots, so 3640 events), for
                                both the native transport ring and the
                                Python span ring.  Overflow keeps the
                                newest events and counts exactly how
                                many were dropped.
- ``MPI4JAX_TPU_PROGRESS_THREAD`` — async progress engine (default on):
                                every transport op is a descriptor on a
                                per-communicator submission queue driven
                                by a dedicated progress thread — small
                                sends return immediately (payload
                                copied, buffered-send semantics), other
                                ops park on a completion futex while an
                                earlier op is still in flight, and run
                                inline when the engine is idle.  ``0``
                                restores the pre-engine inline
                                execution bit-for-bit (read natively).
- ``MPI4JAX_TPU_COALESCE_BYTES`` — sends of at most this many bytes
                                that are adjacent in posted order to
                                the same peer merge into ONE wire frame
                                (split transparently on the receive
                                side, tags and per-channel order
                                preserved).  Default 4096; 0 disables
                                coalescing (read natively; needs the
                                progress engine).
- ``MPI4JAX_TPU_QUEUE_DEPTH`` — submission-queue capacity in ops
                                (default 1024, rounded up to a power of
                                two; posting parks when full — bounded
                                memory, never unbounded buffering; read
                                natively).
- ``MPI4JAX_TPU_URING``       — io_uring submission backend under the
                                progress engine (docs/sharp-bits.md
                                § "The transport floor"; read natively,
                                strict ``auto|0|1`` parser — a typo'd
                                knob aborts loudly): one batched
                                ``io_uring_enter`` moves a whole frame
                                (or descriptor burst), a registered
                                staging pool backs small frames, and
                                sends past the kernel's buffering
                                ceiling go out as MSG_ZEROCOPY with
                                the completion consumed as a CQE.
                                ``auto`` (default) probes the kernel
                                (needs io_uring with EXT_ARG, ~5.11+);
                                ``0`` keeps the poll-driven path
                                bit-for-bit (sanitizer builds, old
                                kernels); ``1`` demands it and warns
                                loudly when the kernel cannot.  Wire
                                bytes, deadlines, poison, and fault
                                injection are identical on both paths;
                                results are bit-for-bit either way.
                                ``config.uring_mode()`` mirrors the
                                parser; the RESOLVED state (on / off /
                                unavailable + reason) is native —
                                ``bridge.uring_status()`` reports it
                                and the diag transport check prints it.
- ``MPI4JAX_TPU_PLAN``        — schedule-plan execution (the analysis
                                layer's verified comm-program rewriting,
                                docs/analysis.md § "From verifier to
                                compiler").  Unset / ``0`` = off (the
                                historic token-order execution,
                                bit-for-bit); a *path* names a plan JSON
                                emitted by ``python -m mpi4jax_tpu.analyze
                                --emit-plan`` (what ``launch --plan``
                                wires up): at communicator creation the
                                rank's verified schedule installs a plan
                                runner — hoisted receives pre-post on the
                                progress engine, large sends defer their
                                completion waits; ``1`` enables runners
                                attached through the API only.  Only
                                *proved* plans execute; a diverging op
                                stream disables the plan loudly and the
                                job continues on the historic path.
                                Implies the host-callback dispatch route
                                (the FFI fast path is skipped while a
                                plan spec is set).  Must agree across
                                ranks.
- ``MPI4JAX_TPU_PLAN_BUCKET_KB`` — gradient-bucket ceiling (KB, default
                                1024) for the schedule compiler's
                                allreduce bucket marks; when set
                                EXPLICITLY it also turns on
                                ``parallel.dp.sync_gradients``
                                bucketing: adjacent small same-op/dtype
                                gradient allreduces fuse into one
                                bucketed allreduce up to this many KB.
                                0 disables bucketing.  Must agree across
                                ranks AND with the analyzer run (it
                                changes the collective schedule; the
                                launcher exports the same environment
                                to both, so they agree by default).
- ``MPI4JAX_TPU_ELASTIC``      — elastic worlds (docs/elasticity.md): a
                                transport failure raises
                                :class:`mpi4jax_tpu.elastic.RankFailure`
                                in Python (after poisoning peers so the
                                group unblocks) instead of hard-exiting
                                the process, and ``elastic.recover()``
                                rebuilds the world over the survivors.
                                Set by ``launch --elastic``; implies the
                                host-callback dispatch route (the FFI
                                fast path bakes comm handles into
                                compiled programs, which cannot survive
                                a rebind).
- ``MPI4JAX_TPU_ELASTIC_DIR``  — coordination directory between the
                                elastic launcher and the ranks: the
                                launcher announces each new world
                                generation as ``gen_<n>.json`` (member
                                map, re-derived base port) and
                                survivors poll it from
                                ``elastic.recover()``.  Set by
                                ``launch --elastic``.
- ``MPI4JAX_TPU_ELASTIC_POLICY`` — what the elastic launcher does about
                                a dead rank: ``shrink`` (default)
                                renumbers the survivors densely into a
                                smaller world; ``respawn`` restarts the
                                dead rank's program in a fresh process
                                and rebuilds at full size.
- ``MPI4JAX_TPU_ELASTIC_GRACE_S`` — how long (seconds, default 60) a
                                surviving rank waits inside
                                ``elastic.recover()`` for the
                                launcher's next generation announcement
                                before giving up (the failure then
                                propagates and the rank exits — the
                                launcher counts it lost).
- ``MPI4JAX_TPU_GENERATION``  — the world generation this process was
                                born into (0 = the original world; the
                                elastic launcher exports it to
                                respawned children).  ``elastic``
                                tracks the live generation from there;
                                obs recordings and traces carry it.
- ``MPI4JAX_TPU_SLOT``        — a rank's original *launcher slot*
                                identity, when it differs from its
                                bootstrap rank: the elastic launcher
                                exports it to respawned children (the
                                generation maps key on slots, which
                                never renumber; ``MPI4JAX_TPU_RANK``
                                carries the dense bootstrap rank).
- ``MPI4JAX_TPU_CKPT_DIR``    — default checkpoint directory for
                                ``utils/checkpoint.py``'s sharded
                                save/restore helpers and the elastic
                                training loop (unset = the caller must
                                pass a directory explicitly).
- ``MPI4JAX_TPU_TOPO``        — topology discovery at communicator
                                creation (``mpi4jax_tpu/topo``,
                                docs/usage.md § Transport tiers and
                                topology): ``auto`` (default) runs the
                                bootstrap fingerprint allgather, derives
                                the intra-island / leader
                                sub-communicators on multi-island
                                worlds, and installs the map natively;
                                ``off`` skips discovery entirely (flat
                                transport, the pre-topology behavior).
                                Must agree across ranks (the handshake
                                is collective).
- ``MPI4JAX_TPU_FAKE_HOSTS``  — virtual host partition for topology
                                testing on one machine
                                (``r0,r1|r2,r3``: groups of world ranks
                                separated by ``|``): ranks in one group
                                share a (virtual) host — they get an
                                intra-island shm arena — while ranks in
                                different groups are treated as
                                host-separated even over loopback (the
                                world arena is withheld).  Read
                                natively at bootstrap AND by the Python
                                discovery; indexes CURRENT world ranks
                                (an elastic rebuild re-applies it to
                                the dense new ranks; out-of-range
                                ranks are ignored).  Malformed specs
                                abort loudly.  Must agree across ranks.
- ``MPI4JAX_TPU_HIER``        — gate over the hierarchical collective
                                schedules (``hring``/``htree`` and the
                                hierarchical bcast/reduce routing; read
                                natively): ``allow`` (default) lets the
                                decision table / env / API select them
                                on a multi-island comm (bcast/reduce
                                route hierarchically at >= 64 KiB);
                                ``deny`` degrades every hierarchical
                                pick to its flat twin (ring/tree) — a
                                routing kill-switch; ``force`` upgrades
                                every eligible allreduce/allgather to a
                                hierarchical twin and routes
                                bcast/reduce hierarchically at any
                                size.  Must agree across ranks (the
                                schedules exchange different frames).
- ``MPI4JAX_TPU_ICI_LEG``     — gate over the ICI data-plane leg of the
                                hierarchical schedules (``hring``/
                                ``htree``): ``auto`` (default) runs the
                                intra-island phase of an f32 SUM
                                allreduce as a Pallas remote-DMA ring
                                (in-kernel int8 codec under ``+q``)
                                when EVERY multi-member island is an
                                ici-tier TPU slice; ``off`` keeps the
                                native shm/TCP intra paths; ``force``
                                activates the leg regardless of tier
                                (off-TPU it runs the kernels' numpy
                                reference — the dryrun and tier-1
                                axis).  Must agree across ranks
                                (the leg exchanges different frames
                                than the native intra paths).
- ``MPI4JAX_TPU_PALLAS_COLLECTIVES`` — route eligible mesh-tier collectives
                                (allreduce-SUM, allgather, ring sendrecv)
                                through the Pallas RDMA ring kernels
                                (``ops/pallas_collectives.py``) instead of
                                XLA's builtin collectives.  Reverse-mode AD
                                only: the routed kernels carry a custom_vjp,
                                so ``jvp``/``jacfwd`` through them raises —
                                leave the flag off for forward-mode code.

- ``MPI4JAX_TPU_SERVE_MAX_BATCH`` — serving plane: initial per-iteration
                                decode batch ceiling (positive int,
                                default 8).  The SLO feedback loop may
                                move the live value below/above this
                                within [1, 4x] — the knob sets the
                                starting point, not a hard bound
                                (serving/_scheduler.py).
- ``MPI4JAX_TPU_SERVE_QUEUE_CAP`` — serving plane: bounded admission
                                queue capacity (positive int, default
                                256).  A submit over the cap is SHED
                                with a loud per-request verdict rather
                                than queued (serving/_scheduler.py).
- ``MPI4JAX_TPU_SERVE_SLO_MS``  — serving plane: per-token decode p99
                                SLO target in milliseconds (positive
                                float; default 0 = SLO loop disabled).
                                A rolling window over the per-phase
                                obs percentiles shrinks max-batch when
                                decode p99 overshoots and regrows it
                                when comfortably under
                                (serving/_scheduler.py).
- ``MPI4JAX_TPU_SERVE_ROLES``  — serving plane: prefill/decode role
                                assignment — ``auto`` (default:
                                disaggregate when the topology has >= 2
                                islands and enough ranks, else
                                colocate), ``colocated`` (every rank
                                both prefills and decodes), ``disagg``
                                (force the split; raises on worlds too
                                small to hold both roles).  Strict:
                                anything else aborts loudly — ranks
                                disagreeing on roles would exchange
                                mismatched frames
                                (serving/_roles.py).

- ``MPI4JAX_TPU_LIVE``         — live drift detection + collective
                                re-tuning (``mpi4jax_tpu.live``):
                                ``off`` (default: no controller thread,
                                no collective-boundary hook — pre-live
                                behavior bit-for-bit) or ``auto`` (a
                                controller follows the native obs
                                stream through the non-destructive
                                cursor, flags drift from the cost
                                model's predictions, and swaps the
                                decision table at an epoch rendezvous
                                all ranks reach together).  Strict:
                                ranks disagreeing on the mode would
                                rendezvous on different collective
                                sequences and deadlock.
- ``MPI4JAX_TPU_LIVE_WINDOW``  — rolling event window the controller
                                keeps over the obs stream (positive
                                int, default 256); drift medians and
                                the refit model use only the freshest
                                window (live/_controller.py).
- ``MPI4JAX_TPU_LIVE_DRIFT_PCT`` — percent deviation of an observed
                                per-(op, size band, algorithm) median
                                from the model prediction that counts
                                as drift (positive float, default 30)
                                (live/_drift.py).
- ``MPI4JAX_TPU_LIVE_COOLDOWN_OPS`` — minimum world-collective
                                boundaries between table swaps
                                (positive int, default 64); also paces
                                the epoch-rendezvous probe at
                                cooldown/4 boundaries
                                (live/_swap.py).

There is intentionally no token/notoken routing knob (the reference's
``MPI4JAX_PREFER_NOTOKEN``, utils.py:167-169 there): ordered effects ARE
the core here, and reference-style explicit-token signatures live in
``mpi4jax_tpu.compat.token_api`` as a direct import — an env var that
changes the primary API's return types at a distance would be a footgun.
"""

from __future__ import annotations

import os

#: The complete knob registry: every environment variable the framework
#: (Python *and* native layers, launcher, test harness) reads, with a
#: one-line role.  ``tests/test_config_lint.py`` greps the source tree and
#: fails when a knob is read anywhere without being declared here — the
#: docstring above carries the long-form documentation.
KNOBS = {
    "MPI4JAX_TPU_DEBUG": "per-call debug tracing",
    "MPI4JAX_TPU_NO_WARN_JAX_VERSION": "silence the jax version warning",
    "MPI4JAX_TPU_DISABLE_FFI": "skip the native XLA FFI fast path",
    "MPI4JAX_TPU_DISABLE_SHM": "force TCP collectives on shared hosts",
    "MPI4JAX_TPU_SHM_MB": "shm arena slot size (MB)",
    "MPI4JAX_TPU_SHM_RING_KB": "per-pair shm p2p ring size (KB)",
    "MPI4JAX_TPU_DISABLE_SHM_P2P": "keep p2p on TCP, collectives on shm",
    "MPI4JAX_TPU_STRICT_TOKENS": "chain guard: warn/raise/silent",
    "MPI4JAX_TPU_RANK": "world job: this process's rank",
    "MPI4JAX_TPU_SIZE": "world job: world size",
    "MPI4JAX_TPU_COORD": "world job: rendezvous host:base-port",
    "MPI4JAX_TPU_HOSTS": "world job: per-rank host table",
    "MPI4JAX_TPU_HOST": "this rank's reachable address (from_mpi)",
    "MPI4JAX_TPU_SHM_TIMEOUT_S": "shm barrier timeout (seconds)",
    "MPI4JAX_TPU_TIMEOUT_S": "progress-based transport deadline (seconds)",
    "MPI4JAX_TPU_CONNECT_TIMEOUT_S": "bootstrap dial/accept deadline",
    "MPI4JAX_TPU_LAUNCH_GRACE_S": "launcher teardown grace (seconds)",
    "MPI4JAX_TPU_TEST_TIMEOUT_S": "world-test per-test hard deadline",
    "MPI4JAX_TPU_FAULT": "deterministic native fault injection",
    "MPI4JAX_TPU_RETRY": "self-healing link retry budget (0 = disarmed)",
    "MPI4JAX_TPU_RETRY_BACKOFF_MS": "reconnect backoff base (milliseconds)",
    "MPI4JAX_TPU_HEARTBEAT_S": "idle-link heartbeat period (seconds)",
    "MPI4JAX_TPU_WIRE_CRC": "header CRC32C: auto/0/1",
    "MPI4JAX_TPU_RETRY_REPLAY_SLACK": "test-only extra replay frames",
    "MPI4JAX_TPU_JOBID": "unique token for /dev/shm segment names",
    "MPI4JAX_TPU_COLL_ALGO": "force world-tier collective algorithms",
    "MPI4JAX_TPU_COLL_QUANT": "quantized wire formats: allow/deny/force",
    "MPI4JAX_TPU_TUNE_CACHE": "persistent autotune cache path",
    "MPI4JAX_TPU_TUNE_MODEL": "persistent collective cost-model path",
    "MPI4JAX_TPU_TRACE": "record per-op events; dump/merge trace here",
    "MPI4JAX_TPU_TRACE_BUF_KB": "observability event-ring size (KB)",
    "MPI4JAX_TPU_PROGRESS_THREAD": "async progress engine on/off",
    "MPI4JAX_TPU_COALESCE_BYTES": "small-send coalescing threshold",
    "MPI4JAX_TPU_PLAN": "schedule-plan execution (off / plan file / api)",
    "MPI4JAX_TPU_PLAN_BUCKET_KB": "gradient allreduce bucket ceiling (KB)",
    "MPI4JAX_TPU_QUEUE_DEPTH": "progress-engine submission-queue depth",
    "MPI4JAX_TPU_URING": "io_uring submission backend: auto/0/1",
    "MPI4JAX_TPU_PALLAS_COLLECTIVES": "route mesh collectives via Pallas",
    "MPI4JAX_TPU_TOPO": "topology discovery at comm creation: auto/off",
    "MPI4JAX_TPU_FAKE_HOSTS": "virtual host partition for topology tests",
    "MPI4JAX_TPU_HIER": "hierarchical schedules: allow/deny/force",
    "MPI4JAX_TPU_ICI_LEG": "Pallas ICI intra-island leg: auto/off/force",
    "MPI4JAX_TPU_ELASTIC": "elastic worlds: RankFailure + recovery",
    "MPI4JAX_TPU_ELASTIC_DIR": "launcher<->rank generation announcements",
    "MPI4JAX_TPU_ELASTIC_POLICY": "dead-rank policy: shrink / respawn",
    "MPI4JAX_TPU_ELASTIC_GRACE_S": "recover() wait for the next generation",
    "MPI4JAX_TPU_GENERATION": "world generation this process was born into",
    "MPI4JAX_TPU_SLOT": "launcher-slot identity of a respawned rank",
    "MPI4JAX_TPU_CKPT_DIR": "default sharded-checkpoint directory",
    "MPI4JAX_TPU_ANALYZE_TIMEOUT_S": "static verifier wall deadline",
    "MPI4JAX_TPU_ANALYZE_SYMBOLIC": "rank-symbolic analysis: auto/off",
    "MPI4JAX_TPU_NATIVE_LIB": "override path of the native transport .so",
    "MPI4JAX_TPU_SERVE_MAX_BATCH": "serving: initial decode batch ceiling",
    "MPI4JAX_TPU_SERVE_QUEUE_CAP": "serving: bounded admission queue size",
    "MPI4JAX_TPU_SERVE_SLO_MS": "serving: decode p99 SLO target (ms)",
    "MPI4JAX_TPU_SERVE_ROLES": "serving: auto / colocated / disagg",
    "MPI4JAX_TPU_LIVE": "live drift detection + re-tuning: off/auto",
    "MPI4JAX_TPU_LIVE_WINDOW": "live controller rolling window (events)",
    "MPI4JAX_TPU_LIVE_DRIFT_PCT": "drift threshold vs model (percent)",
    "MPI4JAX_TPU_LIVE_COOLDOWN_OPS": "min collective boundaries between swaps",
}

_TRUTHY = frozenset(("1", "true", "on", "yes", "y"))
_FALSY = frozenset(("0", "false", "off", "no", "n", ""))


def parse_bool(value: str, *, name: str = "<flag>") -> bool:
    v = value.strip().lower()
    if v in _TRUTHY:
        return True
    if v in _FALSY:
        return False
    raise ValueError(f"cannot parse boolean env var {name}={value!r}")


def flag(name: str, default: bool = False) -> bool:
    """Read a boolean env var (see module docstring for the known set)."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    return parse_bool(raw, name=name)


def setting(name: str, default: str) -> str:
    return os.environ.get(name, default)


def quant_mode() -> str:
    """``MPI4JAX_TPU_COLL_QUANT`` as "allow" | "deny" | "force" — the
    ONE Python-side reader of the quantized-wire gate, matching the
    native parser byte-for-byte (whitespace-trimmed, loud on anything
    else: a typo'd gate must not silently change numerics — the native
    layer exits on it, so the Python layer must never quietly read the
    same value as "allow")."""
    raw = os.environ.get("MPI4JAX_TPU_COLL_QUANT")
    if raw is None:
        return "allow"
    v = raw.strip()
    if not v:
        return "allow"
    if v in ("allow", "deny", "force"):
        return v
    raise ValueError(
        f"cannot parse MPI4JAX_TPU_COLL_QUANT={raw!r} "
        "(expected allow, deny, or force)")


def topo_mode() -> str:
    """``MPI4JAX_TPU_TOPO`` as "auto" | "off" (strict like quant_mode:
    a typo'd mode must not silently skip — or run — the collective
    discovery handshake on a subset of ranks)."""
    raw = os.environ.get("MPI4JAX_TPU_TOPO")
    if raw is None or not raw.strip():
        return "auto"
    v = raw.strip()
    if v in ("auto", "off"):
        return v
    raise ValueError(
        f"cannot parse MPI4JAX_TPU_TOPO={raw!r} (expected auto or off)")


def hier_mode() -> str:
    """``MPI4JAX_TPU_HIER`` as "allow" | "deny" | "force" — the Python
    mirror of the native gate over the hierarchical schedules, matching
    its parser byte-for-byte (the native layer exits loudly on anything
    else, so this must never quietly read the same value as allow)."""
    raw = os.environ.get("MPI4JAX_TPU_HIER")
    if raw is None:
        return "allow"
    v = raw.strip()
    if not v:
        return "allow"
    if v in ("allow", "deny", "force"):
        return v
    raise ValueError(
        f"cannot parse MPI4JAX_TPU_HIER={raw!r} "
        "(expected allow, deny, or force)")


def ici_leg_mode() -> str:
    """``MPI4JAX_TPU_ICI_LEG`` as "auto" | "off" | "force" — gate over
    the Pallas ICI data-plane leg of the hierarchical schedules (see
    ``topo/_ici_leg.py``).  Strict: a typo aborts loudly rather than
    silently riding the native shm/TCP intra paths."""
    raw = os.environ.get("MPI4JAX_TPU_ICI_LEG")
    if raw is None:
        return "auto"
    v = raw.strip()
    if not v:
        return "auto"
    if v in ("auto", "off", "force"):
        return v
    raise ValueError(
        f"cannot parse MPI4JAX_TPU_ICI_LEG={raw!r} "
        "(expected auto, off, or force)")


def knob_env() -> dict:
    """The RESOLVED tuning-relevant knob environment, for stamping into
    benchmark records and tuner-cache payloads: every committed BENCH
    artifact / derived cache names the gates it was measured under, so
    it is reproducible without reading the shell history.

    Values are the resolved modes (the same resolution the native layer
    applies), not the raw strings — ``{"MPI4JAX_TPU_COLL_QUANT":
    "allow", ...}``.  ``MPI4JAX_TPU_PLAN`` reports ``"0"`` when plan
    execution is off and the spec (a path or ``"1"``) otherwise;
    ``MPI4JAX_TPU_COLL_ALGO`` reports the raw force string or ``""``.
    """
    return {
        "MPI4JAX_TPU_COLL_ALGO":
            os.environ.get("MPI4JAX_TPU_COLL_ALGO", "").strip(),
        "MPI4JAX_TPU_COLL_QUANT": quant_mode(),
        "MPI4JAX_TPU_HIER": hier_mode(),
        "MPI4JAX_TPU_ICI_LEG": ici_leg_mode(),
        "MPI4JAX_TPU_URING": uring_mode(),
        "MPI4JAX_TPU_PLAN": plan_spec() or "0",
    }


def tune_model_path():
    """MPI4JAX_TPU_TUNE_MODEL: an explicit cost-model file path, or
    None (the schedule compiler then never probes the disk for one)."""
    raw = os.environ.get("MPI4JAX_TPU_TUNE_MODEL")
    return raw if raw and raw.strip() else None


def fake_hosts_spec():
    """The raw MPI4JAX_TPU_FAKE_HOSTS spec, or None (parsed by
    ``topo.parse_fake_hosts`` and, independently, natively)."""
    raw = os.environ.get("MPI4JAX_TPU_FAKE_HOSTS")
    return raw if raw and raw.strip() else None


def debug_enabled() -> bool:
    return flag("MPI4JAX_TPU_DEBUG")


def ffi_disabled() -> bool:
    return flag("MPI4JAX_TPU_DISABLE_FFI")


def pallas_collectives_enabled() -> bool:
    return flag("MPI4JAX_TPU_PALLAS_COLLECTIVES")


def _float_knob(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    try:
        v = float(raw)
    except ValueError:
        raise ValueError(f"cannot parse {name}={raw!r} as seconds")
    # an explicit non-positive value means OFF, not "use the default" —
    # this mirror must agree with the native parser it reports on
    return v if v > 0 else 0.0


def transport_timeout_s() -> float:
    """Resolved MPI4JAX_TPU_TIMEOUT_S (seconds; 0.0 = no deadline).

    The knob itself is read natively on every wait; this mirror is for
    diagnostics (``runtime.diag``) and documentation tooling.
    """
    return _float_knob("MPI4JAX_TPU_TIMEOUT_S", 0.0)


def connect_timeout_s() -> float:
    """Resolved MPI4JAX_TPU_CONNECT_TIMEOUT_S (seconds; default 30;
    0.0 = explicitly unbounded, matching the native parser)."""
    return _float_knob("MPI4JAX_TPU_CONNECT_TIMEOUT_S", 30.0)


def fault_spec():
    """The raw MPI4JAX_TPU_FAULT spec, or None (parsed/enforced natively)."""
    raw = os.environ.get("MPI4JAX_TPU_FAULT")
    return raw if raw else None


def retry_budget() -> int:
    """Resolved MPI4JAX_TPU_RETRY (reconnect attempts per link failure;
    default 0 = the self-healing layer is disarmed and the wire is
    bit-identical to the historic transport).  The knob itself is read
    natively on every armed path; this mirror serves diag/tooling and
    must agree with the native parser (strict: the native layer exits
    on a malformed value, so this must never quietly read it as 0)."""
    raw = os.environ.get("MPI4JAX_TPU_RETRY")
    if raw is None or not raw.strip():
        return 0
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"cannot parse MPI4JAX_TPU_RETRY={raw!r} as an integer")
    return max(0, v)


def retry_armed() -> bool:
    """True when the self-healing link layer is armed (retry budget > 0)."""
    return retry_budget() > 0


def retry_backoff_ms() -> float:
    """Resolved MPI4JAX_TPU_RETRY_BACKOFF_MS (base reconnect backoff,
    milliseconds; default 100; non-positive restores the default,
    matching the native parser)."""
    raw = os.environ.get("MPI4JAX_TPU_RETRY_BACKOFF_MS")
    if raw is None or not raw.strip():
        return 100.0
    try:
        v = float(raw)
    except ValueError:
        raise ValueError(
            f"cannot parse MPI4JAX_TPU_RETRY_BACKOFF_MS={raw!r} as "
            "milliseconds")
    return v if v > 0 else 100.0


def heartbeat_s() -> float:
    """Resolved MPI4JAX_TPU_HEARTBEAT_S (idle-link heartbeat period,
    seconds; default 0.0 = off, matching the native parser)."""
    return _float_knob("MPI4JAX_TPU_HEARTBEAT_S", 0.0)


def wire_crc_mode() -> str:
    """``MPI4JAX_TPU_WIRE_CRC`` as "auto" | "0" | "1" — the Python
    mirror of the native parser, byte-for-byte (whitespace-trimmed,
    loud on anything else).  "auto" resolves to on exactly when
    :func:`retry_armed`; "1" with the retry layer disarmed makes the
    native layer exit loudly (the unarmed wire has no CRC slot)."""
    raw = os.environ.get("MPI4JAX_TPU_WIRE_CRC")
    if raw is None:
        return "auto"
    v = raw.strip()
    if not v:
        return "auto"
    if v in ("auto", "0", "1"):
        return v
    raise ValueError(
        f"cannot parse MPI4JAX_TPU_WIRE_CRC={raw!r} "
        "(expected auto, 0, or 1)")


def retry_replay_slack() -> int:
    """Resolved MPI4JAX_TPU_RETRY_REPLAY_SLACK (test-only: extra
    already-delivered frames replayed per reconnect; default 0)."""
    raw = os.environ.get("MPI4JAX_TPU_RETRY_REPLAY_SLACK")
    if raw is None or not raw.strip():
        return 0
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"cannot parse MPI4JAX_TPU_RETRY_REPLAY_SLACK={raw!r} as "
            "an integer")
    return max(0, v)


def analyze_symbolic_mode() -> str:
    """``MPI4JAX_TPU_ANALYZE_SYMBOLIC`` as "auto" | "off" (strict like
    topo_mode: a typo'd mode aborts loudly rather than silently
    changing which verification path produced a verdict).  Mirrors
    ``analysis._symbolic.symbolic_mode`` byte-for-byte — the analysis
    package reads the environment directly to stay standalone-loadable,
    and the two parsers must never drift apart."""
    raw = os.environ.get("MPI4JAX_TPU_ANALYZE_SYMBOLIC")
    if raw is None or not raw.strip():
        return "auto"
    v = raw.strip()
    if v in ("auto", "off"):
        return v
    raise ValueError(
        f"cannot parse MPI4JAX_TPU_ANALYZE_SYMBOLIC={raw!r} "
        "(expected auto or off)")


def analyze_timeout_s() -> float:
    """Resolved MPI4JAX_TPU_ANALYZE_TIMEOUT_S (seconds; default 120;
    0 = no deadline, matching MPI4JAX_TPU_TIMEOUT_S's convention)."""
    return _float_knob("MPI4JAX_TPU_ANALYZE_TIMEOUT_S", 120.0)


def native_lib_override():
    """MPI4JAX_TPU_NATIVE_LIB: an explicit transport .so path, or None."""
    raw = os.environ.get("MPI4JAX_TPU_NATIVE_LIB")
    return raw if raw else None


def progress_thread_enabled() -> bool:
    """Resolved MPI4JAX_TPU_PROGRESS_THREAD (default True).

    The knob itself is read natively on every op; this mirror is for
    diagnostics (``runtime.diag``) and documentation tooling."""
    raw = os.environ.get("MPI4JAX_TPU_PROGRESS_THREAD")
    if raw is None or not raw.strip():
        return True
    return parse_bool(raw, name="MPI4JAX_TPU_PROGRESS_THREAD")


def coalesce_bytes() -> int:
    """Resolved MPI4JAX_TPU_COALESCE_BYTES (default 4096; 0 = off),
    mirroring the native parser's clamps for diagnostics."""
    raw = os.environ.get("MPI4JAX_TPU_COALESCE_BYTES")
    if raw is None or not raw.strip():
        return 4096
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"cannot parse MPI4JAX_TPU_COALESCE_BYTES={raw!r} as bytes")
    return max(0, min(v, 64 * 1024))


def uring_mode() -> str:
    """``MPI4JAX_TPU_URING`` as "auto" | "0" | "1" — the Python mirror
    of the native parser, byte-for-byte (whitespace-trimmed, loud on
    anything else: the native layer exits on a typo'd knob, so this
    must never quietly read the same value as "auto").  Whether the
    backend is ACTUALLY active is resolved natively by the kernel
    probe — ``runtime.bridge.uring_status()`` reports on/off/
    unavailable(<reason>)."""
    raw = os.environ.get("MPI4JAX_TPU_URING")
    if raw is None:
        return "auto"
    v = raw.strip()
    if not v:
        return "auto"
    if v in ("auto", "0", "1"):
        return v
    raise ValueError(
        f"cannot parse MPI4JAX_TPU_URING={raw!r} (expected auto, 0, or 1)")


def uring_active() -> bool:
    """True when the loaded native transport resolved the io_uring
    backend ON (knob allows it AND the kernel probe succeeded).  False
    on ``MPI4JAX_TPU_URING=0``, an incapable kernel, or a pre-uring
    native library.  Mirror for diagnostics/tooling — the native layer
    is the single authority."""
    if uring_mode() == "0":
        return False
    from ..runtime import bridge

    status = bridge.uring_status()
    return status is not None and status.startswith("on")


def trace_path():
    """MPI4JAX_TPU_TRACE: the recording dump/merge base path, or None
    (observability recorder off)."""
    raw = os.environ.get("MPI4JAX_TPU_TRACE")
    return raw if raw else None


def plan_spec():
    """MPI4JAX_TPU_PLAN: a plan-file path or enable flag, or None when
    plan execution is off (the resolution itself lives in
    runtime/planrt.py; this mirror serves diag and the FFI gate)."""
    raw = os.environ.get("MPI4JAX_TPU_PLAN", "").strip()
    if not raw or raw.lower() in ("0", "false", "off", "no"):
        return None
    return raw


def elastic_enabled() -> bool:
    """Resolved MPI4JAX_TPU_ELASTIC (default False): transport failures
    raise :class:`mpi4jax_tpu.elastic.RankFailure` instead of
    hard-exiting the process (``runtime/bridge.py`` reads this on its
    abort path; ``launch --elastic`` sets it)."""
    return flag("MPI4JAX_TPU_ELASTIC")


def elastic_dir():
    """MPI4JAX_TPU_ELASTIC_DIR: the launcher<->rank coordination
    directory for generation announcements, or None."""
    raw = os.environ.get("MPI4JAX_TPU_ELASTIC_DIR")
    return raw if raw else None


def elastic_policy() -> str:
    """MPI4JAX_TPU_ELASTIC_POLICY as "shrink" | "respawn" (strict like
    quant_mode: a typo'd policy must not silently shrink a job whose
    operator asked for respawn)."""
    raw = os.environ.get("MPI4JAX_TPU_ELASTIC_POLICY")
    if raw is None or not raw.strip():
        return "shrink"
    v = raw.strip()
    if v in ("shrink", "respawn"):
        return v
    raise ValueError(
        f"cannot parse MPI4JAX_TPU_ELASTIC_POLICY={raw!r} "
        "(expected shrink or respawn)")


def elastic_grace_s() -> float:
    """Resolved MPI4JAX_TPU_ELASTIC_GRACE_S (seconds, default 60):
    how long ``elastic.recover()`` waits for the launcher's next
    generation announcement."""
    v = _float_knob("MPI4JAX_TPU_ELASTIC_GRACE_S", 60.0)
    return v if v > 0 else 60.0


def generation() -> int:
    """The world generation this process was BORN into (default 0; the
    elastic launcher exports it to respawned children).  The live
    generation after in-process recoveries is tracked by
    ``mpi4jax_tpu.elastic`` on top of this."""
    raw = os.environ.get("MPI4JAX_TPU_GENERATION")
    if raw is None or not raw.strip():
        return 0
    try:
        return int(raw)
    except ValueError:
        raise ValueError(
            f"cannot parse MPI4JAX_TPU_GENERATION={raw!r} as an integer")


def ckpt_dir():
    """MPI4JAX_TPU_CKPT_DIR: the default sharded-checkpoint directory,
    or None (callers must pass one explicitly)."""
    raw = os.environ.get("MPI4JAX_TPU_CKPT_DIR")
    return raw if raw else None


def plan_bucket_bytes() -> int:
    """Resolved MPI4JAX_TPU_PLAN_BUCKET_KB in bytes (default 1 MiB;
    0 disables gradient bucketing)."""
    raw = os.environ.get("MPI4JAX_TPU_PLAN_BUCKET_KB")
    if raw is None or not raw.strip():
        return 1 << 20
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"cannot parse MPI4JAX_TPU_PLAN_BUCKET_KB={raw!r} as KB")
    return max(0, v) * 1024


def _positive_int_knob(name: str, default: int) -> int:
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(f"cannot parse {name}={raw!r} as an integer")
    if v <= 0:
        raise ValueError(f"{name}={raw!r} must be a positive integer")
    return v


def serve_max_batch() -> int:
    """``MPI4JAX_TPU_SERVE_MAX_BATCH``: the serving scheduler's initial
    per-iteration decode batch ceiling (strict positive int, default 8).
    The SLO loop adjusts the live value from this starting point."""
    return _positive_int_knob("MPI4JAX_TPU_SERVE_MAX_BATCH", 8)


def serve_queue_cap() -> int:
    """``MPI4JAX_TPU_SERVE_QUEUE_CAP``: bounded admission-queue capacity
    (strict positive int, default 256).  Submits over the cap are shed
    with a loud verdict, never silently queued."""
    return _positive_int_knob("MPI4JAX_TPU_SERVE_QUEUE_CAP", 256)


def serve_slo_ms() -> float:
    """``MPI4JAX_TPU_SERVE_SLO_MS``: per-token decode p99 target in
    milliseconds for the serving SLO feedback loop.  Strict: a
    non-numeric or negative value aborts loudly (a typo'd SLO silently
    disabling adaptation would defeat the loop); 0 / unset = loop
    disabled."""
    raw = os.environ.get("MPI4JAX_TPU_SERVE_SLO_MS")
    if raw is None or not raw.strip():
        return 0.0
    try:
        v = float(raw)
    except ValueError:
        raise ValueError(
            f"cannot parse MPI4JAX_TPU_SERVE_SLO_MS={raw!r} as ms")
    if v < 0:
        raise ValueError(
            f"MPI4JAX_TPU_SERVE_SLO_MS={raw!r} must be >= 0")
    return v


def serve_roles() -> str:
    """``MPI4JAX_TPU_SERVE_ROLES`` as "auto" | "colocated" | "disagg" —
    the serving plane's prefill/decode role-assignment mode.  Strict
    like the other cross-rank gates: ranks disagreeing on roles would
    exchange mismatched frames, so a typo aborts loudly instead of
    silently colocating."""
    raw = os.environ.get("MPI4JAX_TPU_SERVE_ROLES")
    if raw is None or not raw.strip():
        return "auto"
    v = raw.strip()
    if v in ("auto", "colocated", "disagg"):
        return v
    raise ValueError(
        f"cannot parse MPI4JAX_TPU_SERVE_ROLES={raw!r} "
        "(expected auto, colocated, or disagg)")


def live_mode() -> str:
    """``MPI4JAX_TPU_LIVE`` as "off" | "auto" — the live re-tuning
    subsystem (``mpi4jax_tpu.live``): a controller thread that watches
    the native obs stream for drift from the cost model's predictions
    and swaps the collective decision table at an agreed boundary.
    Strict like the other cross-rank gates: ranks disagreeing on the
    mode would rendezvous on different collective sequences and
    deadlock, so a typo aborts loudly.  The "off" default arms nothing
    — no thread, no boundary hook, no obs-ring enable — pinning
    pre-live behavior bit-for-bit."""
    raw = os.environ.get("MPI4JAX_TPU_LIVE")
    if raw is None or not raw.strip():
        return "off"
    v = raw.strip()
    if v in ("off", "auto"):
        return v
    raise ValueError(
        f"cannot parse MPI4JAX_TPU_LIVE={raw!r} (expected off or auto)")


def live_window() -> int:
    """``MPI4JAX_TPU_LIVE_WINDOW``: the live controller's rolling
    window over the native obs stream, in events (strict positive int,
    default 256).  Drift medians and the refit model both come from
    the freshest ``window`` events only — stale timings never pool
    with the current contention regime's."""
    return _positive_int_knob("MPI4JAX_TPU_LIVE_WINDOW", 256)


def live_drift_pct() -> float:
    """``MPI4JAX_TPU_LIVE_DRIFT_PCT``: how far (percent) an observed
    per-(op, size band, algorithm) median may deviate from the cost
    model's prediction before the controller declares drift and
    prepares a candidate table (strict positive float, default 30).
    Strict: a typo'd threshold silently never (or always) firing would
    defeat the loop."""
    raw = os.environ.get("MPI4JAX_TPU_LIVE_DRIFT_PCT")
    if raw is None or not raw.strip():
        return 30.0
    try:
        v = float(raw)
    except ValueError:
        raise ValueError(
            f"cannot parse MPI4JAX_TPU_LIVE_DRIFT_PCT={raw!r} as percent")
    if v <= 0:
        raise ValueError(
            f"MPI4JAX_TPU_LIVE_DRIFT_PCT={raw!r} must be > 0")
    return v


def live_cooldown_ops() -> int:
    """``MPI4JAX_TPU_LIVE_COOLDOWN_OPS``: minimum world-collective
    boundaries between table swaps (strict positive int, default 64).
    Also paces the epoch rendezvous itself — ranks compare epochs every
    ``cooldown / 4`` boundaries (at least every boundary), so a
    proposed table is installed well within one cooldown of drift
    detection while a quiescent run pays a 16-byte bcast at most every
    few boundaries."""
    return _positive_int_knob("MPI4JAX_TPU_LIVE_COOLDOWN_OPS", 64)
