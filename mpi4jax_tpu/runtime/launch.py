"""Process launcher for world-tier (multi-process) jobs.

The reference has no launcher — users run ``mpirun -n N python prog.py``
(README.rst there).  This framework ships its own:

    python -m mpi4jax_tpu.runtime.launch -n 4 prog.py [args...]

Each rank becomes one process with ``MPI4JAX_TPU_RANK``/``SIZE``/``COORD``
set; ``get_default_comm()`` then returns the :class:`WorldComm`.

Failure detection & teardown (the job-reaper role MPI_Abort + the mpirun
supervisor play in the reference):

- **fail-fast**: if any rank exits nonzero, the rest are SIGTERMed (then
  SIGKILLed after a grace period) and the launcher exits with that code,
  printing a one-line post-mortem naming the first-failing rank and its
  last native transport error;
- **--timeout**: a wall-clock watchdog — when the job outlives it, the
  whole rank group is reaped (SIGTERM -> SIGKILL) and the launcher exits
  124, so a wedged job can never hang a scheduler slot forever;
- **SIGTERM** (scheduler preemption) is forwarded to every rank and the
  group is reaped before the launcher exits 143 — no orphan ranks;
- **Ctrl-C** forwards SIGINT, waits a grace period, then escalates to
  SIGTERM/SIGKILL and reaps (exit 130).

The grace period between escalation steps is ``MPI4JAX_TPU_LAUNCH_GRACE_S``
(default 5 seconds).

**Elastic mode** (``--elastic [--elastic-policy shrink|respawn]``,
docs/elasticity.md)
replaces fail-fast with recovery supervision: a dead rank advances the
world *generation* — the launcher announces the survivor map and a
re-derived port block as ``gen_<n>.json`` in a coordination directory
(``MPI4JAX_TPU_ELASTIC_DIR``), survivors rebuild through
``mpi4jax_tpu.elastic.recover()``, and under the ``respawn`` policy the
dead slot's program is restarted in a fresh process that joins the new
bootstrap.  A job that finishes after recoveries exits 0; the
post-mortem then names the recovery outcome (generation reached, slots
lost, resume step) instead of a first-failing rank.
"""

from __future__ import annotations

import argparse
import collections
import os
import signal
import subprocess
import sys
import threading
import time


def _grace_s() -> float:
    try:
        return max(0.1, float(os.environ.get("MPI4JAX_TPU_LAUNCH_GRACE_S",
                                             "5")))
    except ValueError:
        return 5.0


class _Terminated(Exception):
    """Raised by the SIGTERM handler to unwind into the reap path."""


def _pump_stderr(pipe, tail):
    """Forward one rank's stderr verbatim, keeping a tail for the
    post-mortem.  Verbatim matters: peers' transport diagnostics and the
    debug-trace format must reach the launcher's stderr unchanged."""
    try:
        for line in iter(pipe.readline, b""):
            tail.append(line)
            try:
                sys.stderr.buffer.write(line)
                sys.stderr.buffer.flush()
            except Exception:
                pass
    finally:
        try:
            pipe.close()
        except Exception:
            pass


def _self_heal_outcomes(slot_tails):
    """Scan every slot's stderr tail for link-layer self-healing lines
    (native/tpucomm.cc's greppable contract).  Returns
    ``({slot: recovered_count}, [(slot, peer), ...])`` — slots that
    healed a transient link fault IN PLACE (they are not dead and must
    not be reported as deaths), and links the layer declared DEAD after
    exhausting MPI4JAX_TPU_RETRY (naming the failed connection is the
    post-mortem's job; the exit code alone cannot)."""
    import re as _re

    healed = {}
    dead_links = []
    for slot, tail in sorted(slot_tails.items()):
        for line in tail:
            raw = bytes(line)
            if _re.search(rb"self-heal: link to r\d+ recovered", raw):
                healed[slot] = healed.get(slot, 0) + 1
            m = _re.search(rb"self-heal: link to r(\d+) DEAD", raw)
            if m:
                dead_links.append((slot, int(m.group(1))))
    return healed, dead_links


def _last_native_error(tail):
    """The most recent transport diagnostic in a rank's stderr tail."""
    for line in reversed(tail):
        text = line.decode(errors="replace").strip()
        if "tpucomm" in text or "returned error code" in text:
            return text
    for line in reversed(tail):
        text = line.decode(errors="replace").strip()
        if text:
            return text
    return ""


def _terminate_group(procs, grace=None):
    """SIGTERM every live rank, wait up to the grace period, SIGKILL the
    stragglers, and reap everything — no orphans survive this call."""
    grace = _grace_s() if grace is None else grace
    live = [p for p in procs if p.poll() is None]
    for p in live:
        try:
            p.terminate()
        except OSError:
            pass
    deadline = time.time() + grace
    for p in live:
        try:
            p.wait(timeout=max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            try:
                p.kill()
            except OSError:
                pass
            p.wait()


def _preflight_verify(prog: str, np_: int, prog_args=()) -> int:
    """Run the static communication verifier on ``prog`` before spawning
    any rank.  Returns 0 to proceed; 3 (with the findings table on
    stderr) when verification fails; the analyzer's own code on analyzer
    errors.

    Runs as a subprocess on purpose: the launcher itself imports no jax,
    and a verifier crash must not take the launcher down with it.
    """
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env.setdefault("PYTHONPATH", repo)
    # warnings document assumptions and do not block a launch; the "--"
    # keeps the program's own flags out of the analyzer's parser
    res = subprocess.run(
        [sys.executable, "-m", "mpi4jax_tpu.analyze", prog,
         "--np", str(np_), "--errors-only", "--", *prog_args],
        capture_output=True, text=True, env=env,
    )
    if res.returncode == 0:
        if "WARNING" in res.stdout:
            print(f"[launch] --verify: {prog} has warnings at np={np_} "
                  "(launch proceeds):", file=sys.stderr)
            sys.stderr.write(res.stdout)
        else:
            print(f"[launch] --verify: {prog} clean at np={np_}",
                  file=sys.stderr)
        sys.stderr.flush()
        return 0
    if res.returncode == 3:
        print(f"[launch] --verify FAILED for {prog} at np={np_} — "
              "no rank was spawned:", file=sys.stderr)
        sys.stderr.write(res.stdout)
        sys.stderr.write(res.stderr)
        sys.stderr.flush()
        return 3
    print(f"[launch] --verify could not run the analyzer "
          f"(exit {res.returncode}):", file=sys.stderr)
    sys.stderr.write(res.stderr[-2000:])
    sys.stderr.flush()
    return res.returncode or 2


def _emit_plan_at(prog: str, np_: int, prog_args, plan_path: str):
    """One analyzer --emit-plan run at a specific world size; returns
    the CompletedProcess (the caller interprets exit codes)."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env.setdefault("PYTHONPATH", repo)
    return subprocess.run(
        [sys.executable, "-m", "mpi4jax_tpu.analyze", prog,
         "--np", str(np_), "--errors-only", "--emit-plan", plan_path,
         "--", *prog_args],
        capture_output=True, text=True, env=env,
    )


def _bundle_shrink_ladder(prog: str, np_: int, prog_args,
                          plan_path: str, primary: dict) -> None:
    """Elastic-safe plans: extend the verified primary plan into a
    *bundle* carrying one verified plan per world size a shrinking job
    may pass through (np-1 .. 2).  ``bridge.rebuild`` then re-derives
    and re-proves the surviving size's plan inside recovery instead of
    dropping the overlap.  Sizes whose plan cannot be compiled/proved
    are skipped with a notice — recovery at those sizes runs the
    historic path.  The bundle overwrites ``plan_path`` in place (the
    MPI4JAX_TPU_PLAN export is unchanged); the wire format is
    ``analysis/_plan.py``'s plan-bundle/1."""
    import json as _json
    import tempfile

    plans = {str(np_): primary}
    skipped = []
    for n2 in range(np_ - 1, 1, -1):
        fd, sub_path = tempfile.mkstemp(prefix="m4j_plan_",
                                        suffix=".json")
        os.close(fd)
        try:
            res = _emit_plan_at(prog, n2, prog_args, sub_path)
            if res.returncode not in (0, 3):
                skipped.append((n2, f"analyzer exit {res.returncode}"))
                continue
            with open(sub_path) as f:
                sub = _json.load(f)
            if not (sub.get("proved") and sub.get("rewritten")):
                why = ("not proved" if not sub.get("proved")
                       else "unrewritten")
                skipped.append((n2, why))
                continue
            plans[str(n2)] = sub
        except Exception as e:
            skipped.append((n2, str(e)))
        finally:
            try:
                os.unlink(sub_path)
            except OSError:
                pass
    try:
        # one source of truth for the wire format; the literals below
        # only serve the run-as-a-plain-file mode (no package context)
        from ..analysis._plan import BUNDLE_FORMAT, BUNDLE_VERSION
    except ImportError:
        BUNDLE_FORMAT, BUNDLE_VERSION = "plan-bundle", 1
    bundle = {
        "format": BUNDLE_FORMAT,
        "version": BUNDLE_VERSION,
        "analyzer_version": primary.get("analyzer_version", ""),
        "plans": plans,
    }
    tmp = f"{plan_path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        _json.dump(bundle, f, indent=1, sort_keys=True)
    os.replace(tmp, plan_path)
    covered = sorted(int(n) for n in plans)
    print(f"[launch] --plan --elastic: plan bundle covers "
          f"np={covered} — a shrink inside this range re-proves and "
          "keeps its plan", file=sys.stderr, flush=True)
    for n2, why in skipped:
        print(f"[launch] --plan --elastic: no plan for np={n2} ({why}); "
              "a shrink to that size runs the historic path",
              file=sys.stderr, flush=True)


def _preflight_plan(prog: str, np_: int, prog_args=(),
                    enforce_verify: bool = False, elastic: bool = False):
    """Compile + verify ``prog``'s execution plan before spawning any
    rank (the schedule compiler, docs/analysis.md § "From verifier to
    compiler").  Returns ``(rc, plan_path)``: nonzero ``rc`` aborts the
    launch (only possible with ``enforce_verify``, which folds the
    ``--verify`` gate into this single analyzer run instead of tracing
    the program twice); an empty ``plan_path`` means no plan should be
    installed — compile failure, an unproved plan, or an unrewritten
    one (exporting a trivial plan would cost the FFI fast path and
    per-op bookkeeping for zero overlap benefit) — and the job runs the
    historic token-order path, which is always correct.

    ``elastic`` additionally compiles the shrink ladder into a plan
    BUNDLE (see :func:`_bundle_shrink_ladder`) so recovery keeps the
    overlap."""
    import tempfile

    fd, plan_path = tempfile.mkstemp(prefix="m4j_plan_", suffix=".json")
    os.close(fd)
    res = _emit_plan_at(prog, np_, prog_args, plan_path)
    if res.returncode == 3 and enforce_verify:
        print(f"[launch] --verify FAILED for {prog} at np={np_} — "
              "no rank was spawned:", file=sys.stderr)
        sys.stderr.write(res.stdout)
        sys.stderr.write(res.stderr)
        sys.stderr.flush()
        os.unlink(plan_path)
        return 3, ""
    if enforce_verify and res.returncode == 0 and "WARNING" in res.stdout:
        # same surfacing contract as plain --verify: warnings document
        # assumptions and must not get quieter because --plan rode along
        print(f"[launch] --verify: {prog} has warnings at np={np_} "
              "(launch proceeds):", file=sys.stderr)
        sys.stderr.write(res.stdout)
        sys.stderr.flush()
    if res.returncode not in (0, 3):
        if enforce_verify:
            print(f"[launch] --verify could not run the analyzer "
                  f"(exit {res.returncode}):", file=sys.stderr)
            sys.stderr.write(res.stderr[-2000:])
            sys.stderr.flush()
            os.unlink(plan_path)
            return res.returncode or 2, ""
        print(f"[launch] --plan: schedule compiler could not run "
              f"(exit {res.returncode}); running without a plan:",
              file=sys.stderr)
        sys.stderr.write(res.stderr[-2000:])
        sys.stderr.flush()
        os.unlink(plan_path)
        return 0, ""
    try:
        import json as _json

        with open(plan_path) as f:
            plan = _json.load(f)
        proved = bool(plan.get("proved"))
        rewritten = bool(plan.get("rewritten"))
        reasons = plan.get("reasons", [])
    except Exception as e:
        print(f"[launch] --plan: cannot read compiled plan: {e}",
              file=sys.stderr, flush=True)
        os.unlink(plan_path)
        return 0, ""
    if not (proved and rewritten):
        state = "NOT proved equivalent" if not proved else "unrewritten"
        print(f"[launch] --plan: plan for {prog} at np={np_} is "
              f"{state}; running without a plan:"
              + "".join(f"\n    {r}" for r in reasons),
              file=sys.stderr, flush=True)
        os.unlink(plan_path)
        return 0, ""
    print(f"[launch] --plan: verified plan "
          f"{plan.get('cache_key', '?')} for {prog} at np={np_}"
          + "".join(f"\n    note: {r}" for r in reasons),
          file=sys.stderr, flush=True)
    if elastic:
        _bundle_shrink_ladder(prog, np_, prog_args, plan_path, plan)
    return 0, plan_path


def _merge_trace(out_path: str, np_: int) -> None:
    """Merge the per-rank recordings into one Perfetto-loadable Chrome
    trace at ``out_path``.  Best effort — a failed job may have dumped
    only some parts, and a partial timeline still beats none (the merge
    reports how many ranks it found)."""
    import json

    try:
        from .. import obs  # stdlib-only import (no jax)
    except ImportError:  # executed as a plain file (no package context)
        import importlib.util

        _obs_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "obs")
        _spec = importlib.util.spec_from_file_location(
            "m4j_obs_launch", os.path.join(_obs_dir, "__init__.py"),
            submodule_search_locations=[_obs_dir])
        obs = importlib.util.module_from_spec(_spec)
        sys.modules["m4j_obs_launch"] = obs
        _spec.loader.exec_module(obs)

    parts = obs.part_paths(out_path)
    if not parts:
        print(f"launch: --trace: no recordings found at "
              f"{out_path}.rank*.json (did the ranks reach comm init?)",
              file=sys.stderr, flush=True)
        return
    try:
        merged = obs.merge_files(parts)
        with open(out_path, "w") as f:
            json.dump(merged, f)
        spans = sum(1 for e in merged["traceEvents"] if e.get("ph") == "X")
        print(f"launch: --trace: merged {len(parts)}/{np_} rank "
              f"recording(s), {spans} spans -> {out_path} "
              "(load in https://ui.perfetto.dev)",
              file=sys.stderr, flush=True)
    except Exception as e:
        print(f"launch: --trace: merge failed: {e}", file=sys.stderr,
              flush=True)


# libtpu's slice-builder port; one per rank process on a shared host
_TPU_PROCESS_PORT0 = 8476


def tpu_rank_env(platforms: str, slot: int, np_: int) -> dict:
    """Environment that gives rank ``slot`` its own TPU chip.

    A TPU process claims every chip it can see, so ``np_ > 1`` ranks on
    one host would each try to claim all of them.  Each rank instead
    sees one chip (chip ``slot``) as a single-chip slice of its own,
    with its own libtpu port; several processes then load libtpu, one
    chip each.  A rank whose chip does not exist fails at libtpu start.
    One rank, or a platform list without ``tpu``, keeps every chip.
    """
    if np_ <= 1 or "tpu" not in platforms.split(","):
        return {}
    return {
        "TPU_VISIBLE_CHIPS": str(slot),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": str(_TPU_PROCESS_PORT0 + slot),
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="python -m mpi4jax_tpu.runtime.launch",
        description="run a program as N world-tier ranks",
    )
    parser.add_argument("-n", "--np", type=int, required=True,
                        help="number of ranks")
    parser.add_argument("--port", type=int, default=None,
                        help="base TCP port (default: derived from pid)")
    parser.add_argument("--platform", default=None,
                        help="JAX_PLATFORMS for the ranks (default: cpu)")
    parser.add_argument("--timeout", type=float, default=None,
                        help="wall-clock watchdog: SIGTERM (then SIGKILL) "
                             "the whole rank group after this many seconds "
                             "and exit 124 — a wedged job is reaped, not "
                             "inherited by the scheduler")
    parser.add_argument("--hosts", default=None,
                        help="comma-separated per-rank host list for the "
                             "native transport (pod/DCN layout; default: "
                             "all ranks on 127.0.0.1). Rank i listens on "
                             "hosts[i]; peers dial it there. NOTE: this "
                             "launcher always spawns every rank locally "
                             "(the list is for multi-homed hosts and "
                             "loopback-alias testing); on a real pod, "
                             "start one process per rank with your "
                             "scheduler and set MPI4JAX_TPU_RANK/SIZE "
                             "plus MPI4JAX_TPU_HOSTS directly.")
    parser.add_argument("--fake-hosts", default=None, metavar="SPEC",
                        help="virtual host partition for topology testing "
                             "(exports MPI4JAX_TPU_FAKE_HOSTS to every "
                             "rank): 'r0,r1|r2,r3' makes ranks 0-1 and "
                             "2-3 two islands — intra-island shm arenas, "
                             "TCP between islands, hierarchical "
                             "collectives eligible (docs/usage.md "
                             "§ Transport tiers and topology)")
    parser.add_argument("--verify", action="store_true",
                        help="pre-flight: statically verify the program's "
                             "communication schedule (python -m "
                             "mpi4jax_tpu.analyze) and exit 3 with the "
                             "findings table when it fails — BEFORE any "
                             "rank is spawned")
    parser.add_argument("--plan", action="store_true",
                        help="pre-flight: compile the program's "
                             "communication schedule into a verified "
                             "execution plan (python -m mpi4jax_tpu."
                             "analyze --emit-plan) and run every rank "
                             "with MPI4JAX_TPU_PLAN pointing at it — "
                             "hoisted recv posts and deferred send "
                             "completions on the progress engine.  An "
                             "unprovable plan falls back to the "
                             "historic path with a notice "
                             "(docs/analysis.md)")
    parser.add_argument("--elastic", action="store_true",
                        help="supervise for RECOVERY instead of "
                             "fail-fast teardown: a dead rank advances "
                             "the world generation — survivors rebuild "
                             "over a re-derived port block via "
                             "mpi4jax_tpu.elastic.recover(), and under "
                             "the respawn policy the dead slot's "
                             "program restarts in a fresh process.  A "
                             "job that completes after recoveries "
                             "exits 0 (docs/elasticity.md)")
    parser.add_argument("--elastic-policy", default=None,
                        choices=("shrink", "respawn"),
                        help="what --elastic does about a dead rank: "
                             "shrink (default; survivors renumber "
                             "densely into a smaller world) or respawn "
                             "(restart the dead slot at full size).  "
                             "Default: MPI4JAX_TPU_ELASTIC_POLICY, "
                             "else shrink")
    parser.add_argument("--trace", default=None, metavar="OUT.json",
                        help="record every rank's per-op events "
                             "(MPI4JAX_TPU_TRACE) and merge them into one "
                             "Perfetto-loadable Chrome trace at OUT.json "
                             "after the job ends; per-rank recordings stay "
                             "next to it as OUT.json.rank<r>.json "
                             "(docs/observability.md)")
    parser.add_argument("--live", action="store_true",
                        help="arm live drift detection + collective "
                             "re-tuning in every rank "
                             "(MPI4JAX_TPU_LIVE=auto; thresholds via "
                             "MPI4JAX_TPU_LIVE_WINDOW / _DRIFT_PCT / "
                             "_COOLDOWN_OPS — docs/usage.md)")
    parser.add_argument("prog", help="python program to run")
    parser.add_argument("args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)

    if args.hosts:
        nhosts = len(args.hosts.split(","))
        if nhosts != args.np:
            parser.error(
                f"--hosts lists {nhosts} entries for {args.np} ranks"
            )

    plan_path = ""
    if args.plan:
        # one analyzer run serves both gates: with --verify it enforces
        # the findings verdict too (tracing a large program twice would
        # double the pre-launch cost for nothing)
        rc, plan_path = _preflight_plan(args.prog, args.np, args.args,
                                        enforce_verify=args.verify,
                                        elastic=args.elastic)
        if rc != 0:
            return rc
    elif args.verify:
        rc = _preflight_verify(args.prog, args.np, args.args)
        if rc != 0:
            return rc

    if args.trace:
        # stale parts from a previous run at the same path (possibly a
        # different world size) must not leak into this run's merge or
        # into tune --from-trace's glob
        import glob as _glob

        trace_abs = os.path.abspath(args.trace)
        for stale in _glob.glob(f"{_glob.escape(trace_abs)}.rank*.json"):
            try:
                os.unlink(stale)
            except OSError:
                pass

    base_port = args.port or (40000 + os.getpid() % 20000)
    # job-unique token for /dev/shm arena names: a crashed earlier job
    # with the same port must never collide with this one's segments
    import uuid

    jobid = uuid.uuid4().hex[:16]

    elastic_policy = None
    elastic_dir = None
    if args.elastic:
        elastic_policy = (args.elastic_policy
                          or os.environ.get("MPI4JAX_TPU_ELASTIC_POLICY")
                          or "shrink").strip()
        if elastic_policy not in ("shrink", "respawn"):
            parser.error(
                f"--elastic policy must be shrink or respawn, "
                f"got {elastic_policy!r}")
        import tempfile

        elastic_dir = tempfile.mkdtemp(prefix="m4j_elastic_")

    procs = []
    tails = []
    pumps = []

    # scheduler preemption (SIGTERM to the launcher) must take the whole
    # rank group down, not orphan it — installed BEFORE the first spawn
    # so a signal landing mid-startup still reaches the reap path.
    # During the spawn loop itself delivery is DEFERRED, not raised: a
    # handler firing between Popen() returning and procs.append() would
    # otherwise reap a group missing the just-forked rank.  (Blocking
    # the signals with pthread_sigmask instead is wrong: children
    # inherit the blocked mask through fork+exec and would then never
    # see forwarded signals at all.)
    in_spawn = [True]
    deferred = []

    def _on_sigterm(signum, frame):
        if in_spawn[0]:
            deferred.append(_Terminated)
        else:
            raise _Terminated

    def _on_sigint_spawn(signum, frame):
        deferred.append(KeyboardInterrupt)

    old_term = signal.signal(signal.SIGTERM, _on_sigterm)
    old_int = signal.getsignal(signal.SIGINT)

    exit_code = 0
    first_fail = None  # (rank, exit code)
    watchdog_fired = False
    t_start = time.time()
    pending = {}       # slot -> live process
    slot_tails = {}    # slot -> the slot's LATEST process's stderr tail
    generation = 0
    deaths = []        # every rank death observed, in order
    lost_slots = []    # slots PERMANENTLY lost (shrink; respawned
                       # slots died but are back, so they are not lost)
    # a deterministically-crashing program under respawn would otherwise
    # loop forever; past this many generations the launcher gives up
    max_generations = 2 * args.np + 2

    def _spawn(slot, *, rank, size, coord, gen):
        """One rank process; returns its Popen.  ``slot`` is the
        launcher-slot identity (stable across generations), ``rank``
        the world rank this process bootstraps with."""
        env = dict(os.environ)
        env["MPI4JAX_TPU_RANK"] = str(rank)
        env["MPI4JAX_TPU_SIZE"] = str(size)
        env["MPI4JAX_TPU_COORD"] = coord
        env["MPI4JAX_TPU_JOBID"] = jobid
        if elastic_policy is not None:
            env["MPI4JAX_TPU_ELASTIC"] = "1"
            env["MPI4JAX_TPU_ELASTIC_DIR"] = elastic_dir
            env["MPI4JAX_TPU_ELASTIC_POLICY"] = elastic_policy
            env["MPI4JAX_TPU_GENERATION"] = str(gen)
            env["MPI4JAX_TPU_SLOT"] = str(slot)
            # recovery depends on every blocking wait being bounded:
            # poison frames unblock most peers instantly, but a peer
            # parked on the DEAD rank's socket needs the deadline.
            # setdefault — explicit operator settings win.
            env.setdefault("MPI4JAX_TPU_TIMEOUT_S", "60")
            env.setdefault("MPI4JAX_TPU_CONNECT_TIMEOUT_S", "60")
        if args.trace:
            env["MPI4JAX_TPU_TRACE"] = os.path.abspath(args.trace)
        if args.live:
            env["MPI4JAX_TPU_LIVE"] = "auto"
        if plan_path:
            env["MPI4JAX_TPU_PLAN"] = plan_path
        if args.hosts:
            env["MPI4JAX_TPU_HOSTS"] = args.hosts
        if args.fake_hosts:
            env["MPI4JAX_TPU_FAKE_HOSTS"] = args.fake_hosts
        if args.platform:
            env["JAX_PLATFORMS"] = args.platform
        else:
            env.setdefault("JAX_PLATFORMS", "cpu")
        env.update(tpu_rank_env(env["JAX_PLATFORMS"], slot, args.np))
        p = subprocess.Popen(
            [sys.executable, args.prog, *args.args], env=env,
            stderr=subprocess.PIPE,
        )
        tail = collections.deque(maxlen=80)
        pump = threading.Thread(
            target=_pump_stderr, args=(p.stderr, tail), daemon=True
        )
        pump.start()
        procs.append(p)
        tails.append(tail)
        pumps.append(pump)
        slot_tails[slot] = tail
        return p

    def _announce(gen, members, port, policy):
        """Atomically write the generation file survivors poll for:
        member map (slot -> dense new rank; lost slots -> -1), world
        size, and the re-derived base port."""
        mapping = {str(s): i for i, s in enumerate(members)}
        for s in lost_slots:
            mapping.setdefault(str(s), -1)
        hosts = ""
        if args.hosts:
            hl = args.hosts.split(",")
            hosts = ",".join(hl[s] for s in members)
        spec = {
            "generation": gen,
            "size": len(members),
            "base_port": port,
            "map": mapping,
            "lost": list(lost_slots),
            "policy": policy,
            "hosts": hosts,
            "np0": args.np,
        }
        path = os.path.join(elastic_dir, f"gen_{gen}.json")
        import json as _json

        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            _json.dump(spec, f)
        os.replace(tmp, path)
        return spec

    def _failover(slot, rc):
        """One dead rank under --elastic: record it, advance the
        generation, announce the survivor map, and (respawn policy)
        restart the dead slot's program.  Returns False when recovery
        is impossible (no survivors / generation cap) — the caller
        then falls back to fail-fast semantics."""
        nonlocal generation
        deaths.append(slot)
        if elastic_policy != "respawn":
            lost_slots.append(slot)
        generation += 1
        live = sorted(pending)
        err = _last_native_error(slot_tails.get(slot, ()))
        print(
            f"launch: elastic: rank slot {slot} died (exit code {rc})"
            + (f"; last error: {err}" if err else "")
            + f"; advancing to generation {generation} "
            f"({elastic_policy})", file=sys.stderr, flush=True,
        )
        if generation > max_generations:
            print(
                f"launch: elastic: giving up after {generation - 1} "
                "recoveries (generation cap); tearing the job down",
                file=sys.stderr, flush=True,
            )
            return False
        if not live and elastic_policy == "shrink":
            print(
                "launch: elastic: no surviving rank to shrink onto",
                file=sys.stderr, flush=True,
            )
            return False
        new_port = base_port + generation * (args.np + 1)
        if elastic_policy == "respawn":
            members = sorted(set(live) | {slot})
            spec = _announce(generation, members, new_port, "respawn")
            new_rank = spec["map"][str(slot)]
            in_spawn[0] = True
            try:
                p = _spawn(slot, rank=new_rank, size=len(members),
                           coord=f"127.0.0.1:{new_port}",
                           gen=generation)
            finally:
                in_spawn[0] = False
            pending[slot] = p
            if deferred:
                raise deferred[0]
        else:
            _announce(generation, live, new_port, "shrink")
        return True

    try:
        signal.signal(signal.SIGINT, _on_sigint_spawn)
        for rank in range(args.np):
            pending[rank] = _spawn(
                rank, rank=rank, size=args.np,
                coord=f"127.0.0.1:{base_port}", gen=0)
        in_spawn[0] = False
        signal.signal(signal.SIGINT, old_int)
        if deferred:
            raise deferred[0]  # a signal arrived mid-spawn: reap now
        while pending:
            dead = []
            for slot, p in list(pending.items()):
                rc = p.poll()
                if rc is not None:
                    dead.append((slot, rc))
            if any(rc != 0 for _, rc in dead):
                # cascade failures land milliseconds after their root
                # cause: a victim polled late in the sweep could be
                # seen dead while the root cause (already exited, but
                # polled earlier, while still alive) waits for the
                # next sweep — misattributing "failed first".  One
                # short beat + re-poll collects the whole failure
                # wave before attribution.
                time.sleep(0.08)
                for slot, p in list(pending.items()):
                    rc = p.poll()
                    if rc is not None and (slot, rc) not in dead:
                        dead.append((slot, rc))
            aborted = False
            for slot, rc in sorted(dead):
                if slot not in pending:
                    continue
                del pending[slot]
                if rc == 0:
                    continue
                if first_fail is None:
                    first_fail = (slot, rc)
                if elastic_policy is not None and _failover(slot, rc):
                    continue
                exit_code = rc
                # fail-fast: take the rest of the job down
                _terminate_group(list(pending.values()))
                pending.clear()
                aborted = True
                break
            if aborted:
                break
            if pending and args.timeout is not None \
                    and time.time() - t_start > args.timeout:
                watchdog_fired = True
                stuck = sorted(s for s, p in pending.items()
                               if p.poll() is None)
                print(
                    f"launch: watchdog: wall-clock timeout after "
                    f"{args.timeout:g} s; terminating rank(s) {stuck}",
                    file=sys.stderr, flush=True,
                )
                _terminate_group(list(pending.values()))
                pending.clear()
                exit_code = 124
            time.sleep(0.02)
    except KeyboardInterrupt:
        # repeated signals must not unwind the reap itself: ignore both
        # for the remainder of the teardown
        signal.signal(signal.SIGINT, signal.SIG_IGN)
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        live = [p for p in procs if p.poll() is None]
        for p in live:
            try:
                p.send_signal(signal.SIGINT)
            except OSError:
                pass
        # grace, then escalate: no orphan ranks survive Ctrl-C
        deadline = time.time() + _grace_s()
        for p in live:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                pass
        _terminate_group(live)
        signal.signal(signal.SIGINT, old_int)
        exit_code = 130
    except _Terminated:
        # a re-delivered SIGTERM (schedulers re-signal) or a Ctrl-C
        # during the grace wait must not raise inside this very handler
        # and abort the reap half-way
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        signal.signal(signal.SIGINT, signal.SIG_IGN)
        _terminate_group(procs)
        signal.signal(signal.SIGINT, old_int)
        exit_code = 143
    except Exception:
        # e.g. a Popen failure mid-spawn: already-forked ranks must not
        # outlive the launcher's own crash
        _terminate_group(procs)
        raise
    finally:
        signal.signal(signal.SIGINT, old_int)
        signal.signal(signal.SIGTERM, old_term)
        for pump in pumps:
            pump.join(timeout=2.0)
        if plan_path:  # every exit path, not just straight-line success
            try:
                os.unlink(plan_path)
            except OSError:
                pass

    if args.trace:
        _merge_trace(os.path.abspath(args.trace), args.np)

    # link-layer self-healing outcomes from every slot's stderr tail: a
    # slot that RECOVERED a transient link fault in place is not a dead
    # rank, and must never be reported as one; a link the layer declared
    # DEAD names the failed connection (slot -> peer) for the
    # post-mortem, since the dying rank's exit code alone cannot
    healed_slots, dead_links = _self_heal_outcomes(slot_tails)
    heal_note = ""
    if healed_slots:
        total = sum(healed_slots.values())
        heal_note = (
            f"; transient link fault(s) healed in-place on rank slot(s) "
            f"{sorted(healed_slots)} ({total} reconnect(s), not rank "
            f"deaths)")
    link_note = ""
    if dead_links:
        link_note = "; failed link(s): " + ", ".join(
            f"rank {s} -> rank {p}" for s, p in dead_links)

    if elastic_policy is not None and generation > 0 and exit_code == 0:
        # the recovery outcome, not the first failure: the job SURVIVED
        # — say what it cost and where it resumed (exit code stays 0)
        import re as _re

        steps = []
        for tail in slot_tails.values():
            for line in tail:
                m = _re.search(rb"resum\w+ from step (\d+)",
                               bytes(line))
                if m:
                    steps.append(int(m.group(1)))
        resume = f", resumed from step {max(steps)}" if steps else \
            ", no checkpoint resume reported"
        # shrink loses slots permanently; a respawned slot died but
        # finished — saying "lost" for it would misread the outcome
        outcome = (f"lost rank slot(s) {lost_slots}"
                   if elastic_policy != "respawn" else
                   f"rank death(s) at slot(s) {deaths} (respawned)")
        print(
            f"launch: post-mortem: elastic job completed after recovery "
            f"(policy {elastic_policy}): reached generation "
            f"{generation}, {outcome}{resume}{link_note}{heal_note}",
            file=sys.stderr, flush=True,
        )
    elif first_fail is not None:
        rank, rc = first_fail
        err = _last_native_error(slot_tails.get(rank, ()))
        gen_note = (
            f" after reaching generation {generation} "
            f"(death(s) at slot(s) {deaths})"
            if elastic_policy is not None and generation > 0 else "")
        print(
            f"launch: post-mortem: rank {rank} failed first (exit code "
            f"{rc}){gen_note}" + (f"; last error: {err}" if err else "")
            + link_note + heal_note,
            file=sys.stderr, flush=True,
        )
    elif healed_slots:
        # the job SUCCEEDED and nothing died, but the wire was not
        # quiet: say what the link layer absorbed, so a flaky fabric is
        # visible before it degrades into actual rank deaths
        print(
            "launch: post-mortem: job completed; no rank failed"
            + heal_note.replace("; ", " — ", 1),
            file=sys.stderr, flush=True,
        )
    elif watchdog_fired:
        print(
            "launch: post-mortem: no rank failed — the job outlived the "
            f"--timeout watchdog ({args.timeout:g} s); a hung transport "
            "wait with MPI4JAX_TPU_TIMEOUT_S unset looks exactly like "
            "this (docs/sharp-bits.md)",
            file=sys.stderr, flush=True,
        )
    if elastic_dir is not None:
        import shutil

        shutil.rmtree(elastic_dir, ignore_errors=True)
    return exit_code


if __name__ == "__main__":
    sys.exit(main())
