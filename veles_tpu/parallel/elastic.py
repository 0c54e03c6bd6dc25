"""Elastic SPMD recovery plane (ISSUE 13, ROADMAP item 5 remainder).

The coordinator (master↔slave) tier survives membership churn since
PR 12, but a ``jax.distributed`` SPMD pod (:mod:`mesh` / :mod:`dp`)
dies permanently when ANY participant is lost: one SIGKILL wedges every
survivor inside a collective, and the runtime cannot re-initialize at a
new world size in-process. This module is the orchestration layer that
turns that into a bounded hiccup:

* :class:`RendezvousServer` — a tiny generation-numbered membership
  service (JSON lines over TCP, one persistent connection per host
  supervisor). A *generation* is one agreed membership: it assigns
  ``(generation, world_size, rank)``, distributes the per-generation
  ``jax.distributed`` coordinator address, and detects participant
  death through connection EOF (a SIGKILLed supervisor's kernel closes
  the socket) with heartbeat age as the partition backstop. Any death
  *breaks* the generation; survivors re-rendezvous and a new one forms
  at the surviving world size after a settle window.

* :class:`ElasticSupervisor` — the per-host process that OWNS the
  worker lifecycle. It spawns the SPMD worker with the generation's
  membership in ``VELES_ELASTIC_*`` env, watches both the worker (a
  local death is reported within one poll tick) and the rendezvous
  (a remote death arrives as a ``restart`` verdict), SIGKILLs the
  wedged worker on a break, and re-enters rendezvous — since
  ``jax.distributed`` cannot re-init in-process, restart-the-process
  IS the mesh re-formation primitive.

* :func:`run_elastic_training` — the worker-side harness: joins the
  runtime (``mesh.init_multihost`` through the shared backoff dial),
  restores the newest complete sharded checkpoint generation
  (``snapshotter.restore_latest`` — a world-size-N checkpoint
  re-assembles and re-shards at world size M), rewinds the loader to
  the last complete step boundary (``decision.prepare_resume`` +
  ``loader.reset_to_epoch_start``), and trains with a per-epoch
  sharded checkpoint cut on the trainer's ``epoch_callback`` seam.

**The determinism contract** (the loss-parity proof in
``tests/test_elastic.py``): every process derives the SAME global index
matrix from the checkpointed PRNG streams, and the mesh sharding — not
per-process bookkeeping — partitions it over the membership. So the
re-partition at a new world size is deterministic by construction,
every minibatch of a replayed epoch trains exactly once, and a killed
run restarted from its last complete checkpoint produces a loss curve
*bit-identical* to an uninterrupted run of the same mesh shape.

CLI (also the chaos harness's building blocks)::

    # membership service (one per pod; typically beside the scheduler)
    python -m veles_tpu.parallel.elastic rendezvous --port 4710 \\
        --expected 2

    # one per host: supervise the training process
    python -m veles_tpu.parallel.elastic supervise \\
        --rdzv 10.0.0.1:4710 --snapshots /ckpt/run17 -- \\
        python train_my_pod.py

    # the built-in loopback demo worker (tests / chaos legs)
    python -m veles_tpu.parallel.elastic worker-demo --out hist.json
"""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

from veles_tpu.envknob import env_flag, env_knob
from veles_tpu.logger import Logger
from veles_tpu.parallel.retry import retry_with_backoff

#: env contract between supervisor and worker
ENV_GEN = "VELES_ELASTIC_GEN"
ENV_WORLD = "VELES_ELASTIC_WORLD"
ENV_RANK = "VELES_ELASTIC_RANK"
ENV_COORD = "VELES_ELASTIC_COORD"
ENV_SNAPSHOTS = "VELES_ELASTIC_SNAPSHOTS"
#: job identity (ISSUE 19): the scheduler mints ONE trace id per job
#: and carries it here, so worker spans, flight records from a dying
#: gang and preempt/resume events all correlate under the job's id
ENV_TRACE = "VELES_ELASTIC_TRACE"
ENV_JOB = "VELES_ELASTIC_JOB"
ENV_TENANT = "VELES_ELASTIC_TENANT"
#: test/chaos hook: ``"<rank>:<epochs_done>"`` — the matching worker
#: SIGKILLs itself at that epoch boundary BEFORE the checkpoint is cut
#: (the deterministic mid-epoch death, like PR 12's death-on-job-8)
ENV_TEST_DIE = "VELES_ELASTIC_TEST_DIE"
#: like ENV_TEST_DIE but the worker RAISES instead of SIGKILLing
#: itself — the death leaves a flight record behind, which the trace-
#: correlation tests read back (a SIGKILL leaves only the scheduler's
#: own record)
ENV_TEST_FAIL = "VELES_ELASTIC_TEST_FAIL"


def _metrics():
    from veles_tpu.telemetry.registry import get_registry
    r = get_registry()
    return {
        "generation": r.gauge(
            "veles_mesh_generation",
            "Current elastic SPMD mesh generation number"),
        "world": r.gauge(
            "veles_spmd_world_size",
            "World size of the current SPMD generation"),
        "lost": r.counter(
            "veles_spmd_participants_lost_total",
            "SPMD participants lost (worker crash, supervisor death, "
            "heartbeat silence)", labels=("reason",)),
        "recovery": r.histogram(
            "veles_spmd_recovery_ms",
            "SPMD recovery latencies (reform: break -> new generation "
            "formed; respawn: break verdict -> replacement worker "
            "spawned; restore: checkpoint load + rewind)",
            labels=("event",)),
    }


def _free_port(host="127.0.0.1"):
    with socket.socket() as s:
        s.bind((host, 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# rendezvous service
# ---------------------------------------------------------------------------


class RendezvousServer(Logger):
    """Generation-numbered membership for elastic SPMD supervisors.

    Protocol: newline-delimited JSON over one persistent TCP
    connection per supervisor. Commands: ``join`` (register / poll for
    an assignment), ``hb`` (liveness + the break verdict), ``set_coord``
    / ``coord`` (per-generation jax.distributed coordinator address,
    published by the generation's rank 0), ``worker_exit`` (local
    worker ended), ``leave`` (give up for good).

    Formation policy: generation 0 waits for ``expected`` members when
    given (the scheduler's initial pod must assemble whole); later
    generations form with whatever membership is present once it has
    been stable for ``settle_s`` (and ≥ ``min_workers``) — that is the
    world-size shrink on failure, and the grow-back when a replaced
    host rejoins. Membership loss is detected by connection EOF
    immediately, or ``heartbeat_timeout_s`` of silence as the
    partition backstop.

    The server is the pod's rendezvous anchor; its own host failing is
    out of scope here (run it under the cluster scheduler beside the
    job — the same place the pod would be rescheduled from anyway).
    """

    def __init__(self, port=0, host="127.0.0.1", min_workers=1,
                 expected=None, settle_s=1.0, heartbeat_timeout_s=5.0,
                 absorb_joins=False):
        super(RendezvousServer, self).__init__()
        self.min_workers = int(min_workers)
        self.expected = int(expected) if expected else None
        self.settle_s = float(settle_s)
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.absorb_joins = bool(absorb_joins)
        self._lock = threading.RLock()
        self._members = {}  # token -> state dict
        self.generation = 0
        self.phase = "forming"  # forming | running | done
        self.world_size = 0
        self._coords = {}  # generation -> "host:port"
        self._last_change = time.monotonic()
        self._break_at = None
        self.lost_total = 0
        self.last_recovery_s = None
        self._metrics = _metrics()
        #: federated member telemetry (ISSUE 19): heartbeats carry
        #: SnapshotEncoder deltas, absorbed here with the SAME
        #: resync/GC/cardinality semantics as the coordinator path —
        #: created on the first beat that actually carries telemetry
        self._federation = None
        self._federation_lock = threading.Lock()
        self._stop = threading.Event()
        self._conns = set()
        self._listener = socket.socket()
        self._listener.setsockopt(socket.SOL_SOCKET,
                                  socket.SO_REUSEADDR, 1)
        self._listener.bind((host, port))
        self._listener.listen(32)
        self.address = self._listener.getsockname()
        self._threads = []

    # -- lifecycle ---------------------------------------------------------

    def start(self):
        for target, name in ((self._accept_loop, "rdzv-accept"),
                             (self._reap_loop, "rdzv-reaper")):
            t = threading.Thread(target=target, daemon=True, name=name)
            t.start()
            self._threads.append(t)
        self.info("rendezvous serving on %s:%d (expected=%s "
                  "min_workers=%d settle=%.1fs)", self.address[0],
                  self.address[1], self.expected, self.min_workers,
                  self.settle_s)
        return self

    def stop(self):
        self._stop.set()
        try:
            self._listener.close()
        except OSError:
            pass
        # unwind the per-connection serving threads too: a long-lived
        # embedder (perf-gate probe, bench orchestrator, tests) must
        # not accumulate one parked readline() thread + open fd per
        # supervisor per server instance
        with self._lock:
            conns = list(self._conns)
        for conn in conns:
            try:
                conn.close()
            except OSError:
                pass

    # -- connection handling -----------------------------------------------

    def _accept_loop(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 daemon=True, name="rdzv-conn")
            t.start()

    def _serve_conn(self, conn):
        member = None
        with self._lock:
            self._conns.add(conn)
        try:
            fin = conn.makefile("rb")
            fout = conn.makefile("wb")
            while not self._stop.is_set():
                line = fin.readline()
                if not line:
                    break
                try:
                    msg = json.loads(line)
                except ValueError:
                    break
                member = msg.get("member", member)
                reply = self._handle(msg)
                telemetry = msg.get("telemetry")
                if telemetry is not None and member is not None:
                    # absorbed OUTSIDE self._lock (the coordinator's
                    # _absorb_telemetry pattern): merging a delta must
                    # not serialize against membership dispatch
                    try:
                        reply.update(
                            self._absorb_telemetry(member, telemetry))
                    except Exception:
                        pass  # telemetry must never kill the beat
                with self._lock:
                    # this conn is now the member's CURRENT lifeline
                    state = self._members.get(member)
                    if state is not None:
                        state["conn_"] = conn
                fout.write(json.dumps(reply).encode() + b"\n")
                fout.flush()
        except (OSError, ValueError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass
            with self._lock:
                self._conns.discard(conn)
                state = self._members.get(member)
                # a client that RECONNECTED under the same token owns
                # a newer lifeline: this connection's EOF is stale and
                # must not evict the rejoined member (that would break
                # a healthy re-formed generation over a TCP blip)
                stale = (state is not None and
                         state.get("conn_") is not conn)
            if member is not None and not stale and \
                    not self._stop.is_set():
                # the supervisor's lifeline died: a SIGKILLed host's
                # kernel closes this socket — the FAST death-detection
                # path (the heartbeat age check is only the partition
                # backstop). Never on server stop(): that close is
                # ours, not a death.
                self._remove_member(member, reason="connection_lost")

    # -- federated telemetry -----------------------------------------------

    def federation(self):
        """The server's :class:`FederatedRegistry` (created on first
        use — a pod that never piggybacks telemetry never pays for
        one)."""
        with self._federation_lock:
            if self._federation is None:
                from veles_tpu.telemetry.federation import \
                    FederatedRegistry
                self._federation = FederatedRegistry()
            return self._federation

    def _absorb_telemetry(self, member, delta):
        """Merge one beat-carried delta; returns ack hints for the
        reply (``{"resync": True}`` after a sequence gap)."""
        hints = self.federation().apply(member, delta)
        with self._lock:
            live = member in self._members
        if not live:
            # reaped between dispatch and merge: the feed must not
            # outlive the membership (same liveness re-check the
            # coordinator does after its out-of-lock merge)
            self._federation.remove_slave(member)
            return {}
        return hints or {}

    # -- protocol ----------------------------------------------------------

    def _handle(self, msg):
        cmd = msg.get("cmd")
        member = msg.get("member")
        with self._lock:
            state = self._members.get(member)
            if state is not None:
                # EVERY command is proof of life — a supervisor parked
                # in a long coord wait must not be reaped for silence
                state["last_seen"] = time.monotonic()
            if cmd == "join":
                return self._join(member)
            if cmd == "hb":
                return self._heartbeat(member, msg.get("gen"))
            if cmd == "set_coord":
                self._coords[int(msg["gen"])] = msg["addr"]
                return {"status": "ok"}
            if cmd == "coord":
                gen = int(msg["gen"])
                return {"status": "ok", "addr": self._coords.get(gen),
                        "current_gen": self.generation,
                        "phase": self.phase}
            if cmd == "worker_exit":
                return self._worker_exit(member, msg.get("gen"),
                                         int(msg.get("code", 1)))
            if cmd == "leave":
                self._remove_member(member, reason="leave")
                return {"status": "ok"}
        return {"status": "error", "error": "unknown cmd %r" % cmd}

    def _join(self, member):
        """Register/refresh a member. Caller holds ``self._lock``
        (every ``_handle`` dispatch runs under it)."""
        if self.phase == "done":
            return {"status": "done"}
        state = self._members.get(member)
        if state is None:
            state = self._members[member] = {
                "state": "waiting", "rank": None, "gen": None,
                "last_seen": time.monotonic()}
            self._last_change = time.monotonic()
            self.info("member %s joined (now %d waiting)", member,
                      sum(1 for m in self._members.values()
                          if m["state"] == "waiting"))
            if self.phase == "running" and self.absorb_joins:
                self._break_generation("absorb_join", lost=False)
        state["last_seen"] = time.monotonic()
        if self.phase == "running" and state["gen"] == self.generation:
            return {"status": "assigned", "gen": self.generation,
                    "world": self.world_size, "rank": state["rank"]}
        if state["state"] != "waiting":
            state["state"] = "waiting"
            state["gen"] = None
        self._maybe_form()
        if self.phase == "running" and state["gen"] == self.generation:
            return {"status": "assigned", "gen": self.generation,
                    "world": self.world_size, "rank": state["rank"]}
        return {"status": "wait"}

    def _heartbeat(self, member, gen):
        state = self._members.get(member)
        if self.phase == "done":
            return {"status": "done"}
        if state is None:
            return {"status": "restart"}  # reaped: re-join from scratch
        state["last_seen"] = time.monotonic()
        if self.phase == "running" and state["gen"] == self.generation \
                and gen == self.generation:
            return {"status": "ok"}
        return {"status": "restart"}

    def _worker_exit(self, member, gen, code):
        state = self._members.get(member)
        if state is None:
            # reaped while the worker was dying: whatever killed the
            # membership is the root cause, not this worker
            return {"status": "restart", "stale": True}
        state["last_seen"] = time.monotonic()
        if gen != self.generation or self.phase != "running":
            # the generation was ALREADY broken when this worker died:
            # its death is collateral (a peer loss aborted its
            # collective), not a crash of its own — the supervisor
            # must not charge it against the crash budget
            return {"status": "restart", "stale": True}
        if code == 0:
            state["state"] = "done"
            current = [m for m in self._members.values()
                       if m["gen"] == self.generation]
            if current and all(m["state"] == "done" for m in current):
                self.phase = "done"
                self.info("generation %d complete (world %d)",
                          self.generation, self.world_size)
            return {"status": "done" if self.phase == "done" else "ok"}
        self._break_generation("worker_crash(%s, rc=%s)"
                               % (member, code))
        return {"status": "restart"}

    # -- membership state machine ------------------------------------------

    def _remove_member(self, member, reason):
        with self._lock:
            state = self._members.pop(member, None)
            if state is None:
                return
            self._last_change = time.monotonic()
            in_current = (self.phase == "running" and
                          state["gen"] == self.generation)
            self.info("member %s removed (%s)%s", member, reason,
                      " — breaking generation %d" % self.generation
                      if in_current else "")
            if in_current:
                self._break_generation("%s(%s)" % (reason, member))
        with self._federation_lock:
            federation = self._federation
        if federation is not None:
            # GC the dead member's federated feed with the membership
            federation.remove_slave(member)

    def _break_generation(self, reason, lost=True):
        """A participant of the RUNNING generation is gone (or a join
        must be absorbed): bump the generation and send every
        survivor back through rendezvous. Caller holds
        ``self._lock``."""
        if self.phase != "running":
            return
        if lost:
            self.lost_total += 1
            self._metrics["lost"].labels(
                reason=reason.split("(")[0]).inc()
        self.warning("generation %d broken: %s — re-forming at the "
                     "surviving world size", self.generation, reason)
        self.generation += 1
        self.phase = "forming"
        self._break_at = time.monotonic()
        self._last_change = time.monotonic()
        for state in self._members.values():
            state["state"] = "waiting"
            state["gen"] = None
            state["rank"] = None

    def _maybe_form(self):
        if self.phase != "forming":
            return
        waiting = sorted(token for token, m in self._members.items()
                         if m["state"] == "waiting")
        if not waiting:
            return
        now = time.monotonic()
        if self.generation == 0 and self.expected:
            # the initial pod assembles WHOLE: a slow-starting host
            # must not get raced into a shrunken first generation
            if len(waiting) < self.expected:
                return
        else:
            if len(waiting) < self.min_workers:
                return
            full = self.expected is not None and \
                len(waiting) >= self.expected
            if not full and now - self._last_change < self.settle_s:
                return
        for rank, token in enumerate(waiting):
            state = self._members[token]
            state["state"] = "running"
            state["gen"] = self.generation
            state["rank"] = rank
        self.world_size = len(waiting)
        self.phase = "running"
        self._metrics["generation"].set(self.generation)
        self._metrics["world"].set(self.world_size)
        if self._break_at is not None:
            self.last_recovery_s = now - self._break_at
            self._metrics["recovery"].labels(event="reform").observe(
                self.last_recovery_s * 1e3)
            self._break_at = None
        self.info("generation %d formed: world=%d members=%s",
                  self.generation, self.world_size, waiting)

    def _reap_loop(self):
        while not self._stop.is_set():
            time.sleep(0.25)
            with self._lock:
                if self.phase == "done":
                    continue
                now = time.monotonic()
                stale = [token for token, m in self._members.items()
                         if now - m["last_seen"] >
                         self.heartbeat_timeout_s]
            for token in stale:
                self._remove_member(token, reason="heartbeat_timeout")
            with self._lock:
                self._maybe_form()


class RendezvousClient(object):
    """The supervisor's side of the protocol (one persistent
    connection; the dial and any reconnect go through the shared
    jittered-backoff helper)."""

    def __init__(self, address, member, dial_budget_s=60.0):
        if isinstance(address, str):
            host, _, port = address.rpartition(":")
            address = (host or "127.0.0.1", int(port))
        self.address = tuple(address)
        self.member = member
        self.dial_budget_s = dial_budget_s
        self._lock = threading.Lock()
        self._sock = None
        self._fin = self._fout = None
        self._closed = False
        self._connect(dial_budget_s)

    def _connect(self, budget_s):
        def attempt():
            sock = socket.create_connection(self.address, timeout=10.0)
            self._sock = sock
            self._fin = sock.makefile("rb")
            self._fout = sock.makefile("wb")

        retry_with_backoff(
            attempt, budget_s,
            give_up=lambda e: self._closed,
            describe="could not reach the rendezvous at %s:%d"
                     % self.address)

    def _request(self, msg, reconnect_budget_s=10.0):
        msg = dict(msg, member=self.member)

        def attempt():
            if self._sock is None:
                self._connect(reconnect_budget_s)
            try:
                self._fout.write(json.dumps(msg).encode() + b"\n")
                self._fout.flush()
                line = self._fin.readline()
                if not line:
                    raise ConnectionError("rendezvous closed the "
                                          "connection")
                return json.loads(line)
            except (OSError, ValueError) as e:
                self._teardown()
                raise ConnectionError(str(e))

        with self._lock:
            return retry_with_backoff(
                attempt, reconnect_budget_s, base_s=0.1,
                give_up=lambda e: self._closed,
                describe="rendezvous request to %s:%d failed"
                         % self.address)

    def _teardown(self):
        for f in (self._fin, self._fout, self._sock):
            try:
                if f is not None:
                    f.close()
            except OSError:
                pass
        self._sock = self._fin = self._fout = None

    # -- commands ----------------------------------------------------------

    def join_wait(self, poll_s=0.2, timeout_s=None):
        """Block until this member is assigned into a generation.
        Returns the assignment dict, or ``None`` when the whole run
        completed while we waited."""
        deadline = (time.monotonic() + timeout_s) if timeout_s else None
        while True:
            reply = self._request({"cmd": "join"})
            status = reply.get("status")
            if status == "assigned":
                return reply
            if status == "done":
                return None
            if deadline and time.monotonic() > deadline:
                raise TimeoutError("rendezvous did not form a "
                                   "generation in %.0fs" % timeout_s)
            time.sleep(poll_s)

    def heartbeat(self, gen):
        return self.heartbeat_full(gen).get("status")

    def heartbeat_full(self, gen, telemetry=None):
        """Full heartbeat reply dict; ``telemetry`` (a SnapshotEncoder
        delta) piggybacks on the beat — the reply may carry a
        ``resync`` hint the caller must feed back to its encoder."""
        msg = {"cmd": "hb", "gen": gen}
        if telemetry is not None:
            msg["telemetry"] = telemetry
        return self._request(msg)

    def set_coord(self, gen, addr):
        self._request({"cmd": "set_coord", "gen": gen, "addr": addr})

    def get_coord_wait(self, gen, poll_s=0.1, timeout_s=60.0):
        """The generation's jax.distributed coordinator address, or
        ``None`` when the generation was superseded while waiting."""
        deadline = time.monotonic() + timeout_s
        while True:
            reply = self._request({"cmd": "coord", "gen": gen})
            if reply.get("addr"):
                return reply["addr"]
            if reply.get("current_gen", gen) != gen or \
                    reply.get("phase") == "done":
                return None
            if time.monotonic() > deadline:
                return None
            time.sleep(poll_s)

    def worker_exit(self, gen, code):
        """Full reply dict: ``status`` plus ``stale`` when the
        generation had already broken before this report."""
        return self._request({"cmd": "worker_exit", "gen": gen,
                              "code": code})

    def leave(self):
        try:
            self._request({"cmd": "leave"})
        except ConnectionError:
            pass

    def close(self):
        self._closed = True
        self._teardown()


# ---------------------------------------------------------------------------
# the per-host supervisor
# ---------------------------------------------------------------------------


class ElasticSupervisor(Logger):
    """Owns one SPMD worker process through membership churn.

    Lifecycle per generation: rendezvous -> (rank 0 publishes a fresh
    ``jax.distributed`` coordinator port) -> spawn the worker with the
    membership in env -> watch. A ``restart`` verdict (someone else
    died, or a join was absorbed) SIGKILLs the worker — it is wedged
    in a collective or about to be — and re-enters rendezvous; a local
    worker death is reported and counts against ``max_restarts``
    (regroup restarts do not: they are the recovery working, not a
    crash loop). Workers run in their own session so the kill takes
    the whole worker process group.
    """

    def __init__(self, rdzv_address, worker_argv, snapshot_dir=None,
                 member=None, max_restarts=3, worker_env=None,
                 poll_s=0.2, coord_host="127.0.0.1",
                 dial_budget_s=60.0, announce=False):
        super(ElasticSupervisor, self).__init__()
        self.rdzv_address = rdzv_address
        self.worker_argv = list(worker_argv)
        self.snapshot_dir = snapshot_dir
        self.member = member or ("%s-%d" % (socket.gethostname(),
                                            os.getpid()))
        self.max_restarts = int(max_restarts)
        self.worker_env = dict(worker_env or {})
        self.poll_s = float(poll_s)
        self.coord_host = coord_host
        self.dial_budget_s = dial_budget_s
        self.announce = announce
        self.worker = None  # current subprocess.Popen
        self.generation = None
        self._metrics = _metrics()
        self._detect_t = None
        # ISSUE 19: the job trace id rides VELES_ELASTIC_TRACE from
        # the scheduler through this supervisor into the worker env
        # (os.environ is copied into every spawn) — our own spans and
        # flight records correlate under it too
        self.trace_id = env_knob(ENV_TRACE)
        if self.trace_id:
            from veles_tpu.telemetry import tracing
            tracing.set_default_trace_id(self.trace_id)
        # heartbeat-piggybacked telemetry (same flag as the
        # coordinator tier: VELES_FEDERATION=0 turns it off fleet-wide)
        self._encoder = None
        if env_flag("VELES_FEDERATION", True):
            from veles_tpu.telemetry.federation import SnapshotEncoder
            self._encoder = SnapshotEncoder()

    def _announce(self, name, **fields):
        if not self.announce:
            return
        print("EVENT %s t=%.6f %s"
              % (name, time.time(),
                 " ".join("%s=%s" % kv for kv in sorted(fields.items()))),
              file=sys.stderr, flush=True)

    # -- worker lifecycle --------------------------------------------------

    def _spawn_worker(self, gen, world, rank, coord):
        env = dict(os.environ)
        env.update(self.worker_env)
        env[ENV_GEN] = str(gen)
        env[ENV_WORLD] = str(world)
        env[ENV_RANK] = str(rank)
        if coord:
            env[ENV_COORD] = coord
        else:
            env.pop(ENV_COORD, None)
        if self.snapshot_dir:
            env[ENV_SNAPSHOTS] = self.snapshot_dir
        proc = subprocess.Popen(self.worker_argv, env=env,
                                start_new_session=True)
        if self._detect_t is not None:
            self._metrics["recovery"].labels(event="respawn").observe(
                (time.monotonic() - self._detect_t) * 1e3)
            self._detect_t = None
        self.info("gen %d: spawned worker pid %d (world=%d rank=%d "
                  "coord=%s)", gen, proc.pid, world, rank, coord)
        self._announce("spmd_worker", pid=proc.pid, gen=gen,
                       world=world, rank=rank)
        return proc

    def _kill_worker(self):
        proc = self.worker
        if proc is None or proc.poll() is not None:
            return
        try:
            os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError, OSError):
            try:
                proc.kill()
            except OSError:
                pass
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass

    # -- the loop ----------------------------------------------------------

    def run(self):
        """Supervise until the pod completes (returns 0) or this host
        gives up (crash budget exhausted / rendezvous unreachable:
        returns 1)."""
        client = RendezvousClient(self.rdzv_address, self.member,
                                  dial_budget_s=self.dial_budget_s)
        crashes = 0
        try:
            while True:
                assignment = client.join_wait()
                if assignment is None:
                    return 0  # pod completed while we waited
                gen = assignment["gen"]
                world = assignment["world"]
                rank = assignment["rank"]
                self.generation = gen
                self._metrics["generation"].set(gen)
                self._announce("spmd_gen", gen=gen, world=world,
                               rank=rank)
                coord = None
                if world > 1:
                    if rank == 0:
                        coord = "%s:%d" % (self.coord_host,
                                           _free_port(self.coord_host))
                        client.set_coord(gen, coord)
                    else:
                        coord = client.get_coord_wait(gen)
                        if coord is None:  # superseded while waiting
                            continue
                self.worker = self._spawn_worker(gen, world, rank,
                                                 coord)
                verdict = self._watch(client, gen)
                if verdict == "restart":
                    self._detect_t = time.monotonic()
                    self._kill_worker()
                    self._announce("spmd_restart", gen=gen)
                    continue
                if verdict == "done":
                    return 0
                code = self.worker.returncode
                reply = client.worker_exit(gen, code)
                status = reply.get("status")
                if code == 0:
                    if status == "done":
                        return 0
                    # our worker finished but the pod has not: ride
                    # along until it completes or a late break pulls
                    # us back in (a restored-complete worker then
                    # serves its done state instantly)
                    while status not in ("done", "restart"):
                        time.sleep(self.poll_s)
                        status = client.heartbeat(gen)
                    if status == "done":
                        return 0
                    continue
                if reply.get("stale"):
                    # the generation had ALREADY broken when our
                    # worker aborted its collective — a regroup, not
                    # an own crash; it stays off the crash budget
                    self._detect_t = time.monotonic()
                    self._announce("spmd_restart", gen=gen,
                                   collateral=1)
                    continue
                crashes += 1
                self._detect_t = time.monotonic()
                self.warning("gen %d: worker died rc=%s (crash %d/%d)",
                             gen, code, crashes, self.max_restarts)
                self._announce("spmd_worker_died", gen=gen, code=code,
                               crashes=crashes)
                try:
                    # the supervisor's link in the correlated flight
                    # chain: worker record -> THIS -> the scheduler's
                    # sched_job_failed, all under the job's trace id
                    from veles_tpu.telemetry import flight
                    flight.get_recorder().dump(
                        "elastic_worker_died", gen=gen, rank=rank,
                        code=code, member=self.member,
                        crashes=crashes, trace_id=self.trace_id)
                except Exception:
                    pass  # the black box must never kill recovery
                if crashes > self.max_restarts:
                    self.error("crash budget exhausted — leaving the "
                               "pod")
                    client.leave()
                    return 1
        except (ConnectionError, TimeoutError) as e:
            self.error("rendezvous lost: %s", e)
            return 1
        finally:
            self._kill_worker()
            client.close()

    def _watch(self, client, gen):
        """Poll worker + rendezvous until one of them moves. Returns
        ``"exited"`` (local worker ended), ``"restart"`` (the
        generation broke elsewhere) or ``"done"``. Every beat carries
        this process's metric delta for the rendezvous anchor's
        federated view; encoding failures never break the beat."""
        while True:
            if self.worker.poll() is not None:
                return "exited"
            telemetry = None
            if self._encoder is not None:
                try:
                    telemetry = self._encoder.encode()
                except Exception:
                    telemetry = None
            reply = client.heartbeat_full(gen, telemetry=telemetry)
            if reply.get("resync") and self._encoder is not None:
                self._encoder.mark_resync()
            status = reply.get("status")
            if status == "restart":
                return "restart"
            if status == "done":
                return "done"
            time.sleep(self.poll_s)


# ---------------------------------------------------------------------------
# worker-side harness
# ---------------------------------------------------------------------------


class ElasticContext(object):
    """The membership a supervisor handed this worker process."""

    def __init__(self, generation, world_size, rank, coordinator=None,
                 snapshot_dir=None):
        self.generation = int(generation)
        self.world_size = int(world_size)
        self.rank = int(rank)
        self.coordinator = coordinator
        self.snapshot_dir = snapshot_dir

    def __repr__(self):
        return ("ElasticContext(gen=%d, world=%d, rank=%d, coord=%r)"
                % (self.generation, self.world_size, self.rank,
                   self.coordinator))


def worker_context():
    """The :class:`ElasticContext` from ``VELES_ELASTIC_*`` env, or
    ``None`` when this process is not supervised (plain standalone
    training — every elastic code path degrades to a no-op)."""
    world = env_knob(ENV_WORLD)
    if not world:
        return None
    return ElasticContext(
        generation=env_knob(ENV_GEN, 0),
        world_size=world,
        rank=env_knob(ENV_RANK, 0),
        coordinator=env_knob(ENV_COORD),
        snapshot_dir=env_knob(ENV_SNAPSHOTS))


def init_distributed(ctx):
    """Join this generation's ``jax.distributed`` runtime (no-op at
    world size 1). The dial rides the shared jittered-backoff helper,
    so a worker restarted a beat before its generation's coordinator
    is listening does not lose the race."""
    from veles_tpu.parallel.mesh import init_multihost
    ok = init_multihost(ctx.coordinator, num_processes=ctx.world_size,
                        process_id=ctx.rank)
    metrics = _metrics()
    metrics["generation"].set(ctx.generation)
    metrics["world"].set(ctx.world_size)
    return ok


def _test_die_hook(ctx, trainer):
    spec = env_knob(ENV_TEST_DIE)
    if not spec or ctx is None:
        return
    rank, _, epochs = spec.partition(":")
    if int(rank) == ctx.rank and \
            int(epochs) == len(trainer.decision.epoch_history):
        # deterministic mid-epoch death for the chaos/parity tests:
        # the epoch just computed is NOT yet checkpointed, so the
        # restart must rewind and replay it
        os.kill(os.getpid(), signal.SIGKILL)


def _test_fail_hook(ctx, trainer):
    spec = env_knob(ENV_TEST_FAIL)
    if not spec or ctx is None:
        return
    rank, _, epochs = spec.partition(":")
    if int(rank) == ctx.rank and \
            int(epochs) == len(trainer.decision.epoch_history):
        # the RAISING twin of _test_die_hook: the worker dies through
        # the exception path, so its flight record (carrying the job
        # trace id) exists for the correlation tests to read back
        raise RuntimeError(
            "induced worker failure (%s=%s)" % (ENV_TEST_FAIL, spec))


class _MetricsPusher(object):
    """Rank 0's scheduler rollup feed (ISSUE 19): delta-encode the
    local registry and POST it to the scheduler's loopback control
    endpoint (``VELES_SCHED_METRICS_URL``, set by the scheduler in
    the gang env) every ``VELES_SCHED_METRICS_S`` seconds. Every
    failure is swallowed — the scheduler being down must never stall
    or kill training.

    The feed survives a scheduler RESTART (ISSUE 20): consecutive
    push failures back off with the fleet-wide jittered exponential
    shape (never give up, never hot-spin a refused connection), and
    the first successful push after an outage is a full resync — a
    recovered scheduler has an empty federated view, and waiting for
    its gap-detect ``{"resync": True}`` ack would heal one push later
    than marking the resync ourselves."""

    #: failure backoff bounds: base = one interval (min 0.25 s so a
    #: very fast test interval still decays), cap well under the
    #: scheduler's restart time scale
    BACKOFF_CAP_S = 10.0

    def __init__(self, url, job, interval_s):
        from veles_tpu.telemetry.federation import SnapshotEncoder
        self.url = url
        self.job = job
        self.interval_s = interval_s
        self._encoder = SnapshotEncoder()
        self._failures = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="sched-metrics-push")
        self._thread.start()

    def _push(self):
        import urllib.request
        delta = self._encoder.encode()
        if delta is None:
            return False
        body = json.dumps({"job": self.job,
                           "telemetry": delta}).encode("utf-8")
        req = urllib.request.Request(
            self.url, data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=5.0) as resp:
            reply = json.loads(resp.read().decode("utf-8"))
        if reply.get("resync"):
            self._encoder.mark_resync()
        return True

    def _loop(self):
        from veles_tpu.parallel.retry import backoff_delay
        wait = self.interval_s
        while not self._stop.wait(wait):
            try:
                pushed = self._push()
            except Exception:
                # bounded jittered retry: exponent capped so the wait
                # can't overflow, sleep capped at BACKOFF_CAP_S
                self._failures += 1
                wait = backoff_delay(
                    min(self._failures - 1, 16),
                    base_s=max(self.interval_s, 0.25),
                    cap_s=self.BACKOFF_CAP_S)
            else:
                if pushed and self._failures:
                    # back from an outage: the scheduler may have
                    # restarted with an empty federated view — make
                    # the next delta a full snapshot
                    self._failures = 0
                    self._encoder.mark_resync()
                wait = self.interval_s

    def stop(self):
        self._stop.set()
        try:
            # one final flush so the last epoch's loss reaches the
            # scheduler even when the job exits between intervals
            self._push()
        except Exception:
            pass
        self._thread.join(timeout=5)


def _start_metrics_pusher(ctx):
    """The pusher when this process should feed the scheduler: a
    ``VELES_SCHED_METRICS_URL`` is present and this is the gang's
    rank 0 (or an unsupervised standalone run)."""
    url = env_knob("VELES_SCHED_METRICS_URL")
    if not url or (ctx is not None and ctx.rank != 0):
        return None
    if not env_flag("VELES_FEDERATION", True):
        return None
    interval_s = env_knob("VELES_SCHED_METRICS_S", 0.5, parse=float,
                          on_error="default")
    job = env_knob(ENV_JOB, "")
    try:
        return _MetricsPusher(url, job, interval_s)
    except Exception:
        return None


def save_elastic_checkpoint(trainer, ctx, params, states):
    """Cut one sharded checkpoint generation at a complete step
    boundary: every process writes its own shards, a cross-process
    barrier orders the writes before rank 0's manifest commit."""
    import jax
    from veles_tpu import snapshotter
    records = trainer.checkpoint_records(params, states)
    epoch = snapshotter.wf_epoch(trainer.workflow)
    barrier = None
    if ctx.world_size > 1:
        def barrier():
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(
                "veles-elastic-ckpt-g%d-e%d" % (ctx.generation, epoch))
    return snapshotter.save_snapshot_sharded(
        trainer.workflow, ctx.snapshot_dir, records,
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        tag="_g%d" % ctx.generation, barrier=barrier, link_tag="",
        manifest_extra={"world_size": ctx.world_size,
                        "generation": ctx.generation,
                        # the SOURCE mesh shape, so a restore at a new
                        # world size can log/verify the A->B reshard
                        "mesh_axes": {str(k): int(v) for k, v in
                                      dict(trainer.mesh.shape).items()}
                        if getattr(trainer, "mesh", None) is not None
                        else None})


def run_elastic_training(build_workflow, device=None, mesh=None,
                         trainer_cls=None, trainer_kwargs=None,
                         on_epoch=None, max_epochs=None):
    """Train under the elastic supervisor: restore -> rewind -> train
    with per-epoch sharded checkpoints. Returns the epoch history.

    ``build_workflow()`` must return an INITIALIZED workflow built
    from fixed seeds — on a fresh start every SPMD process derives
    identical initial state from it. On a restart the newest COMPLETE
    checkpoint generation is restored instead (re-assembled and
    re-sharded whatever world size wrote it), the loader rewinds to
    the last complete step boundary, and the PRNG registry restored
    with the snapshot makes the replayed index matrix — and therefore
    its deterministic re-partition over the new membership — identical
    to the lost run's. Without a supervisor (no ``VELES_ELASTIC_*``
    env) this is plain standalone training."""
    import logging
    log = logging.getLogger("elastic")
    ctx = worker_context()
    trace_id = env_knob(ENV_TRACE)
    if trace_id:
        from veles_tpu.telemetry import tracing
        tracing.set_default_trace_id(trace_id)
    if ctx is not None:
        init_distributed(ctx)
    pusher = _start_metrics_pusher(ctx)
    try:
        return _run_elastic_training(
            log, ctx, build_workflow, device=device, mesh=mesh,
            trainer_cls=trainer_cls, trainer_kwargs=trainer_kwargs,
            on_epoch=on_epoch, max_epochs=max_epochs)
    except Exception as e:
        try:
            # the worker's link in the correlated flight chain: its
            # record names the generation/rank AND the job trace id,
            # so an operator can walk worker -> supervisor ->
            # scheduler records of one incident
            from veles_tpu.telemetry import flight
            flight.get_recorder().dump(
                "elastic_worker_failed",
                error="%s: %s" % (type(e).__name__, e),
                generation=ctx.generation if ctx else None,
                rank=ctx.rank if ctx else None,
                job=env_knob(ENV_JOB), trace_id=trace_id)
        except Exception:
            pass
        raise
    finally:
        if pusher is not None:
            pusher.stop()


def _run_elastic_training(log, ctx, build_workflow, device=None,
                          mesh=None, trainer_cls=None,
                          trainer_kwargs=None, on_epoch=None,
                          max_epochs=None):
    snapdir = ctx.snapshot_dir if ctx is not None else None
    workflow = None
    if snapdir:
        from veles_tpu import snapshotter
        t0 = time.perf_counter()
        try:
            workflow, restored_path = snapshotter.restore_latest(snapdir)
        except FileNotFoundError:
            workflow = None
    fresh = workflow is None
    if fresh:
        workflow = build_workflow()
    else:
        if device is None:
            from veles_tpu.backends import Device
            device = Device()
        workflow.initialize(device=device)
        resume_epoch = workflow.decision.prepare_resume()
        _metrics()["recovery"].labels(event="restore").observe(
            (time.perf_counter() - t0) * 1e3)
        if resume_epoch is None:
            log.info("restored run %s is already complete",
                     restored_path)
            return workflow.decision.epoch_history
        workflow.loader.reset_to_epoch_start(resume_epoch)
        log.info("restored %s; resuming from the start of epoch %d "
                 "at world size %d", restored_path, resume_epoch,
                 ctx.world_size)
    if mesh is None:
        # the launcher-SPMD tier's named batch×model mesh (ISSUE 15):
        # an elastic world-size change = this mesh re-built over the
        # surviving devices + reshard-on-restore through pull_params'
        # measured re-placement (parallel/reshard.py)
        from veles_tpu.parallel.gspmd import gspmd_mesh
        mesh = gspmd_mesh()
    if trainer_cls is None:
        from veles_tpu.parallel.gspmd import GSPMDTrainer
        trainer_cls = GSPMDTrainer
    trainer = trainer_cls(workflow, mesh=mesh,
                          **(trainer_kwargs or {}))
    if snapdir:
        def epoch_callback(tr, params, states):
            if on_epoch is not None:
                on_epoch(tr, params, states)
            _test_fail_hook(ctx, tr)
            _test_die_hook(ctx, tr)
            save_elastic_checkpoint(tr, ctx, params, states)

        trainer.epoch_callback = epoch_callback
        initial_state = None
        if fresh:
            # the generation-initial restart point: a death before the
            # first epoch closes must rewind to the seed state, not
            # re-randomize — this checkpoint carries the post-init
            # params and PRNG streams every process agreed on. The
            # pulled state is handed to train() so the model-sized
            # host→device placement happens once, not twice.
            initial_state = trainer.pull_params()
            save_elastic_checkpoint(trainer, ctx, *initial_state)
        return trainer.train(max_epochs=max_epochs,
                             initial_state=initial_state)
    if on_epoch is not None:
        trainer.epoch_callback = on_epoch
    return trainer.train(max_epochs=max_epochs)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _supervise_main(argv):
    import argparse
    worker_argv = None
    if "--" in argv:
        split = argv.index("--")
        worker_argv = argv[split + 1:]
        argv = argv[:split]
    parser = argparse.ArgumentParser(
        prog="veles-elastic supervise",
        description="per-host elastic SPMD supervisor")
    parser.add_argument("--rdzv", required=True,
                        metavar="HOST:PORT",
                        help="rendezvous server address")
    parser.add_argument("--member", default=None,
                        help="stable member token (default host-pid)")
    parser.add_argument("--snapshots", default=None, metavar="DIR",
                        help="sharded checkpoint directory (shared fs)")
    parser.add_argument("--max-restarts", type=int, default=3,
                        help="own-worker crash budget (regroup "
                             "restarts are free)")
    parser.add_argument("--worker-env", action="append", default=[],
                        metavar="K=V", help="extra worker env "
                        "(repeatable)")
    parser.add_argument("--coord-host", default="127.0.0.1",
                        help="address rank 0 publishes for "
                             "jax.distributed")
    parser.add_argument("--poll-s", type=float, default=0.2)
    args = parser.parse_args(argv)
    if not worker_argv:
        parser.error("worker command required after `--`")
    env = {}
    for item in args.worker_env:
        key, _, value = item.partition("=")
        env[key] = value
    supervisor = ElasticSupervisor(
        args.rdzv, worker_argv, snapshot_dir=args.snapshots,
        member=args.member, max_restarts=args.max_restarts,
        worker_env=env, poll_s=args.poll_s,
        coord_host=args.coord_host, announce=True)
    return supervisor.run()


def _rendezvous_main(argv):
    import argparse
    parser = argparse.ArgumentParser(
        prog="veles-elastic rendezvous",
        description="elastic SPMD rendezvous anchor")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--min-workers", type=int, default=1)
    parser.add_argument("--expected", type=int, default=None)
    parser.add_argument("--settle-s", type=float, default=1.0)
    parser.add_argument("--hb-timeout-s", type=float, default=5.0)
    parser.add_argument("--absorb-joins", action="store_true")
    args = parser.parse_args(argv)
    server = RendezvousServer(
        port=args.port, host=args.host, min_workers=args.min_workers,
        expected=args.expected, settle_s=args.settle_s,
        heartbeat_timeout_s=args.hb_timeout_s,
        absorb_joins=args.absorb_joins).start()
    print("RENDEZVOUS %s:%d" % server.address, flush=True)
    try:
        while server.phase != "done":
            time.sleep(0.5)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
    return 0


class _DemoProvider(object):
    """Deterministic synthetic digits for the demo worker. A
    module-level class (not a closure): the loader pickles it into
    every checkpoint."""

    def __init__(self, samples, valid):
        self.samples = samples
        self.valid = valid

    def __call__(self):
        import numpy
        rng = numpy.random.RandomState(5)

        def mk(n):
            return (rng.rand(n, 8, 8).astype(numpy.float32),
                    rng.randint(0, 10, n).astype(numpy.int32))

        tx, ty = mk(self.samples)
        vx, vy = mk(self.valid)
        return tx, ty, vx, vy


def _worker_demo_main(argv):
    """The loopback demo worker: a tiny seeded MnistWorkflow driven
    through :func:`run_elastic_training` — tests and the chaos
    harness's SPMD legs both use it (with a supervisor), and the loss
    parity baselines run it bare (without one)."""
    import argparse
    parser = argparse.ArgumentParser(prog="veles-elastic worker-demo")
    parser.add_argument("--out", required=True,
                        help="write the per-epoch validation curve "
                             "here (JSON)")
    parser.add_argument("--samples", type=int, default=640)
    parser.add_argument("--valid", type=int, default=128)
    parser.add_argument("--mb", type=int, default=64)
    parser.add_argument("--layers", type=int, default=16)
    parser.add_argument("--epochs", type=int, default=3)
    parser.add_argument("--lr", type=float, default=0.08)
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--epoch-sleep", type=float, default=0.0,
                        help="sleep per epoch boundary (gives chaos "
                             "legs a mid-run window to kill into)")
    args = parser.parse_args(argv)
    # CRITICAL ordering: nothing may initialize a jax backend before
    # run_elastic_training has called jax.distributed.initialize —
    # so no Device construction or devices() query happens here, only
    # config. The supervisor already put the backend choice in env.
    os.environ.setdefault("VELES_TPU_BACKEND", "cpu")

    from veles_tpu import prng
    from veles_tpu.backends import Device
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.mnist import MnistWorkflow

    def build():
        prng.get().seed(args.seed)
        prng.get("loader").seed(args.seed + 1)
        wf = MnistWorkflow(DummyLauncher(),
                           provider=_DemoProvider(args.samples,
                                                  args.valid),
                           layers=(args.layers,),
                           minibatch_size=args.mb,
                           learning_rate=args.lr,
                           max_epochs=args.epochs)
        wf.initialize(device=Device(backend="cpu"))
        return wf

    on_epoch = None
    if args.epoch_sleep:
        def on_epoch(trainer, params, states):
            time.sleep(args.epoch_sleep)

    history = run_elastic_training(build, on_epoch=on_epoch)
    curve = [e["validation"]["normalized"] for e in history]
    tmp = args.out + ".tmp"
    with open(tmp, "w") as fout:
        json.dump(curve, fout)
    os.replace(tmp, args.out)
    print("worker-demo done: %s" % curve, flush=True)
    return 0


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    cmd, rest = argv[0], argv[1:]
    if cmd == "supervise":
        return _supervise_main(rest)
    if cmd == "rendezvous":
        return _rendezvous_main(rest)
    if cmd == "worker-demo":
        return _worker_demo_main(rest)
    print("unknown command %r (supervise | rendezvous | worker-demo)"
          % cmd, file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
