"""Device mesh construction and multi-host initialization.

Axis naming convention (used across the package):

* ``data``  — data parallelism (gradient psum rides ICI),
* ``model`` — tensor parallelism (activation collectives),
* ``pipe``  — pipeline stages (ppermute),
* ``seq``   — sequence/context parallelism (ring attention).

``build_mesh`` lays axes out so the fastest-varying axis maps to
physically adjacent devices (JAX mesh_utils handles the torus topology
when available), which keeps ``model``/``seq`` collectives on short ICI
paths and pushes ``data`` onto the remaining links — the scaling-book
recipe.
"""

import jax
import numpy
from jax.experimental import mesh_utils


def local_device_count(platform=None):
    try:
        return len(jax.devices(platform) if platform else jax.devices())
    except RuntimeError:
        return 0


def build_mesh(axes=None, devices=None):
    """Build a ``jax.sharding.Mesh``.

    ``axes``: ordered dict/list of (name, size); sizes must multiply to
    the device count, a single -1 size is inferred. Default: pure data
    parallelism over all visible devices.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if axes is None:
        axes = {"data": n}
    if isinstance(axes, dict):
        axes = list(axes.items())
    names = [a[0] for a in axes]
    sizes = [a[1] for a in axes]
    if sizes.count(-1) > 1:
        raise ValueError("at most one inferred (-1) axis")
    if -1 in sizes:
        known = int(numpy.prod([s for s in sizes if s != -1]))
        if n % known:
            raise ValueError("cannot infer axis: %d %% %d" % (n, known))
        sizes[sizes.index(-1)] = n // known
    if int(numpy.prod(sizes)) != n:
        raise ValueError("mesh %r needs %d devices, have %d" %
                         (dict(zip(names, sizes)),
                          int(numpy.prod(sizes)), n))
    dev_array = mesh_utils.create_device_mesh(tuple(sizes),
                                              devices=devices)
    return jax.sharding.Mesh(dev_array, tuple(names))


def put_global(host_array, sharding):
    """``device_put`` that also works under multi-controller SPMD.

    In a multi-host runtime a plain ``device_put`` onto a sharding
    whose devices span processes is rejected (non-addressable);
    ``make_array_from_callback`` lets every process contribute just its
    addressable shards, sliced from the same full host array (every
    controller holds identical data — same seeds, same loader)."""
    if jax.process_count() == 1:
        return jax.device_put(host_array, sharding)
    host_array = numpy.asarray(host_array)
    return jax.make_array_from_callback(
        host_array.shape, sharding, lambda idx: host_array[idx])


def named_sharding(mesh, *spec):
    """Shorthand for NamedSharding(mesh, PartitionSpec(*spec))."""
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*spec))


def replicated(mesh):
    return named_sharding(mesh)


#: the one-shot jax.distributed spec this process initialized with —
#: the runtime cannot re-initialize in-process, so the guard turns a
#: same-spec double init into a no-op and a different-spec one into a
#: clear error (the elastic supervisor restarts the PROCESS to change
#: membership; see :mod:`veles_tpu.parallel.elastic`)
_MULTIHOST = {"spec": None}


def _runtime_initialized():
    """Best-effort: was jax.distributed initialized behind our back?"""
    try:
        from jax._src import distributed as _dist
        state = _dist.global_state
        return (getattr(state, "coordinator_address", None) is not None
                or getattr(state, "client", None) is not None)
    except Exception:
        return False


def multihost_initialized():
    """True when this process is part of a live multi-host runtime."""
    return _MULTIHOST["spec"] is not None or _runtime_initialized()


def init_multihost(coordinator_address=None, num_processes=None,
                   process_id=None, retry_budget_s=None):
    """Initialize jax.distributed for multi-host pods (DCN).

    The reference's SSH slave spawning (``launcher.py:808-842``) maps to
    the cluster scheduler starting one process per host; this call wires
    them into one JAX runtime. No-op when standalone.

    Idempotent (ISSUE 13 satellite): a second call with the SAME
    (address, world, rank) spec returns True without touching the
    runtime; a DIFFERENT spec raises — jax.distributed cannot re-form
    a membership in-process, which is exactly why the elastic
    supervisor owns the worker lifecycle. The coordinator dial runs
    through the shared jittered-backoff retry helper
    (:func:`veles_tpu.parallel.retry.retry_with_backoff`,
    ``retry_budget_s`` / ``VELES_MESH_INIT_RETRY_S``, default 60 s) so
    a restarting worker cannot lose the race against a rendezvous
    window where the generation's coordinator is not listening yet.
    """
    import logging
    log = logging.getLogger("mesh")
    if num_processes in (None, 1):
        return False
    # None stays None: jax.distributed auto-detects coordinator and
    # process_id on TPU pods/GKE, and that invocation must keep working
    spec = (coordinator_address, int(num_processes),
            None if process_id is None else int(process_id))
    if _MULTIHOST["spec"] is not None:
        if _MULTIHOST["spec"] == spec:
            log.debug("init_multihost: already initialized as %r", spec)
            return True
        raise RuntimeError(
            "jax.distributed is already initialized as %r; re-forming "
            "the mesh as %r needs a fresh process (the elastic "
            "supervisor restarts workers for exactly this reason) or "
            "an explicit shutdown_multihost() first"
            % (_MULTIHOST["spec"], spec))
    if _runtime_initialized():
        # initialized outside this helper (user code / test harness):
        # trust it rather than crash a running pod
        log.warning("init_multihost: jax.distributed was initialized "
                    "outside init_multihost; leaving the runtime as-is")
        _MULTIHOST["spec"] = spec
        return True
    # the CPU backend runs multiprocess computations only through the
    # gloo collectives plugin; without this the post-init computation
    # dies with "Multiprocess computations aren't implemented on the
    # CPU backend" (the loopback tests + any CPU-pod rehearsal). Set
    # unconditionally: the flag only governs the CPU backend's
    # collectives, so it is inert on TPU deployments — and sniffing
    # JAX_PLATFORMS here would miss the default CPU-only host where
    # neither the env var nor jax_platforms is set.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    if retry_budget_s is None:
        from veles_tpu.envknob import env_knob
        retry_budget_s = env_knob("VELES_MESH_INIT_RETRY_S", 60.0,
                                  parse=float)

    def non_retryable(e):
        # non-transport failures can never succeed on retry: an
        # already-initialized runtime, or a backend that some earlier
        # code initialized (computations before distributed init)
        return ("already initialized" in str(e) or
                "before any JAX computations" in str(e))

    def attempt():
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id)
        except Exception as e:
            # a half-failed init (dial timed out mid-handshake) can
            # leave partial global state behind; reset it so the next
            # attempt is a clean first init. NEVER on the non-retryable
            # failures: "already initialized" means a LIVE runtime this
            # helper's best-effort probe missed — shutting it down
            # would crash every collective of a running pod.
            if not non_retryable(e):
                try:
                    jax.distributed.shutdown()
                except Exception:
                    pass
            raise

    from veles_tpu.parallel.retry import retry_with_backoff
    try:
        retry_with_backoff(
            attempt, retry_budget_s,
            retry_on=(RuntimeError, OSError, ConnectionError,
                      TimeoutError),
            give_up=non_retryable,
            describe="could not join the jax.distributed coordinator "
                     "at %s (world=%s rank=%s)" % spec)
    except ConnectionError as e:
        # a give-up failure is NOT a connectivity problem: surface the
        # original error ("already initialized", "computations before
        # init") instead of a ConnectionError blaming the network
        cause = e.__cause__
        if cause is not None and non_retryable(cause):
            raise cause
        raise
    _MULTIHOST["spec"] = spec
    return True


def shutdown_multihost():
    """Tear down the multi-host runtime this process initialized.

    Returns True when a runtime was actually shut down. Safe to call
    unconditionally (no-op when standalone); after it, a FRESH
    ``init_multihost`` spec is accepted again — but note that live
    backends/devices from the old runtime stay unusable, which is why
    production re-formation goes through a process restart (the
    elastic supervisor), not this helper. This exists for clean
    teardown at worker exit and for tests."""
    import logging
    if _MULTIHOST["spec"] is None and not _runtime_initialized():
        return False
    try:
        jax.distributed.shutdown()
    except Exception as e:
        logging.getLogger("mesh").warning(
            "jax.distributed.shutdown failed: %s", e)
    _MULTIHOST["spec"] = None
    return True
