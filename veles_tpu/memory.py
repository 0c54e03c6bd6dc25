"""Array: numpy-semantics buffer with an HBM-resident device half.

Re-designs ``veles/memory.py:110-511``. The reference's Array pairs a
host numpy array with an OpenCL/CUDA buffer under an explicit coherence
protocol (``map_read``/``map_write``/``map_invalidate``/``unmap``).
That protocol survives here as the *host-sync discipline* over a
``jax.Array``:

* ``map_read()``  — make the host view valid (device → host if dirty);
* ``map_write()`` — host will read+write; device copy becomes stale;
* ``map_invalidate()`` — host will overwrite everything; skip the
  device→host copy (pure invalidation);
* ``unmap()``     — push host changes back to device (host → HBM).

Units written against this contract run unchanged on tpu/cpu/numpy.
The step compiler (veles_tpu.train) bypasses the protocol entirely by
keeping weights device-resident across steps — ``devmem`` hands it the
raw ``jax.Array`` and ``assign_devmem`` accepts the updated one back,
which is how donation/aliasing avoids host round-trips in the hot loop.

Global memory accounting mirrors the reference's Watcher
(``veles/memory.py:56-107``).
"""

import threading

import numpy

# coherence states
CLEAN = 0        # host == device
HOST_DIRTY = 1   # host modified; device stale
DEV_DIRTY = 2    # device modified; host stale


class Watcher(object):
    """Process-wide device-memory accounting (``memory.py:56-107``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.total = 0
        self.peak = 0
        self.count = 0

    def add(self, nbytes):
        with self._lock:
            self.total += nbytes
            self.count += 1
            self.peak = max(self.peak, self.total)

    def remove(self, nbytes):
        with self._lock:
            self.total -= nbytes
            self.count -= 1

    def report(self):
        return {"bytes_in_use": self.total, "peak_bytes": self.peak,
                "arrays": self.count}


watcher = Watcher()


class Array(object):
    """Host numpy array + lazily attached device buffer."""

    def __init__(self, data=None, shape=None, dtype=None):
        self._lock_ = threading.RLock()
        self.device = None
        self._devmem_ = None
        self._state_ = CLEAN
        self._accounted_ = 0
        if data is not None:
            self.mem = numpy.asarray(data, dtype=dtype)
        elif shape is not None:
            self.mem = numpy.zeros(shape, dtype=dtype or numpy.float32)
        else:
            self.mem = None

    # -- basic protocol ----------------------------------------------------

    @property
    def shape(self):
        return self.mem.shape if self.mem is not None else None

    @property
    def dtype(self):
        return self.mem.dtype if self.mem is not None else None

    @property
    def size(self):
        return self.mem.size if self.mem is not None else 0

    @property
    def nbytes(self):
        return self.mem.nbytes if self.mem is not None else 0

    def __bool__(self):
        return self.mem is not None and self.mem.size > 0

    def __len__(self):
        return len(self.mem) if self.mem is not None else 0

    def __getitem__(self, index):
        self.map_read()
        return self.mem[index]

    def __setitem__(self, index, value):
        """Element write. ``map_write`` syncs coherence state under
        the lock; the element store itself is not thread-safe by
        design — the lock protects the coherence protocol, not
        concurrent host mutation of the same buffer."""
        self.map_write()
        self.mem[index] = value

    def reset(self, new_mem=None):
        """Replace the host buffer; device copy is dropped."""
        with self._lock_:
            self._drop_devmem()
            self.mem = new_mem
            self._state_ = HOST_DIRTY if new_mem is not None else CLEAN

    # -- device attachment -------------------------------------------------

    def initialize(self, device):
        """Attach to a device; upload happens lazily on first devmem use."""
        with self._lock_:
            if device is not None and not device.exists:
                device = None  # numpy pseudo-device: host only
            if device is not self.device:
                self.map_read()      # preserve newest data on the host
                self._drop_devmem()  # release old device buffer+accounting
            self.device = device
            if self.mem is not None and device is not None:
                self._state_ = HOST_DIRTY
        return self

    @property
    def devmem(self):
        """The device-resident ``jax.Array`` (uploading if stale)."""
        with self._lock_:
            if self.device is None:
                return self.mem
            if self._devmem_ is None or self._state_ == HOST_DIRTY:
                self._upload()
            return self._devmem_

    def assign_devmem(self, new_devmem):
        """Accept an updated device array (output of a jitted step)."""
        with self._lock_:
            if self.device is None:
                # host-only array: the "device" result is a host value.
                # COPY, never view: ``new_devmem`` is typically a
                # jax.Array the next donating segment call will delete
                # under any zero-copy view (backends.JaxDevice.get has
                # the full story) — ``mem`` must own its bytes.
                self.mem = numpy.array(new_devmem)
                self._state_ = CLEAN
                return
            self._devmem_ = new_devmem
            self._state_ = DEV_DIRTY
            # account buffers that arrive device-side too (forward
            # outputs, err_inputs): without this the Watcher's
            # in-use/peak report only saw host-uploaded weights
            old = self._accounted_
            new = getattr(new_devmem, "nbytes", 0)
            if old != new:
                if old:
                    watcher.remove(old)
                if new:
                    watcher.add(new)
                self._accounted_ = new

    def _upload(self):
        """Host -> device copy + accounting. Caller holds
        ``self._lock_``."""
        old = self._accounted_
        self._devmem_ = self.device.put(self.mem)
        self._accounted_ = self.nbytes
        if old != self._accounted_:
            if old:
                watcher.remove(old)
            watcher.add(self._accounted_)
        self._state_ = CLEAN

    def _drop_devmem(self):
        """Release the device buffer + accounting. Caller holds
        ``self._lock_``."""
        if self._accounted_:
            watcher.remove(self._accounted_)
            self._accounted_ = 0
        self._devmem_ = None

    # -- coherence protocol ------------------------------------------------

    def map_read(self):
        """Make the host view valid."""
        with self._lock_:
            if self._state_ == DEV_DIRTY and self._devmem_ is not None:
                self.mem = self.device.get(self._devmem_)
                self._state_ = CLEAN
        return self.mem

    def map_write(self):
        """Host will read-modify-write: sync down, mark device stale."""
        with self._lock_:
            if self._state_ == DEV_DIRTY and self._devmem_ is not None:
                self.mem = self.device.get(self._devmem_)
            self._ensure_writable()
            self._state_ = HOST_DIRTY
        return self.mem

    def map_invalidate(self):
        """Host will overwrite entirely: skip the device→host copy."""
        with self._lock_:
            if self.mem is not None and not self.mem.flags.writeable:
                # caller overwrites everything: a fresh buffer suffices,
                # no need to copy bytes that are about to be clobbered
                self.mem = numpy.empty_like(self.mem)
            self._state_ = HOST_DIRTY
        return self.mem

    def _ensure_writable(self):
        """Caller holds ``self._lock_``."""
        # device→host views (numpy.asarray of a jax.Array) are read-only;
        # a host write mapping must always hand out a mutable buffer
        if self.mem is not None and not self.mem.flags.writeable:
            self.mem = numpy.array(self.mem)

    def release_devmem(self):
        """Drop the device buffer (syncing host first if device-dirty).

        The next ``devmem`` access re-uploads, so this is always safe;
        use when a staged copy supersedes this Array's device residence
        (e.g. dp row-sharding keeps only 1/N per device — holding the
        original full copy too would defeat the sharding's HBM saving).
        """
        with self._lock_:
            self.map_read()  # sync host if device-dirty (RLock reenters)
            self._drop_devmem()

    def block_until_ready(self):
        """Wait until the device half, if there is one, is computed."""
        with self._lock_:
            devmem = self._devmem_
        if hasattr(devmem, "block_until_ready"):
            devmem.block_until_ready()

    def unmap(self):
        """Flush host writes to the device (upload if dirty)."""
        with self._lock_:
            if self.device is not None and self._state_ == HOST_DIRTY \
                    and self.mem is not None:
                self._upload()

    # -- pickling: device half is transient -------------------------------

    def __getstate__(self):
        self.map_read()
        return {"mem": self.mem}

    def __setstate__(self, state):
        self._lock_ = threading.RLock()
        self.device = None
        self._devmem_ = None
        self._state_ = CLEAN
        self._accounted_ = 0
        self.mem = state["mem"]

    def __repr__(self):
        return "<Array %s %s on %s>" % (
            self.shape, self.dtype,
            self.device.backend_name if self.device else "host")


def assert_addr(a, b):
    """Assert two Arrays share the same host buffer (reference helper)."""
    if a.mem is not b.mem:
        raise ValueError("arrays do not share memory")


def roundup(value, multiple):
    """Round ``value`` up to a multiple (``veles/memory.py`` helper)."""
    remainder = value % multiple
    return value if remainder == 0 else value + multiple - remainder
