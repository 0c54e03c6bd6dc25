#!/usr/bin/env python3
"""PR 36, a record: ways to bring the resident data set from the
loader's device to the host as interleaved shards, on one chip, by
their seconds and by what they cost the device.

    chiprun --chips 1 -- python3 scripts/pr36_slice_peak.py

A data set of the four-chip cell's staged shape (17,024 x 16 x 10,112
bf16, 5.51 GB) on the device. (1) a whole shard a strided slice on
the device, ``numpy.asarray(a[c::4])`` (first try: 9.4 s, and 2.75 GB
of the device: refused by ``peak_hbm_mb``); (2) the same in chunks of
rows; (3) contiguous chunks to the host, dealt to the four shards'
buffers there; (4) the whole to the host and dealt there (the
parent's transfer plus a copy). Each prints its seconds and the
device's ``peak_bytes_in_use`` after it (a process's peak only grows:
the order runs from the least to the most).
"""

import time

import jax
import jax.numpy as jnp
import numpy

N = 4


def peak(device):
    return device.memory_stats()["peak_bytes_in_use"] / 1e6


def deal(shards, chunk, row0):
    """Rows ``row0 + i`` of the data set, ``chunk[i]``, into their
    shards' buffers (as 16-bit words: numpy copies a bfloat16 array
    element by element)."""
    words = chunk.view(numpy.uint16)
    for c in range(N):
        first = (c - row0) % N
        mine = words[first::N]
        at = (row0 + first) // N
        shards[c][at:at + len(mine)] = mine


def main():
    device = jax.devices()[0]
    print(device.platform, device.device_kind)
    data = jnp.zeros((17024, 16, 10112), jnp.bfloat16)
    data.block_until_ready()
    n, tail = data.shape[0], data.shape[1:]
    rows = n // N
    print("resident: peak %.1f MB" % peak(device))

    for chunk_rows in (2048, 512):
        t0 = time.perf_counter()
        shards = [numpy.empty((rows,) + tail, numpy.uint16)
                  for _ in range(N)]
        pending = None
        for row0 in list(range(0, n, chunk_rows)) + [None]:
            piece = None
            if row0 is not None:
                piece = data[row0:row0 + chunk_rows]
                piece.copy_to_host_async()
            if pending is not None:
                deal(shards, numpy.asarray(pending[1]), pending[0])
            pending = (row0, piece)
        print("(3) contiguous chunks of %d rows, dealt on the host: "
              "%.2f s; peak %.1f MB" % (
                  chunk_rows, time.perf_counter() - t0, peak(device)))
        del shards

    t0 = time.perf_counter()
    for c in range(N):
        parts = [numpy.asarray(data[c + N * k:c + N * (k + 512):N]).view(
            numpy.uint16) for k in range(0, rows, 512)]
        shard = numpy.concatenate(parts)
    print("(2) strided chunks of 512 rows on the device: %.2f s; "
          "peak %.1f MB" % (time.perf_counter() - t0, peak(device)))
    del parts, shard

    t0 = time.perf_counter()
    whole = numpy.asarray(data)
    t1 = time.perf_counter()
    shards = [numpy.empty((rows,) + tail, numpy.uint16) for _ in range(N)]
    deal(shards, whole, 0)
    print("(4) the whole to the host %.2f s, dealt there %.2f s; peak "
          "%.1f MB" % (t1 - t0, time.perf_counter() - t1, peak(device)))
    t0 = time.perf_counter()
    jax.device_put(shards[0].view(jnp.bfloat16), device).block_until_ready()
    print("a shard back in %.2f s" % (time.perf_counter() - t0))


if __name__ == "__main__":
    main()
