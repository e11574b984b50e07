"""Chunked, overlapped host->device upload for ingest-sized arrays.

Two problems with one ``jax.device_put`` of a multi-GB training matrix:
(1) its host-side staging copy (densify / dtype-cast) is as large as the
matrix, and (2) the staging of chunk k+1 could be running while chunk k is
on the wire, but a monolithic put serializes them.

``chunked_device_put`` splits on the leading axis and keeps at most
``depth`` transfers in flight (double-buffered by default): device_put is
async under JAX, so while chunk k transfers, the python loop is already
slicing/casting chunk k+1. The result — ``jnp.concatenate`` of the chunks —
is value-identical to a whole-array put.

``OverlappedUploader`` is the push-style variant for producers that emit
chunks over time (the multi-process decode pipeline: workers hand the
parent shard columns while later shards are still decoding —
data/parallel_ingest.py's ``column_consumer`` hook plugs straight into
``submit``).

``HostPrefetcher`` is the host-side dual of ``InFlightWindow``: where the
window bounds async DEVICE work already dispatched, the prefetcher bounds
host PRODUCTION of future work — a background thread runs an iterator
(e.g. block decode + featureize of batch k+1, data/block_stream.py) while
the consumer's loop body (device dispatch of batch k) executes, holding at
most ``depth`` finished items. Chaining the two gives the three-stage
decode → H2D → dispatch pipeline of streamed scoring.
"""

from __future__ import annotations

import queue
import threading
from collections import deque

import numpy as np

from photon_ml_tpu.telemetry import span

# Default per-transfer size: bounds the host staging copy while staying
# big enough that per-put dispatch overhead is negligible.
DEFAULT_CHUNK_BYTES = 128 << 20


def _rows_per_chunk(nbytes_per_row: int, chunk_bytes: int) -> int:
    return max(1, chunk_bytes // max(1, nbytes_per_row))


def chunked_device_put(x, dtype=None, device=None,
                       chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                       depth: int = 2):
    """Upload ``x`` (numpy or scipy-sparse-row-sliceable) in leading-axis
    chunks, ``depth`` transfers in flight; returns one device array equal
    to ``jnp.asarray(x, dtype)``.

    Sparse input is densified PER CHUNK (``.toarray()`` on the row slice),
    so the full dense host copy never materializes — the peak host
    footprint is the CSR plus ``depth`` chunks.

    Device-side peak is transiently ~2x the array during the final
    ``jnp.concatenate`` (chunks + destination). A donated
    dynamic-update-slice into a preallocated buffer would cap it at ~1x
    on TPU, but donation is ignored on CPU, where every functional
    update would copy the full buffer per chunk — deliberately not done
    until a workload actually hits the 2x ceiling.

    The whole call reports as one ``h2d`` telemetry span: device_put is
    async, so the span measures host staging + enqueue plus the
    window-bounding ``block_until_ready`` waits — the H2D stage of the
    decode -> H2D -> dispatch attribution, charged where the host
    actually spends the time.
    """
    with span("h2d"):
        return _chunked_device_put(x, dtype, device, chunk_bytes, depth)


def _chunked_device_put(x, dtype, device, chunk_bytes, depth):
    import jax
    import jax.numpy as jnp
    import scipy.sparse as sp

    sparse = sp.issparse(x)
    if sparse:
        x = x.tocsr()  # coo/dia/... aren't row-sliceable; csr is (no-op
        # for the csr matrices the ingest paths hand in)
    else:
        x = np.asarray(x)
    n = x.shape[0] if x.ndim else 0
    # Size chunks by the WIDER of source and target dtypes: the transfer
    # happens at the target width, so casting int8 -> f32 must not turn a
    # 128 MB host chunk into a 512 MB wire transfer.
    itemsize = np.dtype(np.float64).itemsize if sparse else x.dtype.itemsize
    if dtype is not None:
        try:
            itemsize = max(itemsize, np.dtype(dtype).itemsize)
        except TypeError:
            pass  # exotic dtype numpy can't size; host itemsize stands
    elems_per_row = int(np.prod(x.shape[1:])) if x.ndim > 1 else 1
    row_bytes = elems_per_row * itemsize
    total_bytes = n * row_bytes

    def put(chunk):
        if sparse:
            chunk = chunk.toarray()
        a = jnp.asarray(chunk, dtype)
        return a if device is None else jax.device_put(a, device)

    if x.ndim == 0 or n <= 1 or total_bytes <= chunk_bytes:
        return put(x)

    rows = _rows_per_chunk(row_bytes, chunk_bytes)
    parts = []
    in_flight: deque = deque()
    for start in range(0, n, rows):
        a = put(x[start:start + rows])
        parts.append(a)
        in_flight.append(a)
        if len(in_flight) >= depth:
            # Bound the in-flight window: wait for the OLDEST transfer so
            # chunk k+depth's host staging overlaps chunks k+1..k+depth-1
            # on the wire.
            jax.block_until_ready(in_flight.popleft())
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


class InFlightWindow:
    """Bounded window of in-flight async device work.

    The shared scheduling primitive behind every overlapped host<->device
    pipeline here: ``push(item, ready=...)`` enqueues a unit of async work
    and — once ``depth`` units are in flight — BLOCKS on the oldest one
    and returns its item (else None). The caller's loop body between
    pushes (slicing/casting the next chunk, featureizing the next request
    batch) thereby overlaps the transfers/dispatches already on the wire.
    Used by ``OverlappedUploader`` (decode ‖ H2D) and the serving
    engine's featureize -> H2D -> score pipeline (host work for batch k+1
    ‖ device dispatch of batch k).
    """

    def __init__(self, depth: int = 2):
        self._depth = max(1, depth)
        self._q: deque = deque()

    def __len__(self) -> int:
        return len(self._q)

    def push(self, item, ready=None):
        """Enqueue ``item``; block on/return the oldest item when the
        window is full, else None. ``ready`` (default: item itself) is
        what jax.block_until_ready waits on — pass the device arrays when
        item is a richer record."""
        import jax

        self._q.append((item, item if ready is None else ready))
        if len(self._q) >= self._depth:
            old_item, old_ready = self._q.popleft()
            # ``device_wait``: the ONE place device execution meets the
            # host — this block_until_ready already existed to bound the
            # window, so a span here attributes device-bound time
            # without adding a sync (docs/OBSERVABILITY.md span rules).
            with span("device_wait"):
                jax.block_until_ready(old_ready)
            return old_item
        return None

    def drain(self):
        """Yield the remaining items oldest-first, blocking on each."""
        import jax

        while self._q:
            item, ready = self._q.popleft()
            with span("device_wait"):
                jax.block_until_ready(ready)
            yield item


class HostPrefetcher:
    """Bounded background-thread prefetch of an iterator.

    ``iter(HostPrefetcher(src, depth))`` yields ``src``'s items in order
    while a daemon thread keeps producing ahead, blocking once ``depth``
    finished items wait unconsumed — so the producer can never run the
    host out of memory. Items RESIDENT at any instant are bounded by
    ``depth`` (queued) + 1 (in the producer's hand, blocked on a full
    queue) + 1 (held by the consumer) = ``depth + 2``; ``peak_resident``
    records the high-water mark of the first two terms plus the
    consumer's (so its bound is exactly ``depth + 2``).

    Producer exceptions re-raise in the consumer at the position they
    occurred; abandoning the iterator mid-stream (``close()``/GC of the
    generator) stops the producer promptly via a poll-stop flag rather
    than leaving it blocked on a full queue forever.
    """

    _POLL_S = 0.05

    def __init__(self, src, depth: int = 2):
        self._src = src
        self._depth = max(1, depth)
        self.peak_resident = 0

    def __iter__(self):
        q: "queue.Queue[tuple]" = queue.Queue(maxsize=self._depth)
        stop = threading.Event()
        lock = threading.Lock()
        in_flight = [0]  # produced, not yet handed to the consumer

        def put(msg) -> bool:
            while not stop.is_set():
                try:
                    q.put(msg, timeout=self._POLL_S)
                    return True
                except queue.Full:
                    continue
            return False

        def produce():
            try:
                for item in self._src:
                    with lock:
                        in_flight[0] += 1
                        # +1: the item the consumer currently holds.
                        self.peak_resident = max(self.peak_resident,
                                                 in_flight[0] + 1)
                    if not put(("item", item)):
                        return
                put(("done", None))
            except BaseException as e:  # noqa: BLE001 — re-raised below
                put(("err", e))

        t = threading.Thread(target=produce, daemon=True,
                             name="host-prefetch")
        t.start()
        try:
            while True:
                # ``prefetch_wait``: consumer blocked on the producer —
                # the feeder-bound share of the stall attribution (its
                # dual, device-bound, is InFlightWindow's device_wait).
                with span("prefetch_wait"):
                    kind, val = q.get()
                if kind == "done":
                    break
                if kind == "err":
                    raise val
                with lock:
                    in_flight[0] -= 1
                yield val
        finally:
            stop.set()


class OverlappedUploader:
    """Push-style double-buffered feeder: ``submit(host_chunk)`` starts an
    async device transfer and returns immediately (unless ``depth``
    transfers are already in flight); ``collect()`` waits and concatenates.

    The producer (e.g. the parallel-decode assembly loop) keeps decoding
    while submitted chunks ride the wire — H2D of chunk k overlaps decode
    of chunk k+1, which is the whole point.

    Chunks are copied at submit time (``jnp.asarray``), so callers may hand
    in views over transient buffers (shared-memory segments included).
    """

    def __init__(self, dtype=None, device=None, depth: int = 2,
                 chunk_bytes: int = DEFAULT_CHUNK_BYTES):
        self._dtype = dtype
        self._device = device
        self._depth = max(1, depth)
        self._chunk_bytes = chunk_bytes
        self._parts: list = []
        self._window = InFlightWindow(depth)

    def submit(self, chunk) -> None:
        a = chunked_device_put(chunk, self._dtype, self._device,
                               self._chunk_bytes, self._depth)
        self._parts.append(a)
        self._window.push(a)

    def collect(self):
        """Device concatenation of everything submitted (None if empty)."""
        import jax.numpy as jnp

        if not self._parts:
            return None
        out = (self._parts[0] if len(self._parts) == 1
               else jnp.concatenate(self._parts, axis=0))
        self._parts = []
        self._window = InFlightWindow(self._depth)
        return out
