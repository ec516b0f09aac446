"""Training input pipeline: sequence packing + host-side prefetch.

Tokenized documents are packed into fixed ``[batch, seq]`` buffers with
*segment ids* (1-based per document, 0 = padding) and *restart
positions* (rope positions reset per document), so short documents
never waste MXU cycles on padding and packed documents cannot attend
across boundaries (ops.attention masks on segment ids).

The packing hot loop is native C++ (native/packer.cc, loaded via
ctypes, built on demand with g++ — nothing prebuilt is committed) with
a pure-numpy fallback — same split the reference makes for its
performance-critical host paths (reference: SURVEY.md §0, third-party
native data movers). The fallback is never silent: a failed build is
one typed ``input_pipeline.native_packer_unavailable`` event naming
the compiler's error, and ``skytpu_packed_batches_total{packer}``
says which packer produced every batch.

``prefetch`` overlaps host packing with device compute via a
double-buffered background thread (the standard TPU input recipe).
"""

from __future__ import annotations

import ctypes
import os
import queue
import subprocess
import threading
from typing import Dict, Iterable, Iterator, List, Optional, Sequence

import numpy as np

from skypilot_tpu.observability import metrics, tracing

PACKED_BATCHES = metrics.counter(
    "skytpu_packed_batches_total",
    "Packed train batches produced, by the packer that ran "
    "(native = native/packer.cc, numpy = the fallback)", ("packer",))

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_LIB_PATH = os.path.join(_REPO_ROOT, "native", "build",
                         "libskytpu_packer.so")

_lib: Optional[ctypes.CDLL] = None
_lib_tried = False


def _load_native() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native packer; None on failure."""
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    try:
        if not os.path.exists(_LIB_PATH):
            subprocess.run(["make", "-C",
                            os.path.join(_REPO_ROOT, "native")],
                           capture_output=True, timeout=120, check=True)
        lib = ctypes.CDLL(_LIB_PATH)
    except (OSError, subprocess.SubprocessError) as e:
        # Once per process (_lib_tried): the numpy packer takes over,
        # and the event log says why.
        detail = getattr(e, "stderr", None) or str(e)
        if isinstance(detail, bytes):
            detail = detail.decode(errors="replace")
        tracing.add_event(
            "input_pipeline.native_packer_unavailable",
            {"error": type(e).__name__, "detail": detail[-2000:],
             "fallback": "numpy"}, echo=True)
        return None
    lib.pack_documents.restype = ctypes.c_int64
    lib.pack_documents.argtypes = [
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
    ]
    _lib = lib
    return _lib


def _pack_numpy(docs: List[np.ndarray], rows: int, cols: int,
                pad_id: int):
    tokens = np.full((rows, cols), pad_id, np.int32)
    segments = np.zeros((rows, cols), np.int32)
    positions = np.zeros((rows, cols), np.int32)
    used = [0] * rows
    next_seg = [1] * rows
    placed = 0
    for doc in docs:
        n = len(doc)
        if n > cols:
            placed += 1        # consumed (caller should pre-chunk)
            continue
        row = next((r for r in range(rows) if cols - used[r] >= n), -1)
        if row < 0:
            break
        tokens[row, used[row]:used[row] + n] = doc
        segments[row, used[row]:used[row] + n] = next_seg[row]
        positions[row, used[row]:used[row] + n] = np.arange(n)
        next_seg[row] += 1
        used[row] += n
        placed += 1
    return tokens, segments, positions, placed


def pack(docs: Sequence[Sequence[int]], rows: int, cols: int,
         pad_id: int = 0, force_numpy: bool = False):
    """Pack documents -> (tokens, segment_ids, positions, n_placed).

    ``n_placed`` counts consumed documents; the caller carries
    ``docs[n_placed:]`` into the next batch.
    """
    np_docs = [np.asarray(d, np.int32) for d in docs]
    lib = None if force_numpy else _load_native()
    if lib is None:
        return _pack_numpy(np_docs, rows, cols, pad_id)
    flat = (np.concatenate(np_docs) if np_docs
            else np.zeros((0,), np.int32))
    lens = np.asarray([len(d) for d in np_docs], np.int64)
    tokens = np.full((rows, cols), pad_id, np.int32)
    segments = np.zeros((rows, cols), np.int32)
    positions = np.zeros((rows, cols), np.int32)
    placed = lib.pack_documents(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(np_docs),
        tokens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        segments.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        positions.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        rows, cols, pad_id)
    return tokens, segments, positions, int(placed)


def packed_batches(doc_stream: Iterable[Sequence[int]], batch: int,
                   seq: int, pad_id: int = 0,
                   force_numpy: bool = False) -> Iterator[Dict[str, np.ndarray]]:
    """Stream documents -> packed train batches.

    Yields {"tokens", "segment_ids", "positions", "mask"} with
    mask = (segment_ids > 0) as the loss mask.
    """
    pending: List[Sequence[int]] = []
    it = iter(doc_stream)
    exhausted = False
    while not exhausted or pending:
        # Greedy fill: pull enough docs to plausibly fill the buffer.
        budget = batch * seq
        have = sum(min(len(d), seq) for d in pending)
        while not exhausted and have < budget * 2:
            try:
                d = next(it)
            except StopIteration:
                exhausted = True
                break
            if len(d) > seq:   # chunk oversized docs
                for i in range(0, len(d), seq):
                    pending.append(d[i:i + seq])
                    have += len(d[i:i + seq])
            else:
                pending.append(d)
                have += len(d)
        if not pending:
            break
        tokens, segments, positions, placed = pack(
            pending, batch, seq, pad_id, force_numpy)
        if placed == 0:
            break
        PACKED_BATCHES.labels(
            "numpy" if force_numpy or _load_native() is None
            else "native").inc()
        pending = pending[placed:]
        yield {
            "tokens": tokens,
            "segment_ids": segments,
            "positions": positions,
            "mask": (segments > 0).astype(np.float32),
        }


def prefetch(batches: Iterable[Dict[str, np.ndarray]], size: int = 2,
             device_put=None) -> Iterator:
    """Double-buffered background prefetch (optionally device_put)."""
    q: "queue.Queue" = queue.Queue(maxsize=size)
    _END = object()

    def worker():
        try:
            for b in batches:
                if device_put is not None:
                    b = device_put(b)
                q.put(b)
            q.put(_END)
        except BaseException as e:  # noqa: BLE001 — re-raised in consumer
            q.put(e)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    while True:
        item = q.get()
        if item is _END:
            return
        if isinstance(item, BaseException):
            raise item
        yield item


def synthetic_doc_stream(n_docs: int, vocab_size: int, mean_len: int,
                         seed: int = 0) -> Iterator[List[int]]:
    """Length-varied synthetic documents (for benchmarks/tests)."""
    rng = np.random.default_rng(seed)
    for _ in range(n_docs):
        n = max(int(rng.poisson(mean_len)), 1)
        yield rng.integers(1, vocab_size, n).astype(np.int32).tolist()
