"""Out-of-core streaming: host-resident compressed bins, chunked H2D.

Every training path before this layer assumed the full binned matrix is
device-resident; a dated N-scaling probe showed the HBM wall turning
into a ~4x-worse-than-linear throughput knee at the 10.5M reference
scale (ROADMAP item 1). The out-of-core GPU GBDT literature
(arXiv:2005.09148, arXiv:1806.11248) recovers near-resident throughput
with two ingredients this module provides:

* **Compressed wire format, host-side.** The binned matrix stays in
  host memory in the SAME `max_bin`-aware bit-packed format the compact
  cores already use on device (4-bit codes when every declared column
  fits a nibble, else u8/u16, packed into u32 words — see
  `DeviceTreeLearner.pack_codes`). Nothing is re-encoded on the way to
  the device: a chunk transfer is a memcpy of packed words.

* **Double-buffered chunk iteration.** `iter_chunks` dispatches chunk
  i+1's `jax.device_put` BEFORE blocking on chunk i, so the host->device
  copy of the next chunk overlaps whatever the caller does with the
  current one. The blocking residue is attributed to the `stream_wait`
  telemetry phase and every transferred byte to the existing
  `transfer_h2d_bytes` counter, making the overlap measurable
  (`overlap_fraction`: 1 - wait/span).

The shard also owns the GOSS working set (top-gradient rows pinned
device-resident across iterations, `stream_mode=goss`), the device-byte
accounting the microbench reports, and the stream cursor + working-set
membership that round-trip through `resilience` checkpoints so a
resumed run streams exactly like the uninterrupted one.

Chunking is pure data movement: the trained model is bit-identical to
resident training for ANY chunk size (see docs/Streaming.md and
tests/test_streaming.py).
"""
from __future__ import annotations

import time
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

import jax

from ..telemetry import counters as telem_counters
from ..telemetry import recorder as telem

__all__ = ["DeviceDataShard", "derive_stream_chunk_rows"]


def derive_stream_chunk_rows(requested: int, core_chunk_rows: int) -> int:
    """The ONE resolution point of `stream_chunk_rows`: an explicit
    param wins; 0 derives from the growth core's chunk size so one
    stream chunk feeds one core chunk. Floored at 1024 rows — below
    that per-transfer latency dominates and the double buffer cannot
    hide it."""
    rows = int(requested) if int(requested) > 0 else int(core_chunk_rows)
    return max(1024, rows)


class DeviceDataShard:
    """Host wire store + double-buffered H2D chunk pipeline.

    `wire` is the (N, CW) u32 array of bit-packed per-row codes
    (`item_bits` codes of `c_cols` columns per row; identical bytes to
    the device `codes_pack` buffer resident training uses). Device-byte
    accounting is explicit: callers register the buffers they hold via
    `track_buffer`/`release_buffer` and the shard folds in its own
    in-flight transfer and working-set buffers; `peak_bytes` is the
    high-water mark the microbench compares against resident training.
    """

    def __init__(self, packed_codes: np.ndarray, *, item_bits: int,
                 c_cols: int, chunk_rows: int = 0,
                 core_chunk_rows: int = 65536):
        wire = np.ascontiguousarray(np.asarray(packed_codes))
        if wire.dtype != np.uint32 or wire.ndim != 2:
            raise ValueError("DeviceDataShard wants (N, CW) u32 packed "
                             f"codes, got {wire.dtype} {wire.shape}")
        self.wire = wire
        self.num_rows, self.code_words = wire.shape
        self.item_bits = int(item_bits)
        self.c_cols = int(c_cols)
        self.chunk_rows = derive_stream_chunk_rows(
            chunk_rows, core_chunk_rows)
        # stream cursor: total chunks transferred so far. Checkpointed
        # (stream_state) purely as bookkeeping consistency — assembly is
        # value-order-independent, so the cursor cannot perturb results;
        # carrying it keeps transfer accounting and working-set refresh
        # cadence identical across a kill/resume.
        self.cursor = 0
        self.ws_ids = np.zeros(0, np.int32)
        self._ws_rows: Optional[jax.Array] = None
        self._live: Dict[str, int] = {}
        self.peak_bytes = 0
        # cumulative pipeline metrics (work with telemetry off; bench's
        # overlap fraction and the microbench read these directly)
        self.h2d_bytes = 0
        self.stream_seconds = 0.0
        self.wait_seconds = 0.0

    # -- device-byte accounting ----------------------------------------
    def track_buffer(self, name: str, nbytes: int) -> None:
        self._live[name] = int(nbytes)
        total = sum(self._live.values())
        if total > self.peak_bytes:
            self.peak_bytes = total

    def release_buffer(self, name: str) -> None:
        self._live.pop(name, None)

    def live_bytes(self) -> int:
        return sum(self._live.values())

    @property
    def host_bytes(self) -> int:
        return int(self.wire.nbytes)

    def overlap_fraction(self) -> Optional[float]:
        """1 - (blocking wait / streaming-pass wall): ~1.0 means every
        transfer was hidden behind dispatch/compute, ~0.0 means the
        pipeline is transfer-bound."""
        if self.stream_seconds <= 0.0:
            return None
        return max(0.0, 1.0 - self.wait_seconds / self.stream_seconds)

    # -- the double-buffered pipeline ----------------------------------
    def iter_chunks(self, row_ids: Optional[np.ndarray] = None,
                    emit_phase: bool = True,
                    device=None
                    ) -> Iterator[Tuple[int, int, jax.Array]]:
        """Yield (start, count, device_chunk) over the wire rows (or the
        given row-id subset), next chunk's H2D dispatched before the
        current chunk's wait. Chunks except the last have exactly
        `chunk_rows` rows. `emit_phase=False` skips the `stream_wait`
        recorder phase (for streaming nested inside another recorded
        phase — recorder phases must not nest); bytes and wait seconds
        are still counted. `device` pins the H2D target (the streamed
        data-parallel learner assembles one working buffer per local
        mesh device); None keeps the default-device placement."""
        if row_ids is not None:
            row_ids = np.asarray(row_ids, dtype=np.int64)
        n = self.num_rows if row_ids is None else int(row_ids.size)
        if n == 0:
            return
        sc = self.chunk_rows
        nch = -(-n // sc)

        def dispatch(i: int):
            s = i * sc
            e = min(n, s + sc)
            if row_ids is None:
                arr = self.wire[s:e]
            else:
                arr = np.ascontiguousarray(self.wire[row_ids[s:e]])
            return s, e - s, int(arr.nbytes), jax.device_put(arr, device)

        self.track_buffer(
            "stream_inflight", 2 * sc * self.code_words * 4)
        t_pass = time.perf_counter()
        try:
            pend = dispatch(0)
            for i in range(nch):
                nxt = dispatch(i + 1) if i + 1 < nch else None
                s, cnt, nb, dev = pend
                t0 = time.perf_counter()
                if emit_phase:
                    with telem.phase("stream_wait"):
                        dev.block_until_ready()
                else:
                    dev.block_until_ready()
                self.wait_seconds += time.perf_counter() - t0
                self.h2d_bytes += nb
                if telem_counters.is_active():
                    telem_counters.incr("transfer_h2d_bytes", nb)
                yield s, cnt, dev
                pend = nxt
            self.cursor += nch
        finally:
            self.release_buffer("stream_inflight")
            self.stream_seconds += time.perf_counter() - t_pass

    # -- GOSS working set ----------------------------------------------
    def pin_working_set(self, ids: np.ndarray,
                        rows: Optional[jax.Array] = None) -> None:
        """Pin `ids` (sorted row ids) device-resident. `rows` is the
        (len(ids), CW) packed code buffer when the caller already holds
        it on device (the refresh path — no H2D); omitted, the rows are
        uploaded from the wire store (checkpoint restore). Codes are
        immutable, so both sources hold identical bytes."""
        ids = np.asarray(ids, dtype=np.int32)
        if rows is None and ids.size:
            arr = np.ascontiguousarray(self.wire[ids.astype(np.int64)])
            rows = jax.device_put(arr)
            self.h2d_bytes += int(arr.nbytes)
            if telem_counters.is_active():
                telem_counters.incr("transfer_h2d_bytes", int(arr.nbytes))
        self.ws_ids = ids
        self._ws_rows = rows if ids.size else None
        if ids.size:
            self.track_buffer(
                "working_set", int(ids.size) * self.code_words * 4)
        else:
            self.release_buffer("working_set")

    def working_set(self) -> Tuple[np.ndarray, Optional[jax.Array]]:
        return self.ws_ids, self._ws_rows

    # -- append-rows (continual/update.py) -----------------------------
    def append_rows(self, packed_rows: np.ndarray) -> int:
        """Append already-packed rows to the wire store; returns the new
        row count. The block must be packed with the SAME
        item_bits/c_cols layout as construction
        (`continual.update.pack_codes` / `pack_codes` on the owning
        learner) — history is never re-encoded, the append is a
        concatenation of wire words. The stream cursor, working set and
        byte accounting are untouched: existing row ids keep their
        meaning, new rows simply extend the chunk iteration space."""
        block = np.ascontiguousarray(np.asarray(packed_rows))
        if block.dtype != np.uint32 or block.ndim != 2 \
                or block.shape[1] != self.code_words:
            raise ValueError(
                f"append_rows wants (M, {self.code_words}) u32 packed "
                f"codes, got {block.dtype} {block.shape}")
        self.wire = np.concatenate([self.wire, block], axis=0)
        self.num_rows = int(self.wire.shape[0])
        return self.num_rows

    # -- checkpoint round-trip -----------------------------------------
    def stream_state(self) -> Dict[str, object]:
        return {"cursor": int(self.cursor),
                "ws_ids": np.asarray(self.ws_ids, dtype=np.int32)}

    def load_stream_state(self, st: Dict[str, object]) -> None:
        self.cursor = int(st.get("cursor", 0))
        ws = np.asarray(st.get("ws_ids", np.zeros(0, np.int32)),
                        dtype=np.int32)
        self.pin_working_set(ws)
