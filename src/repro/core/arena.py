"""Arena layout / storage split: the out-of-core representation of the index.

The paper's headline scaling property — "COBS does not need the complete
index in RAM" — requires the arena (uint32 [total_rows, doc_words]) to be
*addressable in shards* rather than one dense array. This module separates
the two concerns that BitSlicedIndex used to conflate:

* ``ArenaLayout`` — pure host-side metadata (per-block row offsets and
  filter widths, the document-slot permutation, term counts). It fully
  determines query addressing and never touches arena bytes; it is
  pytree-static in the sense that no piece of it is a traced value.

* ``ArenaStorage`` — where the arena bytes live. Three backends:

  - ``DeviceArena``  — one dense device array (the original behavior; the
    zero-copy migration path for existing code).
  - ``HostArena``    — one dense host array, moved to device lazily.
  - ``MappedArena``  — a list of row-range shards, each an ``np.memmap``
    over a raw ``.npy`` file (or an in-memory array for O(metadata)
    merges). Rows are paged to device per shard, on demand — the index
    never has to be resident anywhere end to end.

Shards always cover whole blocks (the store writes shard boundaries on
block-group edges), so per-shard query addressing is the global addressing
with row offsets rebased to the shard's first row.

``DeviceTileCache`` is the HBM paging policy: a bounded LRU of shard id ->
device tile with hit/fault counters, shared by the QueryEngine and the
serving subsystem (which exports the counters as metrics).
``PackedTileCache`` is its HBM-resident form for a set of shards of
different heights: one arena holding them all, with no padding per shard.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from . import codec as _codec
from ..kernels.bitslice_score import SUBLANES, row_geometry
from ..kernels.ops import PackedArena, pack_lines


@dataclasses.dataclass(frozen=True)
class ArenaLayout:
    """Geometric metadata of an arena; pure, host-side, and immutable.

    row_offset[b] is the global first arena row of block b; block b owns
    rows [row_offset[b], row_offset[b] + block_width[b]). Document i of
    the original corpus lives at slot doc_slot[i] (block slot//block_docs,
    column slot%block_docs).
    """

    row_offset: np.ndarray   # int32 [n_blocks]
    block_width: np.ndarray  # int32 [n_blocks]
    doc_slot: np.ndarray     # int32 [n_docs]
    doc_n_terms: np.ndarray  # int32 [n_docs]
    block_docs: int
    n_docs: int

    @staticmethod
    def make(row_offset, block_width, doc_slot, doc_n_terms,
             block_docs: int, n_docs: int) -> "ArenaLayout":
        return ArenaLayout(
            row_offset=np.asarray(row_offset, dtype=np.int32),
            block_width=np.asarray(block_width, dtype=np.int32),
            doc_slot=np.asarray(doc_slot, dtype=np.int32),
            doc_n_terms=np.asarray(doc_n_terms, dtype=np.int32),
            block_docs=int(block_docs),
            n_docs=int(n_docs),
        )

    # -- derived geometry ---------------------------------------------------
    @property
    def n_blocks(self) -> int:
        return int(self.row_offset.shape[0])

    @property
    def doc_words(self) -> int:
        return self.block_docs // 32

    @property
    def total_rows(self) -> int:
        if self.n_blocks == 0:
            return 0
        return int(self.row_offset[-1]) + int(self.block_width[-1])

    @property
    def n_slots(self) -> int:
        return self.n_blocks * self.block_docs

    def block_row_range(self, b: int) -> tuple[int, int]:
        start = int(self.row_offset[b])
        return start, start + int(self.block_width[b])

    def shard_blocks(self, shard_row_starts: np.ndarray
                     ) -> list[tuple[int, int]]:
        """Partition blocks by shard: returns [(block_start, block_end)] per
        shard for row boundaries ``shard_row_starts`` (int64 [n_shards+1]).
        Every shard boundary must fall on a block boundary."""
        bounds = np.concatenate([self.row_offset.astype(np.int64),
                                 [self.total_rows]])
        out = []
        for s in range(len(shard_row_starts) - 1):
            lo = int(np.searchsorted(bounds, shard_row_starts[s]))
            hi = int(np.searchsorted(bounds, shard_row_starts[s + 1]))
            if (bounds[lo] != shard_row_starts[s]
                    or bounds[hi] != shard_row_starts[s + 1]):
                raise ValueError("shard boundary not on a block boundary")
            out.append((lo, hi))
        return out


# --------------------------------------------------------------------------
# Storage backends
# --------------------------------------------------------------------------

class ArenaStorage:
    """Protocol for arena byte storage.

    shape/dtype mirror the dense array; shards are contiguous row ranges
    covering [0, total_rows) whose boundaries are ``shard_row_starts``
    (int64 [n_shards + 1]).
    """

    shape: tuple[int, int]
    dtype: np.dtype
    shard_row_starts: np.ndarray

    @property
    def n_shards(self) -> int:
        return len(self.shard_row_starts) - 1

    def nbytes(self) -> int:
        return int(self.shape[0]) * int(self.shape[1]) * \
            np.dtype(self.dtype).itemsize

    def shard_nbytes(self, s: int) -> int:
        rows = int(self.shard_row_starts[s + 1] - self.shard_row_starts[s])
        return rows * int(self.shape[1]) * np.dtype(self.dtype).itemsize

    # -- byte access (implemented per backend) ------------------------------
    def shard_host(self, s: int) -> np.ndarray:
        raise NotImplementedError

    def shard_device(self, s: int) -> jnp.ndarray:
        return jnp.asarray(self.shard_host(s))

    def full_host(self) -> np.ndarray:
        return np.concatenate([np.asarray(self.shard_host(s))
                               for s in range(self.n_shards)], axis=0)

    def full_device(self) -> jnp.ndarray:
        """Dense device arena — the legacy path; materializes everything."""
        if self.n_shards == 1:
            return self.shard_device(0)
        return jnp.concatenate([self.shard_device(s)
                                for s in range(self.n_shards)], axis=0)

    def read_rows_host(self, rows: np.ndarray) -> np.ndarray:
        """Arbitrary global rows, host-side (point-query path). Pages only
        the rows' shards; never materializes the dense arena for mapped
        storage."""
        rows = np.asarray(rows, dtype=np.int64)
        out = np.empty((rows.size, self.shape[1]), dtype=self.dtype)
        flat = rows.reshape(-1)
        owner = np.searchsorted(self.shard_row_starts, flat, side="right") - 1
        for s in np.unique(owner):
            sel = owner == s
            local = flat[sel] - int(self.shard_row_starts[s])
            out[sel] = np.asarray(self.shard_host(int(s)))[local]
        return out.reshape(*rows.shape, self.shape[1])

    # -- popcount stats (recorded by v2 stores; absent elsewhere) -----------
    def has_popcounts(self) -> bool:
        return False

    def shard_popcounts(self, s: int) -> np.ndarray | None:
        return None

    def row_popcounts(self, rows: np.ndarray) -> np.ndarray | None:
        return None

    def mean_popcount(self) -> float | None:
        return None

    # -- compression surface (raw everywhere except MappedArena) ------------
    def shard_codec(self, s: int) -> str:
        """This shard's on-disk codec (repro.core.codec.CODECS)."""
        return _codec.CODEC_RAW

    def shard_comp_nbytes(self, s: int) -> int:
        """Encoded (on-disk) shard bytes (== shard_nbytes for raw)."""
        return self.shard_nbytes(s)

    def shard_hbm_nbytes(self, s: int) -> int:
        """Bytes the shard's compressed DEVICE form needs: dict + refs
        for rowdict codecs (what the tile cache stages), raw otherwise.
        Unlike ``shard_comp_nbytes`` this excludes disk-only RLE gains —
        it is the working-set number the cache accounts in."""
        return self.shard_nbytes(s)

    def shard_dict_host(self, s: int
                        ) -> tuple[np.ndarray, np.ndarray] | None:
        """The shard's HBM-compressible dictionary form — (dict_rows
        uint32 [D, W], refs int32 [rows]) for rowdict-coded shards, None
        otherwise. The DeviceTileCache stages THIS instead of the
        expanded tile when the compressed score path is planned."""
        return None

    def comp_summary(self) -> tuple[int, int, int]:
        """(raw_bytes, encoded_bytes, n_compressed_shards) over all
        shards — the store-level compression ratio the manifest records
        per shard, aggregated."""
        raw = comp = n = 0
        for s in range(self.n_shards):
            raw += self.shard_nbytes(s)
            comp += self.shard_comp_nbytes(s)
            if self.shard_codec(s) != _codec.CODEC_RAW:
                n += 1
        return raw, comp, n

    def dict_ratio(self) -> float | None:
        """HBM compression ratio of the dict-form shards: expanded bytes
        over (dict + refs) bytes, aggregated across every rowdict-coded
        shard. None when no shard has a dict form — the planner/tuner
        gate the compressed kernel paths on this."""
        raw = comp = 0
        for s in range(self.n_shards):
            if self.shard_codec(s) in _codec.DICT_CODECS:
                raw += self.shard_nbytes(s)
                comp += self.shard_hbm_nbytes(s)
        if comp == 0:
            return None
        return raw / comp


def _starts(n_rows: int) -> np.ndarray:
    return np.array([0, n_rows], dtype=np.int64)


class DeviceArena(ArenaStorage):
    """One dense device-resident array — today's behavior, one shard."""

    def __init__(self, arena):
        self.arena = arena
        self.shape = tuple(arena.shape)
        self.dtype = np.dtype(getattr(arena, "dtype", np.uint32))
        self.shard_row_starts = _starts(self.shape[0])
        self._host: np.ndarray | None = None

    def shard_host(self, s: int) -> np.ndarray:
        if self._host is None:
            self._host = np.asarray(self.arena)
        return self._host

    def shard_device(self, s: int) -> jnp.ndarray:
        return self.arena

    def full_device(self):
        return self.arena


class HostArena(ArenaStorage):
    """One dense host array; the device copy is made lazily and cached."""

    def __init__(self, arena: np.ndarray):
        self.arena = np.asarray(arena)
        self.shape = tuple(self.arena.shape)
        self.dtype = self.arena.dtype
        self.shard_row_starts = _starts(self.shape[0])
        self._device: jnp.ndarray | None = None

    def shard_host(self, s: int) -> np.ndarray:
        return self.arena

    def shard_device(self, s: int) -> jnp.ndarray:
        if self._device is None:
            self._device = jnp.asarray(self.arena)
        return self._device


class MappedArena(ArenaStorage):
    """Row-range shards backed by raw ``.npy`` files (np.memmap), lazy
    compressed sources (``repro.core.codec.CompressedShardSource``),
    and/or in-memory arrays. File-backed shards are opened lazily with
    ``mmap_mode='r'`` so touching a shard costs page faults, not a load;
    in-memory sources make merge an O(metadata) shard-list concatenation.

    Compressed sources decode on first ``shard_host`` touch (the decoded
    tile is cached; all existing raw consumers stay bit-identical), or
    hand their dictionary form to the tile cache via ``shard_dict_host``
    without ever expanding. ``decode_observer(shard, codec, seconds)``,
    when set, sees every host-side decode — the serving layer wires it
    to the obs registry's decode-time histogram.
    """

    def __init__(self, sources: list, shard_row_starts: np.ndarray,
                 doc_words: int, dtype=np.uint32, pop_sources: list | None
                 = None):
        self.sources = list(sources)        # Path | str | ndarray | source
        self.shard_row_starts = np.asarray(shard_row_starts, dtype=np.int64)
        if len(self.sources) != self.n_shards:
            raise ValueError("sources / shard_row_starts length mismatch")
        self.shape = (int(self.shard_row_starts[-1]), int(doc_words))
        self.dtype = np.dtype(dtype)
        self._open: dict[int, np.ndarray] = {}
        self._open_dict: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        # optional per-slice popcount sidecars (Path | ndarray | None per
        # shard, from the v2 manifest's "pops" field): rarest-term-first
        # ordering for the pruned executor; None entries degrade to
        # natural term order
        self.pop_sources = (list(pop_sources) if pop_sources is not None
                            else [None] * self.n_shards)
        if len(self.pop_sources) != self.n_shards:
            raise ValueError("pop_sources / shard_row_starts length mismatch")
        self._open_pops: dict[int, np.ndarray] = {}
        self.decode_observer = None
        self.decodes = 0

    def _shard_rows(self, s: int) -> int:
        return int(self.shard_row_starts[s + 1] - self.shard_row_starts[s])

    def _notify_decode(self, s: int, codec: str, seconds: float) -> None:
        self.decodes += 1
        if self.decode_observer is not None:
            try:
                self.decode_observer(s, codec, seconds)
            except Exception:
                pass              # accounting must never fail a read

    def shard_host(self, s: int) -> np.ndarray:
        a = self._open.get(s)
        if a is None:
            src = self.sources[s]
            if isinstance(src, _codec.CompressedShardSource):
                t0 = time.perf_counter()
                a = src.load().decode()
                self._notify_decode(s, src.codec,
                                    time.perf_counter() - t0)
            elif isinstance(src, np.ndarray):
                a = src
            else:
                a = np.load(src, mmap_mode="r")
            want_rows = self._shard_rows(s)
            if a.shape != (want_rows, self.shape[1]):
                raise ValueError(
                    f"shard {s}: shape {a.shape} != "
                    f"({want_rows}, {self.shape[1]})")
            self._open[s] = a
        return a

    # -- popcount stats surface ----------------------------------------------
    def has_popcounts(self) -> bool:
        """True when EVERY shard carries a popcount sidecar — the pruned
        executor needs a total order over a query's terms, so partial
        stats degrade to natural order."""
        return all(p is not None for p in self.pop_sources)

    def shard_popcounts(self, s: int) -> np.ndarray | None:
        """Per-row popcounts of shard ``s`` (uint32 [rows], mmap-backed),
        or None when the store predates the stats field."""
        src = self.pop_sources[s]
        if src is None:
            return None
        a = self._open_pops.get(s)
        if a is None:
            a = src if isinstance(src, np.ndarray) else np.load(
                src, mmap_mode="r")
            if a.shape != (self._shard_rows(s),):
                raise ValueError(
                    f"shard {s}: popcount sidecar shape {a.shape} != "
                    f"({self._shard_rows(s)},)")
            self._open_pops[s] = a
        return a

    def row_popcounts(self, rows: np.ndarray) -> np.ndarray | None:
        """Popcounts of arbitrary GLOBAL arena rows (int64 [..] -> int64
        [..]), reading only the touched sidecar pages — never the arena.
        None when any shard lacks stats."""
        if not self.has_popcounts():
            return None
        rows = np.asarray(rows, dtype=np.int64)
        flat = rows.reshape(-1)
        out = np.empty(flat.size, dtype=np.int64)
        owner = np.searchsorted(self.shard_row_starts, flat,
                                side="right") - 1
        for s in np.unique(owner):
            sel = owner == s
            local = flat[sel] - int(self.shard_row_starts[s])
            out[sel] = np.asarray(self.shard_popcounts(int(s))[local],
                                  dtype=np.int64)
        return out.reshape(rows.shape)

    def mean_popcount(self) -> float | None:
        """Mean set-bit count per arena row across all shards (the corpus
        density the serving planner's prune-rate prediction uses), or
        None without stats."""
        if not self.has_popcounts():
            return None
        total = n = 0
        for s in range(self.n_shards):
            p = self.shard_popcounts(s)
            total += int(np.asarray(p, dtype=np.int64).sum())
            n += p.shape[0]
        return total / n if n else 0.0

    # -- compression surface -------------------------------------------------
    def shard_codec(self, s: int) -> str:
        src = self.sources[s]
        if isinstance(src, _codec.CompressedShardSource):
            return src.codec
        return _codec.CODEC_RAW

    def shard_comp_nbytes(self, s: int) -> int:
        src = self.sources[s]
        if isinstance(src, _codec.CompressedShardSource):
            return int(src.comp_nbytes)
        return self.shard_nbytes(s)

    def shard_hbm_nbytes(self, s: int) -> int:
        d = self.shard_dict_host(s)
        if d is None:
            return self.shard_nbytes(s)
        return int(d[0].nbytes) + int(d[1].nbytes)

    def shard_dict_host(self, s: int
                        ) -> tuple[np.ndarray, np.ndarray] | None:
        if self.shard_codec(s) not in _codec.DICT_CODECS:
            return None
        cached = self._open_dict.get(s)
        if cached is None:
            src = self.sources[s]
            t0 = time.perf_counter()
            cached = src.load().dict_form()
            # rowdict mmaps straight through (no decode work); the +rle
            # variant expands its dictionary payload here — count it
            if src.codec == _codec.CODEC_ROWDICT_RLE:
                self._notify_decode(s, src.codec,
                                    time.perf_counter() - t0)
            self._open_dict[s] = cached
        return cached

    @staticmethod
    def concat(a: "ArenaStorage", b: "ArenaStorage") -> "MappedArena":
        """Row-axis concatenation without touching bytes: the merged arena
        is the two shard lists back to back (paper section 2.3 merging as
        an O(metadata) operation)."""
        if a.shape[1] != b.shape[1]:
            raise ValueError("doc_words mismatch")

        def shard_sources(st: ArenaStorage) -> list:
            if isinstance(st, MappedArena):
                return st.sources
            return [st.shard_host(s) for s in range(st.n_shards)]

        starts = np.concatenate([
            a.shard_row_starts,
            b.shard_row_starts[1:] + int(a.shard_row_starts[-1])])
        return MappedArena(shard_sources(a) + shard_sources(b), starts,
                           doc_words=a.shape[1], dtype=a.dtype)


def wrap_arena(arena) -> ArenaStorage:
    """Adopt a raw arena value under the storage protocol: numpy stays on
    host (HostArena), anything device-shaped (jax arrays, abstract
    ShapeDtypeStructs from the dry-run lowering) is a DeviceArena."""
    if isinstance(arena, ArenaStorage):
        return arena
    if isinstance(arena, np.ndarray):
        return HostArena(arena)
    return DeviceArena(arena)


# --------------------------------------------------------------------------
# HBM paging
# --------------------------------------------------------------------------

def _pad_dict_rows(n: int) -> int:
    """Pow2 padding (floor 8) for staged dictionary heights — mirrors the
    query planner's unique-row padding so compressed kernel shapes bucket
    into O(log) variants instead of one compile per distinct D."""
    return max(8, 1 << max(0, int(n) - 1).bit_length())


def common_tile_rows(storage: ArenaStorage) -> int | None:
    """Row count unifying all of a sharded storage's tiles (the tallest
    shard), or None for dense single-shard storage (no padding needed)."""
    if storage.n_shards <= 1:
        return None
    return int(np.max(np.diff(storage.shard_row_starts)))


class DeviceTileCache:
    """Bounded LRU of shard id -> device tile.

    ``capacity_bytes`` caps resident tile bytes (None = unbounded: every
    shard sticks after first touch, the right default for engines that own
    the whole device). A miss ("page fault") stages the shard host->device
    and may evict least-recently-used tiles; counters feed the serving
    metrics (shard residency / page faults).

    ``pad_rows_to`` zero-pads every staged tile to a common row count
    (typically the tallest shard): addressed rows are always < the real
    shard height, so results are unchanged, but all tiles share one shape
    and the scoring kernels compile ONCE per (bucket, method) instead of
    once per distinct shard height — compile time would otherwise dominate
    cold out-of-core serving on stores with many block groups.

    ``prefetch`` is the double-buffering hook: it stages a tile WITHOUT
    blocking the caller's compute stream (device transfers are dispatched
    asynchronously), so paged scoring loops can overlap the next shard's
    host->device copy with the current shard's kernel. ``faults`` counts
    every staging (demand or prefetch — each is one H2D transfer);
    ``prefetch_hits`` counts gets served by a previously prefetched tile,
    so prefetch_hits / prefetched is the prefetch usefulness rate exported
    by the serving metrics.

    ``device`` optionally pins staged tiles to a specific jax device — the
    multi-host serving path gives each fake-host worker its own device.

    Compressed residency: for rowdict-coded shards, ``get_compressed``
    stages the (dict_rows, refs) pair instead of the expanded tile — the
    HBM working set shrinks by the shard's measured ratio and the cache
    accounts the COMPRESSED bytes, so the same ``capacity_bytes`` holds
    ratio-times more shards. Raw and compressed forms of a shard are
    independent cache entries (int key vs ("c", shard)) sharing one LRU
    and one byte budget; ``raw_bytes_staged`` / ``comp_bytes_staged``
    accumulate staged bytes per form for the serving metrics.
    """

    def __init__(self, storage: ArenaStorage,
                 capacity_bytes: int | None = None,
                 pad_rows_to: int | None = None,
                 device=None):
        self.storage = storage
        self.capacity_bytes = capacity_bytes
        self.pad_rows_to = pad_rows_to
        self.device = device
        # key: shard id (raw tile) or ("c", shard id) (dict form)
        # The LRU map, byte budget, and counters mutate under one lock so
        # the cache is safe to share between the interactive scoring
        # workers and the bulk lane WITHOUT serializing their kernel
        # work behind the loop's backend lock: staged tiles are immutable
        # device arrays, so a reference obtained under the lock stays
        # valid through a concurrent eviction.
        self._lock = threading.RLock()
        self._tiles: "OrderedDict" = OrderedDict()
        self._sizes: dict = {}
        self._prefetched: set = set()
        self.resident_bytes = 0
        self.hits = 0
        self.faults = 0
        self.prefetched = 0
        self.prefetch_hits = 0
        self.raw_bytes_staged = 0
        self.comp_bytes_staged = 0
        # Per-shard accounting (the global totals above cannot say WHICH
        # shard keeps faulting when the working set outsizes the cache).
        self.shard_hits: dict[int, int] = {}
        self.shard_faults: dict[int, int] = {}
        self.shard_evictions: dict[int, int] = {}
        # Optional event hook: observer(shard, event, seconds) with event
        # in {"hit", "fault", "prefetch", "eviction"}; ``seconds`` is the
        # staging (dispatch) time for faults/prefetches, 0.0 otherwise.
        # The serving layer wires this to labeled registry counters and
        # to trace spans naming the faulted shard.
        self.observer = None

    def row_base(self, s: int) -> int:
        """The first row of shard ``s`` in the tile ``get(s)`` returns:
        0, every shard is a tile of its own."""
        return 0

    def _notify(self, s: int, event: str, seconds: float = 0.0) -> None:
        if self.observer is not None:
            try:
                self.observer(s, event, seconds)
            except Exception:
                pass              # accounting must never fail a gather

    def _put(self, host: np.ndarray) -> jnp.ndarray:
        if self.device is None:
            return jnp.asarray(host)
        import jax
        return jax.device_put(host, self.device)

    def _put_packed(self, host: np.ndarray) -> PackedArena:
        """Stage a [rows, W] host array as the lane-packed device form
        the gather kernels read (see ``repro.kernels.ops.PackedArena``)."""
        host = np.asarray(host)
        return PackedArena(self._put(pack_lines(host)), *host.shape)

    def _stage(self, s: int) -> PackedArena:
        host = self.storage.shard_host(s)
        pad = (self.pad_rows_to or host.shape[0]) - host.shape[0]
        if pad < 0:
            raise ValueError(f"shard {s} taller than pad_rows_to")
        if pad:
            host = np.pad(host, ((0, pad), (0, 0)))
        return self._put_packed(host)

    def _stage_compressed(self, s: int) -> tuple:
        d = self.storage.shard_dict_host(s)
        if d is None:
            raise ValueError(
                f"shard {s} has no dict form "
                f"(codec {self.storage.shard_codec(s)!r})")
        dict_rows, refs = d
        D = int(dict_rows.shape[0])
        d_pad = _pad_dict_rows(D) - D
        if d_pad:
            dict_rows = np.pad(np.asarray(dict_rows), ((0, d_pad), (0, 0)))
        pad_to = self.pad_rows_to or int(refs.shape[0])
        r_pad = pad_to - int(refs.shape[0])
        if r_pad < 0:
            raise ValueError(f"shard {s} taller than pad_rows_to")
        if r_pad:                  # padded rows ref slot 0; never addressed
            refs = np.pad(np.asarray(refs), (0, r_pad))
        return (self._put_packed(dict_rows),
                self._put(np.ascontiguousarray(refs)))

    def _tile_nbytes(self, s: int) -> int:
        if not self.pad_rows_to:
            return self.storage.shard_nbytes(s)
        return (self.pad_rows_to * int(self.storage.shape[1])
                * np.dtype(self.storage.dtype).itemsize)

    @staticmethod
    def _shard_of(key) -> int:
        return key[1] if isinstance(key, tuple) else key

    def __len__(self) -> int:
        return len(self._tiles)

    @property
    def resident_shards(self) -> tuple[int, ...]:
        return tuple(self._shard_of(k) for k in self._tiles)

    def has_compressed(self, s: int) -> bool:
        return ("c", s) in self._tiles

    def _evict_victim(self):
        """Ratio-aware victim selection: the least-recently-used RAW tile
        goes first — a dict-coded entry packs ratio-times more arena per
        resident byte (and costs a re-encode-shaped decode to restage), so
        raw tiles are the cheap bytes to give back. Falls back to plain
        LRU when only dict entries remain."""
        for key in self._tiles:                # OrderedDict: LRU first
            if not isinstance(key, tuple):
                return key
        return next(iter(self._tiles))

    def _insert(self, key) -> tuple:
        s = self._shard_of(key)
        compressed = isinstance(key, tuple)
        t0 = time.perf_counter()
        tile = self._stage_compressed(s) if compressed else self._stage(s)
        staged_s = time.perf_counter() - t0
        if compressed:
            need = sum(int(t.nbytes) for t in tile)
            self.comp_bytes_staged += need
        else:
            need = self._tile_nbytes(s)
            self.raw_bytes_staged += need
        if self.capacity_bytes is not None:
            while (self._tiles
                   and self.resident_bytes + need > self.capacity_bytes):
                old = self._evict_victim()
                del self._tiles[old]
                self.resident_bytes -= self._sizes.pop(old)
                self._prefetched.discard(old)
                old_s = self._shard_of(old)
                self.shard_evictions[old_s] = \
                    self.shard_evictions.get(old_s, 0) + 1
                self._notify(old_s, "eviction")
        self._tiles[key] = tile
        self._sizes[key] = need
        self.resident_bytes += need
        return tile, staged_s

    def _get(self, key):
        with self._lock:
            s = self._shard_of(key)
            tile = self._tiles.get(key)
            if tile is not None:
                self._tiles.move_to_end(key)
                self.hits += 1
                self.shard_hits[s] = self.shard_hits.get(s, 0) + 1
                if key in self._prefetched:
                    self._prefetched.discard(key)
                    self.prefetch_hits += 1
                self._notify(s, "hit")
                return tile
            self.faults += 1
            self.shard_faults[s] = self.shard_faults.get(s, 0) + 1
            tile, staged_s = self._insert(key)
            self._notify(s, "fault", staged_s)
            return tile

    def get(self, s: int) -> PackedArena:
        """Shard ``s`` on device as a lane-packed [rows, W] arena."""
        return self._get(s)

    def get_compressed(self, s: int) -> tuple:
        """(dict_tile PackedArena [D_pad, W], refs int32 [pad_rows_to]) on
        device — the fused kernels' decode inputs. D is padded to a pow2
        (floor 8) so kernel shapes bucket; refs pad with slot 0."""
        return self._get(("c", s))

    def _prefetch(self, key) -> bool:
        with self._lock:
            if key in self._tiles:
                return False
            s = self._shard_of(key)
            self.faults += 1
            self.shard_faults[s] = self.shard_faults.get(s, 0) + 1
            self.prefetched += 1
            self._prefetched.add(key)
            _, staged_s = self._insert(key)
            self._notify(s, "prefetch", staged_s)
            return True

    def prefetch(self, s: int) -> bool:
        """Stage shard ``s`` ahead of use (double buffering). The transfer
        is dispatched without blocking, so it overlaps with whatever the
        caller computes next; a later ``get(s)`` finds the tile resident.
        Counts as a fault (it IS one H2D staging); returns True if a
        transfer was started, False if the tile was already resident."""
        return self._prefetch(s)

    def prefetch_compressed(self, s: int) -> bool:
        """``prefetch`` for the dict form (see ``get_compressed``)."""
        return self._prefetch(("c", s))

    def clear(self) -> None:
        with self._lock:
            self._tiles.clear()
            self._sizes.clear()
            self._prefetched.clear()
            self.resident_bytes = 0


class PackedTileCache(DeviceTileCache):
    """Every shard of ``storage`` staged as ONE lane-packed arena, for a
    host whose shards all stay resident in HBM.

    The shards' rows are concatenated in storage order, so shard ``s``
    starts at ``row_base(s)`` and ``get(s)`` returns the whole arena, to
    be addressed with row offsets rebased by that base. One tile shape per
    storage, padded only to the lane-packing multiple: shards of very
    different heights (COBS blocks follow the archive's document sizes)
    cost their own rows, not the tallest shard's each. Rows stay int32
    row indices, so an arena may pass 2^31 words.

    The arena stages on the first ``get`` or ``prefetch`` of any shard:
    one fault (a prefetch, if that staged it), which the observer sees for
    every shard; later gets are hits. Nothing is ever evicted."""

    _KEY = 0                       # the one entry of the LRU map

    def __init__(self, storage: ArenaStorage, device=None):
        super().__init__(storage, capacity_bytes=None, device=device)

    def row_base(self, s: int) -> int:
        return int(self.storage.shard_row_starts[s])

    def __len__(self) -> int:
        return self.storage.n_shards if self._tiles else 0

    @property
    def resident_shards(self) -> tuple[int, ...]:
        return tuple(range(len(self)))

    def _stage(self, s: int) -> PackedArena:
        """The whole storage, copied once into a host buffer already
        padded to whole 128-lane lines (so packing it is a reshape), then
        put on the device; the host buffer is freed once it is there."""
        st = self.storage
        rows, w = st.shape
        ws, per_line, _ = row_geometry(w)
        pad_rows = -(-rows // (per_line * SUBLANES)) * per_line * SUBLANES
        host = np.zeros((pad_rows, ws), dtype=np.uint32)
        starts = st.shard_row_starts
        for i in range(st.n_shards):
            host[starts[i]:starts[i + 1], :w] = st.shard_host(i)
        lines = self._put(pack_lines(host))
        lines.block_until_ready()
        return PackedArena(lines, rows, w)

    def _stage_all(self, event: str) -> PackedArena:
        t0 = time.perf_counter()
        tile = self._stage(self._KEY)
        staged_s = time.perf_counter() - t0
        self._tiles[self._KEY] = tile
        self._sizes[self._KEY] = tile.nbytes
        self.resident_bytes += tile.nbytes
        self.raw_bytes_staged += tile.nbytes
        for s in range(self.storage.n_shards):
            self.shard_faults[s] = self.shard_faults.get(s, 0) + 1
            self._notify(s, event, staged_s)
        return tile

    def get(self, s: int) -> PackedArena:
        """The packed arena holding shard ``s`` (from ``row_base(s)``)."""
        with self._lock:
            tile = self._tiles.get(self._KEY)
            if tile is None:
                self.faults += 1
                return self._stage_all("fault")
            self.hits += 1
            self.shard_hits[s] = self.shard_hits.get(s, 0) + 1
            if self._KEY in self._prefetched:
                self._prefetched.discard(self._KEY)
                self.prefetch_hits += 1
            self._notify(s, "hit")
            return tile

    def prefetch(self, s: int) -> bool:
        """Stage the arena if it is not resident; True if this staged it."""
        with self._lock:
            if self._KEY in self._tiles:
                return False
            self.faults += 1
            self.prefetched += 1
            self._prefetched.add(self._KEY)
            self._stage_all("prefetch")
            return True

    def get_compressed(self, s: int) -> tuple:
        raise ValueError("a packed arena holds raw rows only")

    prefetch_compressed = get_compressed
