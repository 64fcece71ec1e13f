"""jit'd public wrappers around the Pallas kernels.

Handles padding to tile boundaries, method selection, and CPU fallback:
on a CPU backend the kernels run under ``interpret=True`` (bit-exact
execution of the kernel body); on TPU they compile to Mosaic. ``method=
'ref'`` bypasses Pallas entirely (pure jnp oracle) — useful under vmap-heavy
query batching and as the ground truth in tests.

Tile-shape knobs (``word_block``, ``term_block``, ``grid_order``) default
to ``None`` = the kernel defaults; the serving planner threads measured
choices from ``repro.kernels.autotune`` through these parameters, so a
tuned configuration reaches every call site without baked-in constants.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import bitslice_score as _k
from . import ref as _ref

METHODS = ("ref", "unpack", "vertical", "lookup")


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_axis(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _word_block(W: int, word_block: int | None) -> int:
    wb = _k.DEFAULT_WORD_BLOCK if word_block is None else int(word_block)
    return min(wb, max(8, W))  # small-index friendliness


@functools.partial(jax.jit, static_argnames=("method", "interpret",
                                             "word_block", "term_block"))
def bitslice_score(
    rows: jnp.ndarray, method: str = "vertical", interpret: bool | None = None,
    word_block: int | None = None, term_block: int | None = None,
) -> jnp.ndarray:
    """Score ADD step: uint32 [L, W] (masked rows) -> int32 [W * 32].

    Invalid/padded terms must already be zeroed; zero rows contribute zero.
    """
    if interpret is None:
        interpret = _use_interpret()
    L, W = rows.shape
    if method == "ref":
        return _ref.bitslice_score_ref(rows)
    tb = _k.DEFAULT_TERM_BLOCK if term_block is None else int(term_block)
    wb = _word_block(W, word_block)
    padded = _pad_axis(_pad_axis(rows, 0, tb), 1, wb)
    if method == "unpack":
        out = _k.unpack_score(padded, term_block=tb, word_block=wb,
                              interpret=interpret)
    elif method == "vertical":
        out = _k.vertical_score(padded, term_block=tb, word_block=wb,
                                interpret=interpret)
    else:
        raise ValueError(f"unknown method {method!r}; one of {METHODS}")
    return out[:W].reshape(-1)


# --------------------------------------------------------------------------
# lane-packed arenas (the device form the row-gather kernels read)
# --------------------------------------------------------------------------


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["lines"], meta_fields=["rows", "words"])
@dataclasses.dataclass(frozen=True)
class PackedArena:
    """A [rows, words] uint32 arena held as 128-lane lines (uint32
    [N, 128], see ``bitslice_score.row_geometry``) — the form the device
    tile cache stages, so the row-gather kernels never relayout it. It
    passes through ``jax.jit`` as a pytree; ``shape`` is the logical
    one."""
    lines: jnp.ndarray
    rows: int
    words: int

    @property
    def shape(self) -> tuple[int, int]:
        return (self.rows, self.words)

    @property
    def dtype(self):
        return self.lines.dtype

    @property
    def nbytes(self) -> int:
        return int(self.lines.nbytes)


def pack_lines(arena, xp=np):
    """Logical arena [R, W] -> uint32 lines [N, 128] (N a multiple of 8),
    with ``xp`` numpy (host staging) or jax.numpy (traced). A plain reshape
    when W is a power of two and R fills whole tiles."""
    R, W = arena.shape
    ws, per_line, _ = _k.row_geometry(W)
    rows = -(-R // per_line) * per_line
    if (rows, ws) != (R, W):
        arena = xp.pad(arena, ((0, rows - R), (0, ws - W)))
    lines = arena.reshape(-1, _k.LANES)
    n = -(-lines.shape[0] // _k.SUBLANES) * _k.SUBLANES
    if n != lines.shape[0]:
        lines = xp.pad(lines, ((0, n - lines.shape[0]), (0, 0)))
    return lines


def pack_arena(arena) -> PackedArena:
    """Any [R, W] arena (host or device) -> PackedArena (traceable)."""
    if isinstance(arena, PackedArena):
        return arena
    R, W = arena.shape
    return PackedArena(pack_lines(jnp.asarray(arena), jnp), R, W)


def take_rows(arena, rows: jnp.ndarray) -> jnp.ndarray:
    """``arena[rows]`` for a logical or packed arena: uint32 [..., W]."""
    if not isinstance(arena, PackedArena):
        return arena[rows]
    ws, per_line, lines = _k.row_geometry(arena.words)
    if per_line > 1:
        g = arena.lines[rows // per_line]               # [..., 128]
        g = g.reshape(*rows.shape, per_line, ws)
        seg = (rows % per_line)[..., None, None]
        g = jnp.take_along_axis(g, seg, axis=-2)[..., 0, :]
    else:
        g = arena.lines[rows[..., None] * lines + jnp.arange(lines)]
        g = g.reshape(*rows.shape, ws)
    return g[..., :arena.words]


# --------------------------------------------------------------------------
# scalar- and vector-memory bounds of one kernel call
# --------------------------------------------------------------------------

# Entries per scalar-prefetched int32 table ([Q, nb, L] indices or masks)
# in one pallas_call. TPU scalar memory is 1 MiB and holds both tables;
# larger dispatches are split over queries, blocks and terms.
SMEM_TABLE_ENTRIES = 1 << 15

# VMEM bytes the dedup kernel's resident [U, 128] unique-row tile may take
# (double-buffered). Batches with more unique rows score the gathered
# matrix through the lookup kernel instead, which streams it from HBM.
DEDUP_VMEM_BYTES = 4 << 20


def _over_cells(call, idx, mask, acc=None):
    """Run ``call(idx, mask, acc) -> int32 [q, b, ws, 32]`` over pieces of
    the [Q, nb, L] tables that fit ``SMEM_TABLE_ENTRIES``. Term pieces
    add up (or thread ``acc``), so the result is exact."""
    Q, nb, L = idx.shape
    if Q * nb * L <= SMEM_TABLE_ENTRIES:
        return call(idx, mask, acc)
    lc = min(L, SMEM_TABLE_ENTRIES)
    bc = min(nb, max(1, SMEM_TABLE_ENTRIES // lc))
    qc = min(Q, max(1, SMEM_TABLE_ENTRIES // (bc * lc)))
    rows = []
    for q0 in range(0, Q, qc):
        cols = []
        for b0 in range(0, nb, bc):
            cell = (slice(q0, q0 + qc), slice(b0, b0 + bc))
            run = None if acc is None else acc[cell]
            for l0 in range(0, L, lc):
                piece = cell + (slice(l0, l0 + lc),)
                if acc is not None:
                    run = call(idx[piece], mask[piece], run)
                else:
                    part = call(idx[piece], mask[piece], None)
                    run = part if run is None else run + part
            cols.append(run)
        rows.append(jnp.concatenate(cols, axis=1))
    return jnp.concatenate(rows, axis=0)


def _lookup(arena, idx, mask, acc, interpret, grid_order="wq"):
    """Fused gather+score of a packed arena: int32 [Q, nb, ws, 32]."""
    ws, per_line, _ = _k.row_geometry(arena.words)
    return _over_cells(
        lambda i, m, a: _k.lookup_score_multi(
            arena.lines, i, m, a, per_line=per_line, ws=ws,
            grid_order=grid_order, interpret=interpret),
        idx.astype(jnp.int32), mask.astype(jnp.int32), acc)


def _score_unique(uniq, ws, indir, mask, acc, interpret):
    """Score a unique-row matrix uint32 [U, lines * 128] through ``indir``:
    the VMEM-resident dedup kernel when it fits, else the lookup kernel
    reading the matrix as an arena of one row per line."""
    indir, mask = indir.astype(jnp.int32), mask.astype(jnp.int32)
    U = uniq.shape[0]
    if U * uniq.shape[1] * 4 * 2 <= DEDUP_VMEM_BYTES:
        return _over_cells(
            lambda i, m, a: _k.dedup_score(uniq, i, m, a, ws=ws,
                                           interpret=interpret),
            indir, mask, acc)
    return _over_cells(
        lambda i, m, a: _k.lookup_score_multi(
            uniq.reshape(-1, _k.LANES), i, m, a, per_line=1, ws=ws,
            interpret=interpret),
        indir, mask, acc)


def _lane_rows(rows: jnp.ndarray) -> jnp.ndarray:
    """Logical rows uint32 [U, W] -> [U_pad, lines * 128] at lane 0 (the
    unique-row matrix layout), U padded to a multiple of 8."""
    U, W = rows.shape
    lines = _k.row_geometry(W)[2]
    u_pad = -(-U // _k.SUBLANES) * _k.SUBLANES
    return jnp.pad(rows, ((0, u_pad - U), (0, lines * _k.LANES - W)))


def _slots(out: jnp.ndarray, W: int) -> jnp.ndarray:
    """int32 [Q, nb, ws, 32] -> [Q, nb * W * 32] in slot order."""
    return out[:, :, :W].reshape(out.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("interpret", "word_block"))
def bitslice_lookup_score(
    arena,
    rows_idx: jnp.ndarray,
    mask: jnp.ndarray,
    interpret: bool | None = None,
    word_block: int | None = None,
) -> jnp.ndarray:
    """Fused gather+score from the arena ([R, W] or PackedArena; rows_idx
    and mask int32 [L]) -> int32 [W * 32]. The lookup family reads whole
    128-lane lines, so ``word_block`` only steers the vertical/unpack
    kernels and is accepted here for a uniform call surface."""
    return bitslice_lookup_score_multi(arena, rows_idx[None, None],
                                       mask[None, None],
                                       interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("interpret", "word_block"))
def bitslice_lookup_score_blocks(
    arena,
    rows_idx: jnp.ndarray,
    mask: jnp.ndarray,
    interpret: bool | None = None,
    word_block: int | None = None,
) -> jnp.ndarray:
    """Multi-block fused gather+score: (arena, rows_idx [nb, L], mask
    [nb, L]) -> int32 [nb * W * 32] in (block, word, bit) slot order."""
    return bitslice_lookup_score_multi(arena, rows_idx[None], mask[None],
                                       interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("interpret", "word_block",
                                             "grid_order"))
def bitslice_lookup_score_multi(
    arena,
    rows_idx: jnp.ndarray,
    mask: jnp.ndarray,
    interpret: bool | None = None,
    word_block: int | None = None,
    grid_order: str = "wq",
) -> jnp.ndarray:
    """Multi-query multi-block fused gather+score: (arena [R, W] or
    PackedArena, rows_idx [Q, nb, L], mask [Q, nb, L]) -> int32
    [Q, nb * W * 32], each query in (block, word, bit) slot order — the
    serving batch hot path."""
    if interpret is None:
        interpret = _use_interpret()
    arena = pack_arena(arena)
    return _slots(_lookup(arena, rows_idx, mask, None, interpret,
                          grid_order), arena.words)


def _gather_unique(arena: PackedArena, uniq_rows, interpret):
    """uniq_rows int32 [U] or [U, k] -> unique-row matrix (k rows ANDed)."""
    ws, per_line, _ = _k.row_geometry(arena.words)
    uniq_rows = uniq_rows.astype(jnp.int32)
    cols = uniq_rows[:, None] if uniq_rows.ndim == 1 else uniq_rows
    U = cols.shape[0]
    cols = jnp.pad(cols, ((0, (-U) % _k.SUBLANES), (0, 0)))
    uniq = None
    for j in range(cols.shape[1]):
        g = _k.gather_rows(arena.lines, cols[:, j], per_line=per_line,
                           ws=ws, interpret=interpret)
        uniq = g if uniq is None else uniq & g
    return uniq, ws


@functools.partial(jax.jit, static_argnames=("interpret", "word_block"))
def bitslice_lookup_score_dedup(
    arena,
    uniq_rows: jnp.ndarray,
    indir: jnp.ndarray,
    mask: jnp.ndarray,
    interpret: bool | None = None,
    word_block: int | None = None,
) -> jnp.ndarray:
    """Row-dedup batched gather+score: (arena, uniq_rows [U] or [U, k],
    indir [Q, nb, L], mask [Q, nb, L]) -> int32 [Q, nb * W * 32].

    Two kernels: ``gather_rows`` streams each unique arena row from HBM
    exactly once into a compact unique-row matrix; ``dedup_score``
    accumulates every query through the indirection against that matrix
    (resident in VMEM per word tile). Arena DMA traffic is U row tiles
    instead of the fused path's Q*nb*L. Semantics ==
    ``bitslice_lookup_score_multi(arena, uniq_rows[indir], mask)``,
    property-tested bit-identical.

    For k>1 indexes ``uniq_rows`` is [U, k]: each unique entry is a
    (row-set) tuple whose k gathered rows are AND-reduced on device before
    scoring, so shared row-SETS between queries collapse to one gather +
    one AND each.
    """
    if interpret is None:
        interpret = _use_interpret()
    arena = pack_arena(arena)
    uniq, ws = _gather_unique(arena, uniq_rows, interpret)
    return _slots(_score_unique(uniq, ws, indir, mask, None, interpret),
                  arena.words)


@functools.partial(jax.jit, static_argnames=("interpret", "word_block",
                                             "grid_order"))
def bitslice_lookup_score_multi_comp(
    dict_rows,
    refs: jnp.ndarray,
    rows_idx: jnp.ndarray,
    mask: jnp.ndarray,
    interpret: bool | None = None,
    word_block: int | None = None,
    grid_order: str = "wq",
) -> jnp.ndarray:
    """``bitslice_lookup_score_multi`` over a rowdict-compressed arena:
    (dict [D, W], refs [R], rows_idx [Q, nb, L], mask [Q, nb, L]) ->
    int32 [Q, nb * W * 32]. ``refs[rows_idx]`` is composed on the device
    before the call, so the kernel gathers dict rows directly and the
    refs table never rides scalar memory."""
    return bitslice_lookup_score_multi(dict_rows, refs[rows_idx], mask,
                                       interpret=interpret,
                                       grid_order=grid_order)


@functools.partial(jax.jit, static_argnames=("interpret", "word_block"))
def bitslice_lookup_score_blocks_comp(
    dict_rows,
    refs: jnp.ndarray,
    rows_idx: jnp.ndarray,
    mask: jnp.ndarray,
    interpret: bool | None = None,
    word_block: int | None = None,
) -> jnp.ndarray:
    """``bitslice_lookup_score_blocks`` over a rowdict-compressed arena:
    int32 [nb * W * 32]."""
    return bitslice_lookup_score_blocks(dict_rows, refs[rows_idx], mask,
                                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "word_block"))
def bitslice_lookup_score_dedup_comp(
    dict_rows,
    refs: jnp.ndarray,
    uniq_rows: jnp.ndarray,
    indir: jnp.ndarray,
    mask: jnp.ndarray,
    interpret: bool | None = None,
    word_block: int | None = None,
) -> jnp.ndarray:
    """``bitslice_lookup_score_dedup`` over a rowdict-compressed arena:
    each unique row (or k>1 row-set) is gathered from the dict at
    ``refs[uniq_rows]``. int32 [Q, nb * W * 32]."""
    return bitslice_lookup_score_dedup(dict_rows, refs[uniq_rows], indir,
                                       mask, interpret=interpret)


# --------------------------------------------------------------------------
# chunked pruned-scoring wrappers (branch-and-bound executor support)
# --------------------------------------------------------------------------
#
# The pruned executor (repro.core.query.run_paged_pruned) scores terms in
# chunks and keeps a persistent per-(query, block) running-count buffer per
# shard. Each wrapper returns (new_acc, block_max) where block_max int32
# [Q, nb] is the per-block maximum running count — the executor's survivor
# bound ``block_max + terms_remaining < required`` consumes only that tiny
# array host-side, the acc itself stays on device between chunks.


def chunk_acc_init(q: int, nb: int, w: int,
                   word_block: int | None = None) -> jnp.ndarray:
    """Fresh running-count buffer int32 [Q, nb, ws, 32] with the word axis
    at the kernels' stored row width (stable across chunk calls)."""
    ws, _, _ = _k.row_geometry(w)
    return jnp.zeros((q, nb, ws, 32), jnp.int32)


def chunk_acc_scores(acc: jnp.ndarray, w: int) -> jnp.ndarray:
    """Finished running counts -> int32 [Q, nb * W * 32] in the engine's
    (block, word, bit) slot order."""
    return _slots(acc, w)


def _with_max(out):
    return out, jnp.max(out, axis=(2, 3))


@functools.partial(jax.jit, static_argnames=("interpret", "word_block"))
def bitslice_chunk_score_dedup(
    uniq: jnp.ndarray,
    indir: jnp.ndarray,
    mask: jnp.ndarray,
    acc: jnp.ndarray,
    interpret: bool | None = None,
    word_block: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One term chunk against a gathered unique-row matrix.

    uniq uint32 [U, W] (only the chunk's touched rows were read from the
    store — for k>1 the rows are pre-ANDed); indir/mask int32 [Q, nb, Lc];
    acc int32 [Q, nb, ws, 32]. Returns (acc + chunk counts, per-block max
    int32 [Q, nb])."""
    if interpret is None:
        interpret = _use_interpret()
    return _with_max(_score_unique(_lane_rows(uniq), acc.shape[2], indir,
                                   mask, acc, interpret))


@functools.partial(jax.jit, static_argnames=("interpret", "word_block"))
def bitslice_chunk_score_multi(
    arena,
    rows_idx: jnp.ndarray,
    mask: jnp.ndarray,
    acc: jnp.ndarray,
    interpret: bool | None = None,
    word_block: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One term chunk fused-gathered from a resident shard tile (the
    promoted path: tile already staged, chunk rows stream out of HBM).
    Returns (acc + chunk counts, per-block max int32 [Q, nb])."""
    if interpret is None:
        interpret = _use_interpret()
    return _with_max(_lookup(pack_arena(arena), rows_idx, mask, acc,
                             interpret))


@functools.partial(jax.jit, static_argnames=("interpret", "word_block"))
def bitslice_chunk_score_multi_comp(
    dict_rows,
    refs: jnp.ndarray,
    rows_idx: jnp.ndarray,
    mask: jnp.ndarray,
    acc: jnp.ndarray,
    interpret: bool | None = None,
    word_block: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One term chunk gathered from a resident (dict, refs) pair at
    ``refs[rows_idx]`` — the compressed promoted path. Returns (acc',
    per-block max)."""
    return bitslice_chunk_score_multi(dict_rows, refs[rows_idx], mask, acc,
                                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k",))
def chunk_topk_lower(acc: jnp.ndarray, k: int) -> jnp.ndarray:
    """Per-query k-th-largest running counts of one shard's buffer: int32
    [Q, k] (descending). Running counts are LOWER bounds on final scores,
    so merging these across shards gives a sound, ever-tightening top-k
    pruning cutoff."""
    q = acc.shape[0]
    flat = acc.reshape(q, -1)
    kk = min(int(k), flat.shape[1])
    vals, _ = jax.lax.top_k(flat, kk)
    return vals


def and_rows(rows: jnp.ndarray) -> jnp.ndarray:
    """AND over the k hash rows: uint32 [L, k, W] -> [L, W] (jnp; XLA fuses
    this into the surrounding gather — measured no win from a kernel)."""
    return _ref.and_rows_ref(rows)


@jax.jit
def gather_and_rows(arena: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """Device-side row-set materialization for the promoted k>1 pruned
    path: uint32 tile [R, W] resident in HBM, rows int32 [U, k] ->
    uint32 [U, W] with the k hash rows of each set ANDed in place.

    This replaces the unpromoted path's host mmap reads once a tile is
    staged — the gather streams out of HBM and XLA fuses the AND into it,
    so post-promotion chunks never touch the host arena again."""
    g = take_rows(arena, rows)                    # [U, k, W]
    out = g[:, 0]
    for i in range(1, g.shape[1]):
        out = out & g[:, i]
    return out


@jax.jit
def gather_and_rows_comp(dict_rows: jnp.ndarray, refs: jnp.ndarray,
                         rows: jnp.ndarray) -> jnp.ndarray:
    """``gather_and_rows`` against a resident (dict, refs) pair: the
    double gather decodes rowdict-coded rows on the fly, HBM traffic
    proportional to the dictionary instead of the expanded tile."""
    g = take_rows(dict_rows, refs[rows])          # [U, k, W]
    out = g[:, 0]
    for i in range(1, g.shape[1]):
        out = out & g[:, i]
    return out


def bulk_query_chunk(nb: int, w: int, *, word_block: int | None = None,
                     budget_bytes: int = 32 * 2**20, floor: int = 8,
                     cap: int = 512) -> int:
    """Query-chunk size for the shard-major bulk executor.

    The bulk lane scores the whole query set against one resident tile in
    slabs of Qc queries; the dominant live buffer is the running-count
    accumulator int32 [Qc, nb, ws, 32], so Qc is chosen to keep that under
    ``budget_bytes`` (a conservative stand-in for the VMEM/HBM slice the
    chunk kernels can hold). Rounded down to a power of two so every slab
    of a sweep shares one compiled kernel shape (the last slab is padded
    up, never down)."""
    ws, _, _ = _k.row_geometry(w)
    per_q = max(1, nb * ws * 32 * 4)
    q = max(int(floor), int(budget_bytes) // per_q)
    q = 1 << (q.bit_length() - 1)                 # pow2 floor
    return int(min(int(cap), max(int(floor), q)))
