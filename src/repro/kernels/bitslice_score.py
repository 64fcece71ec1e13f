"""Pallas TPU kernels for the COBS query hot loop.

The query's per-term work is: fetch the term's bit-sliced row (W uint32
words = 32W documents), and accumulate each document's bit into its int32
score. On the paper's CPU this is the SSE LUT expansion; on TPU we target
the VPU with three designs:

1. ``unpack`` — paper-faithful analogue: every row word is expanded to 32
   int32 lanes via shift-and-mask and summed. O(32) vector ops per word.
   BlockSpec tiles (term_block x word_block) keep the working set in VMEM.

2. ``vertical`` — beyond-paper: Harley–Seal style bit-sliced counters.
   Per word column we keep ceil(log2(L+1)) uint32 counter *planes*; adding a
   row is a ripple-carry (AND/XOR chain) across planes — O(2 log2 L) vector
   ops per word instead of O(32); the expensive 32-way expansion happens
   once at the end instead of once per term. For ell >= ~100 terms this cuts
   VPU work by 3-6x and is the preferred production path.

3. ``lookup`` (fused) — gathers rows straight from the arena in HBM using
   scalar-prefetched row indices, so the [L, W] gathered matrix never
   materializes in HBM. This is the TPU analogue of the paper's streaming
   row scan from NVMe: row -> VMEM tile -> accumulate.

All kernels share the oracle semantics of ref.bitslice_score_ref. Tile sizes
default to (8 terms x 128 words) = (sublane x lane) alignment; uint32 words
* 128 lanes = 4096 documents per tile column.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TERM_BLOCK = 8     # sublane-aligned
DEFAULT_WORD_BLOCK = 128   # lane-aligned


def _num_planes(n_terms: int) -> int:
    return max(1, (int(n_terms)).bit_length())


# --------------------------------------------------------------------------
# 1. unpack kernel (paper-faithful ADD step)
# --------------------------------------------------------------------------

def _unpack_kernel(rows_ref, out_ref):
    i_l = pl.program_id(1)
    block = rows_ref[...]                                   # uint32 [bl, bw]
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, None, :]
    bits = ((block[:, :, None] >> shifts) & jnp.uint32(1)).astype(jnp.int32)
    partial = bits.sum(axis=0)                              # int32 [bw, 32]

    @pl.when(i_l == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(i_l > 0)
    def _acc():
        out_ref[...] += partial


def unpack_score(
    rows: jnp.ndarray,
    *,
    term_block: int = DEFAULT_TERM_BLOCK,
    word_block: int = DEFAULT_WORD_BLOCK,
    interpret: bool = False,
) -> jnp.ndarray:
    """uint32 [L, W] -> int32 [W, 32]; L % term_block == W % word_block == 0."""
    L, W = rows.shape
    grid = (W // word_block, L // term_block)
    return pl.pallas_call(
        _unpack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((term_block, word_block), lambda iw, il: (il, iw))],
        out_specs=pl.BlockSpec((word_block, 32), lambda iw, il: (iw, 0)),
        out_shape=jax.ShapeDtypeStruct((W, 32), jnp.int32),
        interpret=interpret,
        name="bitslice_unpack",
    )(rows)


# --------------------------------------------------------------------------
# 2. vertical (Harley–Seal bit-sliced counter) kernel
# --------------------------------------------------------------------------

def _vertical_kernel(rows_ref, out_ref, planes_ref, *, n_planes: int,
                     term_block: int):
    i_l = pl.program_id(1)
    n_l = pl.num_programs(1)

    @pl.when(i_l == 0)
    def _init():
        planes_ref[...] = jnp.zeros_like(planes_ref)

    block = rows_ref[...]                                   # uint32 [bl, bw]

    # Ripple-carry each of the bl rows into the counter planes. The loop over
    # rows is unrolled (bl is small/static); each row costs 2*n_planes vector
    # bit-ops on [bw] lanes — this is the entire per-term inner loop.
    planes = [planes_ref[j, :] for j in range(n_planes)]
    for r in range(term_block):
        carry = block[r, :]
        for j in range(n_planes):
            new_carry = planes[j] & carry
            planes[j] = planes[j] ^ carry
            carry = new_carry
        # counts < 2^n_planes by construction; carry out of the top plane
        # cannot happen (n_planes = ceil(log2(L+1))).
    for j in range(n_planes):
        planes_ref[j, :] = planes[j]

    @pl.when(i_l == n_l - 1)
    def _expand():
        # one-time expansion: count[d] = sum_j bit_j(plane_j) << j
        shifts = jnp.arange(32, dtype=jnp.uint32)[None, :]
        acc = jnp.zeros(out_ref.shape, jnp.int32)
        for j in range(n_planes):
            bits = ((planes_ref[j, :][:, None] >> shifts) & jnp.uint32(1))
            acc += bits.astype(jnp.int32) << j
        out_ref[...] = acc


def vertical_score(
    rows: jnp.ndarray,
    *,
    term_block: int = DEFAULT_TERM_BLOCK,
    word_block: int = DEFAULT_WORD_BLOCK,
    interpret: bool = False,
) -> jnp.ndarray:
    """uint32 [L, W] -> int32 [W, 32] via bit-sliced vertical counters."""
    L, W = rows.shape
    n_planes = _num_planes(L)
    grid = (W // word_block, L // term_block)
    kernel = functools.partial(
        _vertical_kernel, n_planes=n_planes, term_block=term_block)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((term_block, word_block), lambda iw, il: (il, iw))],
        out_specs=pl.BlockSpec((word_block, 32), lambda iw, il: (iw, 0)),
        out_shape=jax.ShapeDtypeStruct((W, 32), jnp.int32),
        scratch_shapes=[pltpu.VMEM((n_planes, word_block), jnp.uint32)],
        interpret=interpret,
        name="bitslice_vertical",
    )(rows)


# --------------------------------------------------------------------------
# 3. row-gather kernels over a lane-packed arena
# --------------------------------------------------------------------------
#
# On TPU a [R, W] uint32 arena with W < 128 words is laid out with R on the
# lanes (no padding), and Mosaic can neither DMA a W-word slice of it nor
# read it as a row-major operand without XLA copying the whole arena first.
# The gather kernels therefore read the arena as 128-lane LINES: a row of
# ``ws`` stored words (W rounded up to a power of two below 128, or to a
# multiple of 128 above) occupies either one segment of a line
# (``per_line`` = 128 // ws rows per line) or ``lines`` = ws // 128 whole
# lines. ``repro.kernels.ops.pack_lines`` builds that [N, 128] view; for
# the paper's 1024-document blocks (W = 32) it is a free reshape of the
# host arena and holds the same bytes as the logical array.
#
# A term's row is fetched as the (8, 128) tile that contains its line (the
# smallest block the TPU pipeline accepts), the line is picked from the
# tile by sublane, and lanes outside the row's segment are masked off. The
# Harley-Seal planes then accumulate 128 lanes; at the end the per_line
# segments are folded (summed), which is exact because a row only ever
# contributes to its own segment. Outputs are int32 [..., ws, 32].
#
# Row indices and masks ride scalar prefetch (SMEM). The ops wrappers
# split a dispatch so that no [Q, nb, L] table outgrows scalar memory.

LANES = 128
SUBLANES = 8


def row_geometry(words: int) -> tuple[int, int, int]:
    """Logical row width in words -> (ws, per_line, lines): the stored
    row width, rows per 128-lane line and lines per row."""
    w = max(1, int(words))
    if w < LANES:
        ws = 1 << (w - 1).bit_length()
        return ws, LANES // ws, 1
    ws = -(-w // LANES) * LANES
    return ws, 1, ws // LANES


def _line_of(r, iw, per_line: int, lines: int):
    """Line index of word tile ``iw`` of row ``r`` (traced int32)."""
    if per_line > 1:
        return r // per_line
    return r * lines + iw


def _fetch(arena_ref, r, iw, *, per_line: int, lines: int, ws: int):
    """Row ``r``'s 128-lane word tile ``iw`` from its resident (8, 128)
    arena tile, with lanes of other rows in the same line zeroed."""
    sub = _line_of(r, iw, per_line, lines) % SUBLANES
    row = arena_ref[pl.ds(sub, 1), :]                       # uint32 [1, 128]
    if per_line > 1:
        seg = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) // ws
        row = jnp.where(seg == r % per_line, row, jnp.uint32(0))
    return row


def _ripple(planes, carry):
    """Add one row into Harley-Seal counter planes (list of [1, 128])."""
    out = []
    for p in planes:
        out.append(p ^ carry)
        carry = p & carry
    return out


def _expand(planes, ws: int) -> jnp.ndarray:
    """Counter planes -> int32 [min(ws, 128), 32] per-word bit counts,
    with the per_line segments of a line folded together."""
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :]
    acc = jnp.zeros((LANES, 32), jnp.int32)
    for j, p in enumerate(planes):
        bits = (p[0][:, None] >> shifts) & jnp.uint32(1)
        acc += bits.astype(jnp.int32) << j
    if ws < LANES:
        acc = sum(acc[s * ws:(s + 1) * ws] for s in range(LANES // ws))
    return acc


def _lookup_kernel(idx_ref, mask_ref, arena_ref, *rest, n_planes: int,
                   per_line: int, lines: int, ws: int, axes: tuple,
                   has_acc: bool):
    acc_ref = rest[0] if has_acc else None
    out_ref, planes_ref = rest[-2], rest[-1]
    iw, iq, ib = (pl.program_id(a) for a in axes)
    il = pl.program_id(3)

    @pl.when(il == 0)
    def _init():
        planes_ref[...] = jnp.zeros_like(planes_ref)

    r = idx_ref[iq, ib, il]
    row = _fetch(arena_ref, r, iw, per_line=per_line, lines=lines, ws=ws)
    row = row * mask_ref[iq, ib, il].astype(jnp.uint32)
    planes = _ripple([planes_ref[pl.ds(j, 1), :] for j in range(n_planes)],
                     row)
    for j in range(n_planes):
        planes_ref[pl.ds(j, 1), :] = planes[j]

    @pl.when(il == pl.num_programs(3) - 1)
    def _finish():
        counts = _expand([planes_ref[pl.ds(j, 1), :]
                          for j in range(n_planes)], ws)
        if has_acc:
            counts = counts + acc_ref[0, 0]
        out_ref[0, 0] = counts


GRID_ORDERS = ("wq", "qw")


def lookup_score_multi(
    arena_lines: jnp.ndarray,
    rows_idx: jnp.ndarray,
    mask: jnp.ndarray,
    acc: jnp.ndarray | None = None,
    *,
    per_line: int,
    ws: int,
    grid_order: str = "wq",
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused multi-query gather+score (the batched-serving hot loop).

    arena_lines uint32 [N, 128] (see ``row_geometry``); rows_idx int32
    [Q, nb, L] (term row per query per sub-index block); mask int32
    [Q, nb, L]; optional acc int32 [Q, nb, ws, 32] of running counts
    (the pruned executor's term chunks) -> int32 [Q, nb, ws, 32].

    Every (word tile, query, block) cell streams its L rows HBM->VMEM via
    scalar-prefetched indices into Harley-Seal planes in one VMEM scratch,
    so the [Q, nb, L, W] gather never exists. ``grid_order`` 'wq' puts the
    word tiles outermost, 'qw' the queries; both are bit-identical.
    """
    Q, nb, L = rows_idx.shape
    lines = max(1, ws // LANES)
    tw = min(ws, LANES)
    if grid_order == "wq":
        grid, axes = (lines, Q, nb, L), (0, 1, 2)
    elif grid_order == "qw":
        grid, axes = (Q, nb, lines, L), (2, 0, 1)
    else:
        raise ValueError(f"unknown grid_order {grid_order!r}; "
                         f"one of {GRID_ORDERS}")

    def cell(*g):
        return tuple(g[a] for a in axes) + (g[3],)

    def arena_map(*g):
        (iw, iq, ib, il), idx = cell(*g[:4]), g[4]
        r = idx[iq, ib, il]
        return _line_of(r, iw, per_line, lines) // SUBLANES, 0

    def out_map(*g):
        iw, iq, ib, _ = cell(*g[:4])
        return iq, ib, iw, 0

    out_spec = pl.BlockSpec((1, 1, tw, 32), out_map)
    in_specs = [pl.BlockSpec((SUBLANES, LANES), arena_map)]
    operands = [rows_idx, mask, arena_lines]
    if acc is not None:
        in_specs.append(out_spec)
        operands.append(acc)
    n_planes = _num_planes(L)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((n_planes, LANES), jnp.uint32)],
    )
    kernel = functools.partial(
        _lookup_kernel, n_planes=n_planes, per_line=per_line, lines=lines,
        ws=ws, axes=axes, has_acc=acc is not None)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, nb, ws, 32), jnp.int32),
        interpret=interpret,
        name="bitslice_lookup",
    )(*operands)


# --------------------------------------------------------------------------
# 4. batched row-dedup pair: unique-row gather + indirected score
# --------------------------------------------------------------------------
#
# Real serving batches share rows heavily (overlapping k-mers between
# queries), but the fused multi-query kernel re-DMAs an arena tile for
# every (query, block, term) grid cell. The dedup pair makes arena traffic
# scale with UNIQUE rows instead:
#
#   gather_rows   — streams each unique arena row HBM->VMEM exactly ONCE
#                   and writes the compact [U, lines * 128] matrix (each row
#                   in its own line, at its segment's lanes).
#   dedup_score   — scores every (query, block) cell against that matrix:
#                   the [U, 128] tile's index map depends only on the word
#                   axis, so it stays resident in VMEM across all (query,
#                   block) steps of a word tile; per term the kernel reads
#                   the indirection index from scalar memory and ripple-
#                   carries the VMEM row into Harley-Seal counter planes.
#
# Host-side planning (repro.core.query.plan_dedup_batch) builds the unique
# row list and the [Q, nb, L] indirection.


def _gather_kernel(idx_ref, arena_ref, out_ref, *, per_line: int,
                   lines: int, ws: int):
    iw, iu = pl.program_id(0), pl.program_id(1)
    row = _fetch(arena_ref, idx_ref[iu], iw, per_line=per_line, lines=lines,
                 ws=ws)
    out_ref[pl.ds(iu % SUBLANES, 1), :] = row


def gather_rows(
    arena_lines: jnp.ndarray,
    uniq_idx: jnp.ndarray,
    *,
    per_line: int,
    ws: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Unique-row gather: (arena_lines uint32 [N, 128], uniq_idx int32 [U],
    U % 8 == 0) -> uint32 [U, lines * 128], row u in line u at its arena
    segment's lanes (other lanes zero) — the input ``dedup_score`` folds."""
    U = uniq_idx.shape[0]
    lines = max(1, ws // LANES)

    def arena_map(iw, iu, idx):
        return _line_of(idx[iu], iw, per_line, lines) // SUBLANES, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(lines, U),
        in_specs=[pl.BlockSpec((SUBLANES, LANES), arena_map)],
        out_specs=pl.BlockSpec((SUBLANES, LANES),
                               lambda iw, iu, idx: (iu // SUBLANES, iw)),
    )
    kernel = functools.partial(_gather_kernel, per_line=per_line,
                               lines=lines, ws=ws)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((U, lines * LANES), jnp.uint32),
        interpret=interpret,
        name="bitslice_gather_rows",
    )(uniq_idx, arena_lines)


def _dedup_score_kernel(indir_ref, mask_ref, uniq_ref, *rest, n_planes: int,
                        n_terms: int, ws: int, has_acc: bool):
    out_ref = rest[-1]
    iq, ib = pl.program_id(1), pl.program_id(2)

    def add_term(il, planes):
        row = (uniq_ref[pl.ds(indir_ref[iq, ib, il], 1), :]
               * mask_ref[iq, ib, il].astype(jnp.uint32))
        return tuple(_ripple(list(planes), row))

    planes = tuple(jnp.zeros((1, LANES), jnp.uint32)
                   for _ in range(n_planes))
    planes = jax.lax.fori_loop(0, n_terms, add_term, planes)
    counts = _expand(planes, ws)
    if has_acc:
        counts = counts + rest[0][0, 0]
    out_ref[0, 0] = counts


def dedup_score(
    uniq: jnp.ndarray,
    indir: jnp.ndarray,
    mask: jnp.ndarray,
    acc: jnp.ndarray | None = None,
    *,
    ws: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Indirected multi-query score over a unique-row matrix.

    uniq uint32 [U, lines * 128] (from ``gather_rows``, or host-gathered
    rows at lane 0); indir int32 [Q, nb, L] (index into uniq per term);
    mask int32 [Q, nb, L]; optional acc int32 [Q, nb, ws, 32] ->
    int32 [Q, nb, ws, 32].

    The [U, 128] block's index map depends only on the word axis, so the
    pipeline re-DMAs it ONLY when the word tile changes — every (query,
    block) cell of a word tile scores against the same resident VMEM copy.
    The caller keeps U within VMEM (``repro.kernels.ops``).
    """
    U = uniq.shape[0]
    Q, nb, L = indir.shape
    lines = max(1, ws // LANES)
    tw = min(ws, LANES)
    out_spec = pl.BlockSpec((1, 1, tw, 32),
                            lambda iw, iq, ib, ind, msk: (iq, ib, iw, 0))
    in_specs = [pl.BlockSpec((U, LANES),
                             lambda iw, iq, ib, ind, msk: (0, iw))]
    operands = [indir, mask, uniq]
    if acc is not None:
        in_specs.append(out_spec)
        operands.append(acc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(lines, Q, nb),
        in_specs=in_specs,
        out_specs=out_spec,
    )
    kernel = functools.partial(_dedup_score_kernel, n_planes=_num_planes(L),
                               n_terms=L, ws=ws, has_acc=acc is not None)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, nb, ws, 32), jnp.int32),
        interpret=interpret,
        name="bitslice_dedup_score",
    )(*operands)
