"""Bloom filter construction for the bit-sliced index.

Builders are jit'd over (chunk, terms, width)-static shapes; the host-side
orchestration in index.py pads/chunks documents so only a handful of traces
occur per build. Bit layout convention used EVERYWHERE in this repo:

  bit-sliced matrix  M : uint32 [rows, doc_words]
  document d lives in   word d // 32, bit d % 32 (LSB-first)

so ``(M[r, d // 32] >> (d % 32)) & 1`` is Bloom bit r of document d.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import hashing

ROW_ALIGN = 512      # filter widths rounded up -> fewer jit traces, aligned rows
TERM_ALIGN = 1024    # least term-count padding for the build scatter
DOC_WORD_BITS = 32   # documents per packed word


def aligned_width(w: int, align: int = ROW_ALIGN) -> int:
    return max(align, ((w + align - 1) // align) * align)


@partial(jax.jit, static_argnames=("w", "n_hashes"))
def build_filters(terms: jnp.ndarray, n_terms: jnp.ndarray, w: int, n_hashes: int):
    """Build Bloom filters for a chunk of documents.

    terms:   uint32 [C, T, 2]  packed terms, padded along T
    n_terms: int32  [C]        number of valid terms per document
    returns  bool   [C, w]     one filter per document
    """
    C, T, _ = terms.shape
    h = hashing.hash_terms(terms, n_hashes)            # uint32 [C, T, k]
    rows = (h % jnp.uint32(w)).astype(jnp.int32)       # [C, T, k]
    valid = (jnp.arange(T, dtype=jnp.int32)[None, :] < n_terms[:, None])
    rows = jnp.where(valid[:, :, None], rows, w)       # dump row w for padding
    rows = rows.reshape(C, T * rows.shape[-1])
    filt = jnp.zeros((C, w + 1), dtype=bool)
    filt = filt.at[jnp.arange(C, dtype=jnp.int32)[:, None], rows].set(True)
    return filt[:, :w]


@jax.jit
def pack_doc_major(filters: jnp.ndarray) -> jnp.ndarray:
    """bool [C, w] -> uint32 [w, C // 32] bit-sliced block (C % 32 == 0).

    This is the transpose into the paper's bit-sliced layout: each output row
    holds one Bloom position across all documents of the block.
    """
    C, w = filters.shape
    assert C % DOC_WORD_BITS == 0, "pad doc count to a multiple of 32 first"
    f = filters.T.reshape(w, C // DOC_WORD_BITS, DOC_WORD_BITS)
    weights = (jnp.uint32(1) << jnp.arange(DOC_WORD_BITS, dtype=jnp.uint32))
    # bits are disjoint -> sum == bitwise or, stays exact in uint32
    return (f.astype(jnp.uint32) * weights).sum(axis=-1).astype(jnp.uint32)


def build_block_matrix(
    terms_list: list[np.ndarray],
    w: int,
    n_hashes: int,
    block_docs: int,
    max_chunk_bytes: int = 1 << 28,
) -> np.ndarray:
    """Build one sub-index block: uint32 [w, block_docs // 32].

    terms_list has <= block_docs documents; missing docs are empty columns
    (the paper pads the final block the same way). Documents are processed in
    chunks so the bool scatter buffer stays under max_chunk_bytes.
    """
    assert block_docs % DOC_WORD_BITS == 0
    n = len(terms_list)
    assert n <= block_docs
    chunk = max(DOC_WORD_BITS, min(block_docs, max_chunk_bytes // max(w, 1)))
    chunk = (chunk // DOC_WORD_BITS) * DOC_WORD_BITS
    parts = []
    for c0 in range(0, block_docs, chunk):
        c1 = min(c0 + chunk, block_docs)
        docs = terms_list[c0:min(c1, n)]
        counts = np.array([d.shape[0] for d in docs] + [0] * (c1 - c0 - len(docs)),
                          dtype=np.int32)
        t_max = int(counts.max()) if counts.size else 0
        # pow2 padding: a skewed corpus then compiles a few scatter shapes
        # per block width instead of one per chunk (each TPU compile of
        # a multi-million-term scatter takes seconds)
        t_pad = max(TERM_ALIGN, 1 << max(0, t_max - 1).bit_length())
        buf = np.zeros((c1 - c0, t_pad, 2), dtype=np.uint32)
        for i, d in enumerate(docs):
            buf[i, : d.shape[0]] = d
        filt = build_filters(jnp.asarray(buf), jnp.asarray(counts), w, n_hashes)
        parts.append(np.asarray(pack_doc_major(filt)))
    return np.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
