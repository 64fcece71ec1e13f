"""The plain reference: COBS query semantics in NumPy, independent of the
program under test.

It imports nothing of ``repro``. Its inputs are what the benchmark itself
made: the documents' term counts, the arena bits (one uint32 [rows, 32]
array per 1024-document block) and the queries' 2-bit DNA codes. It
recomputes the compact layout from the term counts, k-merizes and hashes
each query itself, reads the query's row in every block, counts set bits
per document, and selects like the paper: every document at or above the
coverage cutoff, best first, or the k best by (score desc, document id).

The k-mer packing and the 32-bit murmur-style mix below restate the index
format the program documents (``core/dna.py``, ``core/hashing.py``): a
store whose rows were addressed any other way answers differently, and
the comparison says so.
"""
from __future__ import annotations

import math

import numpy as np

BLOCK_DOCS = 1024
DOC_WORDS = BLOCK_DOCS // 32
ROW_ALIGN = 512

_C1, _C2 = np.uint32(0xCC9E2D51), np.uint32(0x1B873593)
_F1, _F2 = np.uint32(0x85EBCA6B), np.uint32(0xC2B2AE35)


# -- index format -------------------------------------------------------------

def pack_kmers(codes: np.ndarray, k: int) -> np.ndarray:
    """All k-mers of a 2-bit code string as uint32 (lo, hi) pairs [n, 2]:
    base i of the k-mer at bits 2i of lo (i < 16) or 2(i-16) of hi."""
    codes = np.asarray(codes, dtype=np.uint32)
    n = codes.shape[0] - k + 1
    if n <= 0:
        return np.zeros((0, 2), np.uint32)
    out = np.zeros((n, 2), np.uint32)
    for i in range(k):
        word, shift = (0, 2 * i) if i < 16 else (1, 2 * (i - 16))
        out[:, word] |= codes[i:i + n] << np.uint32(shift)
    return out


def distinct_terms(codes: np.ndarray, k: int) -> np.ndarray:
    """The query's distinct k-mers (the paper's |G(P)|), any order."""
    t = pack_kmers(codes, k)
    if t.shape[0] == 0:
        return t
    as64 = t[:, 0].astype(np.uint64) | (t[:, 1].astype(np.uint64) << 32)
    return t[np.unique(as64, return_index=True)[1]]


def _rotl(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def hash_terms(terms: np.ndarray) -> np.ndarray:
    """One 32-bit hash per packed term (seed 0): uint32 [n]."""
    lo = terms[:, 0].astype(np.uint32)
    hi = terms[:, 1].astype(np.uint32)
    with np.errstate(over="ignore"):
        h = np.full(lo.shape, 0x2545F491, np.uint32)   # hash seed 0
        for word in (lo, hi):
            k = _rotl(word * _C1, 15) * _C2
            h = _rotl(h ^ k, 13) * np.uint32(5) + np.uint32(0xE6546B64)
        h = h ^ np.uint32(8)
        h = (h ^ (h >> np.uint32(16))) * _F1
        h = (h ^ (h >> np.uint32(13))) * _F2
        return h ^ (h >> np.uint32(16))


def block_width(max_terms: int, fpr: float) -> int:
    """Bloom rows of a one-hash block sized for its largest document at
    the target false-positive rate, rounded up to 512 rows."""
    w = max(1, math.ceil(-max(max_terms, 1) / math.log(1.0 - fpr)))
    return max(ROW_ALIGN, -(-w // ROW_ALIGN) * ROW_ALIGN)


def compact_layout(counts: np.ndarray, fpr: float):
    """COBS's compact layout from term counts: documents sorted by size
    (stable), 1024 to a block, each block as wide as its largest member
    needs. Returns (doc_slot int64 [n], block widths int64 [nb])."""
    order = np.argsort(counts, kind="stable")
    slot = np.empty(counts.shape[0], np.int64)
    slot[order] = np.arange(counts.shape[0])
    nb = -(-counts.shape[0] // BLOCK_DOCS)
    widths = np.array(
        [block_width(int(counts[order[b * BLOCK_DOCS:(b + 1) * BLOCK_DOCS]]
                         .max()), fpr) for b in range(nb)], np.int64)
    return slot, widths


# -- scoring ------------------------------------------------------------------

class Reference:
    """Scores queries against the benchmark's own arena bits."""

    def __init__(self, counts: np.ndarray, blocks: list, *, kmer: int,
                 fpr: float):
        self.kmer = kmer
        self.doc_slot, self.widths = compact_layout(np.asarray(counts), fpr)
        if [b.shape for b in blocks] != [(int(w), DOC_WORDS)
                                         for w in self.widths]:
            raise ValueError("arena blocks do not match the compact layout")
        self.blocks = blocks

    def scores(self, codes: np.ndarray, *, keep=None
               ) -> tuple[np.ndarray, int]:
        """Per-document scores int64 [n_docs] and the distinct term count.
        ``keep(n)``, a boolean mask over the n distinct terms, scores a subset
        of them; the control uses it, the exact reference never does."""
        terms = distinct_terms(codes, self.kmer)
        if keep is not None:
            terms = terms[keep(terms.shape[0])]
        n = terms.shape[0]
        if n == 0:
            return np.zeros(self.doc_slot.shape[0], np.int64), 0
        h = hash_terms(terms).astype(np.int64)
        per_block = []
        for blk, w in zip(self.blocks, self.widths):
            words = blk[h % w]                                    # [n, 32]
            bits = np.unpackbits(words.view(np.uint8), axis=-1,
                                 bitorder="little")               # [n, 1024]
            per_block.append(bits.sum(axis=0, dtype=np.int64))
        return np.concatenate(per_block)[self.doc_slot], n

    def expect(self, codes: np.ndarray, threshold: float, top_k: int,
               *, keep=None) -> tuple[np.ndarray, np.ndarray]:
        """(document ids, scores) the query must return, best first."""
        s, n = self.scores(codes, keep=keep)
        if n == 0:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        order = np.lexsort((np.arange(s.shape[0]), -s))
        if top_k:
            sel = order[:top_k]
        else:
            sel = order[s[order] >= max(1, math.ceil(threshold * n))]
        return sel, s[sel]


def every_other_term(n: int) -> np.ndarray:
    """The control's broken guarantee: score half of the distinct k-mers
    (a sampled sketch in place of exact containment)."""
    return np.arange(n) % 2 == 0
