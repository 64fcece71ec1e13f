"""Synthetic genomic corpora with the paper's key statistical property.

The 100k-microbial dataset that motivates COBS has *heavily skewed* document
sizes (min 0 k-mers, mean 3.4M, max 138M — a ~40x mean-to-max ratio). The
compact layout's entire advantage (Fig. 4) comes from that skew, so the
generator draws document lengths from a log-normal clipped to a [min, max]
range, giving the same staircase-vs-rectangle geometry at laptop scale.

Query sets mirror section 3 'Queries': true positives are substrings sampled
from indexed documents; true negatives are random strings verified to share
no k-mer with any document.
"""
from __future__ import annotations

import multiprocessing
import os
from dataclasses import dataclass, field

import numpy as np

from ..core import dna


def random_genome(rng: np.random.Generator, length: int) -> np.ndarray:
    """Uniform random 2-bit code string (uint8 [length])."""
    return rng.integers(0, 4, size=length, dtype=np.uint8)


def mutate(rng: np.random.Generator, codes: np.ndarray, rate: float) -> np.ndarray:
    """Point-mutate a fraction ``rate`` of bases (never to the same base)."""
    out = codes.copy()
    n_mut = int(len(codes) * rate)
    if n_mut == 0:
        return out
    pos = rng.choice(len(codes), size=n_mut, replace=False)
    out[pos] = (out[pos] + rng.integers(1, 4, size=n_mut, dtype=np.uint8)) % 4
    return out


@dataclass
class SyntheticCorpus:
    documents: list[np.ndarray]          # 2-bit code arrays
    doc_terms: list[np.ndarray]          # distinct packed k-mers per doc
    k: int
    canonical: bool = False
    names: list[str] = field(default_factory=list)

    @property
    def n_docs(self) -> int:
        return len(self.documents)

    def term_counts(self) -> np.ndarray:
        return np.array([t.shape[0] for t in self.doc_terms], dtype=np.int64)


def lognormal_lengths(rng: np.random.Generator, n_docs: int, *,
                      mean_length: int, sigma: float = 1.0,
                      min_length: int = 64, max_length: int | None = None
                      ) -> np.ndarray:
    """Document lengths in bases: log-normal around ``mean_length``,
    clipped to [min_length, max_length or 50 * mean_length]."""
    mu = np.log(mean_length) - sigma ** 2 / 2
    lengths = np.exp(rng.normal(mu, sigma, size=n_docs)).astype(np.int64)
    return np.clip(lengths, min_length, max_length or 50 * mean_length)


def make_corpus(
    n_docs: int,
    *,
    k: int = 31,
    mean_length: int = 2000,
    sigma: float = 1.0,
    min_length: int = 64,
    max_length: int | None = None,
    canonical: bool = False,
    seed: int = 0,
) -> SyntheticCorpus:
    """Log-normal document-size corpus (the paper's size-skew regime).

    sigma=1.0 gives roughly the 1-to-40 mean/max spread of the microbial set
    at a few thousand documents.
    """
    rng = np.random.default_rng(seed)
    lengths = lognormal_lengths(rng, n_docs, mean_length=mean_length,
                                sigma=sigma, min_length=min_length,
                                max_length=max_length)
    docs, terms = [], []
    for i in range(n_docs):
        g = random_genome(rng, int(lengths[i]))
        docs.append(g)
        terms.append(dna.document_terms([g], k, canonical))
    return SyntheticCorpus(docs, terms, k, canonical,
                           [f"doc{i:06d}" for i in range(n_docs)])


def doc_genome(seed: int, doc: int, length: int) -> np.ndarray:
    """Document ``doc``'s genome, reproducible from (seed, doc) alone — so
    documents generate in parallel and a query can be cut from one
    without keeping the corpus."""
    return random_genome(np.random.default_rng([seed, doc]), int(length))


def _docs_terms(args) -> list[np.ndarray]:
    seed, first, lengths, k = args
    return [dna.document_terms([doc_genome(seed, first + j, n)], k)
            for j, n in enumerate(lengths)]


def _numpy_only() -> None:
    # generation children never need a device; keep them off the one the
    # parent process holds
    os.environ["JAX_PLATFORMS"] = "cpu"


def make_corpus_terms(lengths: np.ndarray, *, k: int = 31, seed: int = 0,
                      workers: int = 1) -> list[np.ndarray]:
    """Distinct k-mers of every ``doc_genome(seed, i, lengths[i])``.

    With ``workers`` > 1 the documents are generated by that many fresh
    (spawned) NumPy-only processes — the bulk of the work holds the GIL,
    so threads would not scale. The result depends on ``seed`` only."""
    lengths = np.asarray(lengths, dtype=np.int64)
    step = max(1, len(lengths) // (8 * max(1, int(workers))))
    jobs = [(seed, i, lengths[i:i + step], k)
            for i in range(0, len(lengths), step)]
    if workers <= 1:
        parts = map(_docs_terms, jobs)
        return [t for part in parts for t in part]
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(int(workers), initializer=_numpy_only) as pool:
        return [t for part in pool.imap(_docs_terms, jobs) for t in part]


def make_queries(
    corpus: SyntheticCorpus,
    *,
    n_pos: int,
    n_neg: int,
    length: int,
    seed: int = 1,
) -> tuple[list[np.ndarray], np.ndarray]:
    """Query batch in random order with ground-truth labels.

    Returns (queries, origin) where origin[i] is the source document id for
    true positives and -1 for verified true negatives (section 3, Queries).
    """
    rng = np.random.default_rng(seed)
    k = corpus.k

    # k-mer membership structure over the whole corpus for negative checking
    all_terms = (np.concatenate(corpus.doc_terms, axis=0)
                 if corpus.doc_terms else np.zeros((0, 2), np.uint32))
    universe = set()
    if all_terms.shape[0]:
        u64 = (all_terms[:, 0].astype(np.uint64)
               | (all_terms[:, 1].astype(np.uint64) << np.uint64(32)))
        universe = set(u64.tolist())

    queries: list[np.ndarray] = []
    origin: list[int] = []

    long_enough = [i for i, d in enumerate(corpus.documents)
                   if len(d) >= max(length, k)]
    if n_pos and not long_enough:
        raise ValueError("no document long enough for positive queries")
    for _ in range(n_pos):
        d = int(rng.choice(long_enough))
        doc = corpus.documents[d]
        start = int(rng.integers(0, len(doc) - length + 1))
        queries.append(doc[start:start + length].copy())
        origin.append(d)

    def is_negative(codes: np.ndarray) -> bool:
        t = dna.pack_kmers(codes, k, corpus.canonical)
        u64 = (t[:, 0].astype(np.uint64)
               | (t[:, 1].astype(np.uint64) << np.uint64(32)))
        return not any(int(v) in universe for v in u64)

    made = 0
    while made < n_neg:
        cand = random_genome(rng, length)
        if is_negative(cand):
            queries.append(cand)
            origin.append(-1)
            made += 1

    perm = rng.permutation(len(queries))
    return [queries[i] for i in perm], np.array(origin, dtype=np.int64)[perm]
