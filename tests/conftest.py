import os

# Smoke tests and benches must see ONE device; only launch/dryrun.py sets
# xla_force_host_platform_device_count (and only in its own process).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


@pytest.fixture(scope="session")
def small_corpus():
    from repro.data import make_corpus
    return make_corpus(64, k=15, mean_length=400, sigma=1.0, seed=7)


@pytest.fixture(scope="session")
def small_indexes(small_corpus):
    from repro.core import IndexParams, build_classic, build_compact
    params = IndexParams(n_hashes=1, fpr=0.3, kmer=15)
    classic = build_classic(small_corpus.doc_terms, params)
    compact = build_compact(small_corpus.doc_terms, params,
                            block_docs=32, row_align=64)
    return classic, compact
