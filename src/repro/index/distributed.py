"""Mesh-sharded COBS query engine.

Sharding layout (TPU adaptation of the paper's external-memory scan):

* arena columns (packed document words) shard over the ``doc_axes``
  (("pod", "data") on the production mesh) — every chip scans only its own
  documents; this is the embarrassingly-parallel axis and carries ZERO
  communication until result selection.
* arena rows optionally shard over ``row_axis`` ("model") — each chip holds
  a horizontal stripe of the Bloom rows; a term's row lives on exactly one
  stripe, partial scores are psum'd over the row axis. Row sharding requires
  n_hashes == 1 (the paper's default): with k > 1 the AND over hash rows
  does not commute with the score reduction across stripes.

Result selection is a distributed top-k: per-shard lax.top_k of local
document scores, all_gather of (score, global_slot) candidates over the
document axes, then a final top-k — O(shards * topk) bytes, negligible next
to the row scan.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..core import dna, hashing
from ..core.index import BitSlicedIndex
from ..core.query import plan_rows
from ..kernels import ops


def _pad_to(x: np.ndarray, axis: int, multiple: int) -> np.ndarray:
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return np.pad(x, widths)


class DistributedIndex:
    """A BitSlicedIndex resident on a device mesh.

    doc_axes: mesh axes sharding the document (word-column) dimension.
    row_axis: optional mesh axis sharding the Bloom-row dimension.
    """

    def __init__(self, index: BitSlicedIndex, mesh: Mesh,
                 doc_axes: tuple[str, ...] = ("data",),
                 row_axis: str | None = None,
                 score_method: str = "vertical",
                 score_dtype=jnp.int32):
        if row_axis is not None and index.params.n_hashes != 1:
            raise ValueError("row sharding requires n_hashes == 1 "
                             "(AND over hashes does not commute with psum)")
        self.mesh = mesh
        self.doc_axes = tuple(doc_axes)
        self.row_axis = row_axis
        self.params = index.params
        self.score_method = score_method
        # int16 halves the psum bytes over the row axis; safe while
        # ell <= 32767 (§Perf cell C iteration)
        self.score_dtype = score_dtype
        self.n_docs = index.n_docs
        self.block_docs_orig = index.block_docs

        n_doc_shards = math.prod(mesh.shape[a] for a in self.doc_axes)
        n_row_shards = mesh.shape[row_axis] if row_axis else 1

        # full_host reads mmap'd shards directly — index.arena would first
        # concatenate an out-of-core index dense in device memory
        arena = index.storage.full_host()
        arena = _pad_to(arena, 1, n_doc_shards)       # pad doc words
        arena = _pad_to(arena, 0, n_row_shards)       # pad rows (zeros, never
        self.doc_words = arena.shape[1]               # addressed by queries)
        self.total_rows = arena.shape[0]
        self.row_stripe = self.total_rows // n_row_shards
        self.words_local = self.doc_words // n_doc_shards
        self.n_blocks = index.n_blocks
        self.slots_per_block = self.doc_words * 32

        spec = P(self.row_axis, self.doc_axes if len(self.doc_axes) > 1
                 else self.doc_axes[0])
        self.arena = jax.device_put(arena, NamedSharding(mesh, spec))
        rep = NamedSharding(mesh, P())
        self.row_offset = jax.device_put(np.asarray(index.row_offset), rep)
        self.block_width = jax.device_put(np.asarray(index.block_width), rep)
        self.doc_slot = np.asarray(index.doc_slot)
        # original-id lookup: slot -> doc id (-1 for padding slots)
        self.slot_doc = np.full(self.n_blocks * self.slots_per_block, -1,
                                dtype=np.int64)
        # doc i sits at slot: block*B_orig + pos, where B_orig = orig block
        # width*32. After column padding the per-block slot capacity grew, so
        # remap: orig slot (b, pos) -> padded slot b*slots_per_block + pos.
        b = self.doc_slot // index.block_docs
        pos = self.doc_slot % index.block_docs
        padded_slots = b * self.slots_per_block + pos
        self.slot_doc[padded_slots] = np.arange(index.n_docs)
        self._padded_doc_slot = padded_slots  # int64 [n_docs]
        # score_fn() output is SHARD-major (shard_map stitches per-shard
        # [nb*Wl*32] score vectors along the doc axis):
        #   flat = shard*(nb*Wl*32) + block*(Wl*32) + word_local*32 + bit
        word, bit = pos // 32, pos % 32
        shard_of = word // self.words_local
        word_l = word % self.words_local
        per_shard = self.n_blocks * self.words_local * 32
        self._flat_doc_slot = (shard_of * per_shard + b * self.words_local * 32
                               + word_l * 32 + bit)
        self._score_jit = None
        self._topk_jit = {}

    # ------------------------------------------------------------------
    def _shard_body(self, topk: int | None):
        n_hashes = self.params.n_hashes
        nb = self.n_blocks
        row_axis, doc_axes = self.row_axis, self.doc_axes
        row_stripe = self.row_stripe
        words_local = self.words_local
        slots_per_block = self.slots_per_block
        method = self.score_method
        sdtype = self.score_dtype

        def one_query(arena_l, row_offset, block_width, terms, n_valid):
            L = terms.shape[0]
            h = hashing.hash_terms(terms, n_hashes)            # [L, k]
            rows = plan_rows(h, row_offset, block_width)       # [L, k, nb]
            valid = jnp.arange(L, dtype=jnp.int32) < n_valid
            if row_axis is not None:
                m = jax.lax.axis_index(row_axis)
                base = (m * row_stripe).astype(jnp.int32)
                local = rows - base
                own = (local >= 0) & (local < row_stripe)
                local = jnp.clip(local, 0, row_stripe - 1)
            else:
                local, own = rows, None
            if method == "lookup" and n_hashes == 1:
                # fused path: rows stream straight from the arena shard —
                # the [L, nb, Wl] gathered copy never materializes
                idx = local[:, 0].T                            # [nb, L]
                msk = jnp.broadcast_to(valid[None, :], idx.shape)
                if own is not None:
                    msk = msk & own[:, 0].T
                scores = ops.bitslice_lookup_score_blocks(
                    arena_l, idx, msk.astype(jnp.int32))
                return scores.astype(sdtype)
            g = arena_l[local]                                 # [L,k,nb,Wl]
            if own is not None:
                g = jnp.where(own[..., None], g, jnp.uint32(0))
            anded = g[:, 0]
            for i in range(1, n_hashes):
                anded = anded & g[:, i]
            anded = jnp.where(valid[:, None, None], anded, jnp.uint32(0))
            flat = anded.reshape(L, nb * words_local)
            m_ = "vertical" if method == "lookup" else method
            return ops.bitslice_score(flat, method=m_).astype(sdtype)

        def body(arena_l, row_offset, block_width, terms, n_valid):
            scores = jax.vmap(one_query, in_axes=(None, None, None, 0, 0))(
                arena_l, row_offset, block_width, terms, n_valid)
            if row_axis is not None:
                scores = jax.lax.psum(scores, row_axis)        # [Q, local]
            if topk is None:
                return scores
            # ---- distributed top-k over the document axes ----
            q, n_local = scores.shape
            k = min(topk, n_local)
            vals, idx = jax.lax.top_k(scores, k)               # [Q, k]
            d = jax.lax.axis_index(doc_axes)                   # flat doc rank
            blk = idx // (words_local * 32)
            rem = idx % (words_local * 32)
            word_l, bit = rem // 32, rem % 32
            gslot = (blk * slots_per_block
                     + (d * words_local + word_l) * 32 + bit)
            vals_g = jax.lax.all_gather(vals, doc_axes, axis=1,
                                        tiled=True)            # [Q, P*k]
            slot_g = jax.lax.all_gather(gslot, doc_axes, axis=1, tiled=True)
            best_v, pos = jax.lax.top_k(vals_g, min(topk, vals_g.shape[1]))
            best_s = jnp.take_along_axis(slot_g, pos, axis=1)
            return best_v, best_s

        return body

    def _specs(self, topk: int | None):
        doc = self.doc_axes if len(self.doc_axes) > 1 else self.doc_axes[0]
        arena_spec = P(self.row_axis, doc)
        in_specs = (arena_spec, P(), P(), P(), P())
        if topk is None:
            out_specs = P(None, doc)
        else:
            out_specs = (P(), P())
        return in_specs, out_specs

    def score_fn(self):
        """jit'd (terms [Q, L, 2], n_valid [Q]) -> scores [Q, n_slots]
        (slot order, sharded over the doc axes)."""
        if self._score_jit is None:
            body = self._shard_body(topk=None)
            in_specs, out_specs = self._specs(None)
            fn = shard_map(body, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
            self._score_jit = jax.jit(fn)
        return self._score_jit

    def topk_fn(self, topk: int):
        """jit'd (terms, n_valid) -> (scores [Q, topk], slots [Q, topk])."""
        if topk not in self._topk_jit:
            body = self._shard_body(topk=topk)
            in_specs, out_specs = self._specs(topk)
            fn = shard_map(body, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
            self._topk_jit[topk] = jax.jit(fn)
        return self._topk_jit[topk]

    # ------------------------------------------------------------------
    def search_batch(self, patterns: list, threshold: float = 0.8,
                     topk: int = 32, term_pad: int = 64):
        """Host-level batched search mirroring QueryEngine.search_batch but
        running the sharded engine; returns per-query (doc_ids, scores)."""
        term_sets = []
        for p in patterns:
            codes = dna.encode_dna(p) if isinstance(p, str) else p
            term_sets.append(dna.unique_terms(
                dna.pack_kmers(codes, self.params.kmer, self.params.canonical)))
        ells = np.array([t.shape[0] for t in term_sets], dtype=np.int32)
        pad = max(term_pad, ((int(ells.max(initial=1)) + term_pad - 1)
                             // term_pad) * term_pad)
        buf = np.zeros((len(patterns), pad, 2), dtype=np.uint32)
        for i, t in enumerate(term_sets):
            buf[i, :t.shape[0]] = t
        vals, slots = self.topk_fn(topk)(
            self.arena, self.row_offset, self.block_width,
            jnp.asarray(buf), jnp.asarray(ells))
        vals, slots = np.asarray(vals), np.asarray(slots)
        out = []
        for i, ell in enumerate(ells):
            cut = max(1, math.ceil(threshold * int(ell)))
            ids = self.slot_doc[slots[i]]
            keep = (vals[i] >= cut) & (ids >= 0)
            out.append((ids[keep].astype(np.int32), vals[i][keep]))
        return out

    def scores_for(self, terms: np.ndarray, term_pad: int = 64) -> np.ndarray:
        """Full score vector in ORIGINAL document order (test/oracle path)."""
        L = terms.shape[0]
        pad = max(term_pad, ((L + term_pad - 1) // term_pad) * term_pad)
        buf = np.zeros((1, pad, 2), dtype=np.uint32)
        buf[0, :L] = terms
        slots = self.score_fn()(self.arena, self.row_offset, self.block_width,
                                jnp.asarray(buf),
                                jnp.asarray([L], dtype=np.int32))
        return np.asarray(slots)[0][self._flat_doc_slot]
