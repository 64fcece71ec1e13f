"""Item -> node placement for fault tolerance and elastic scaling.

COBS' compact index is a concatenation of INDEPENDENT sub-indexes (paper
section 2.3) — the unit of distribution, recovery, and elasticity is
therefore an independent sub-range of the index. Two granularities exist:

* ``BlockPlacement`` — one Bloom-filter block per item (the original
  control-plane granularity, used with the mesh data plane in
  ``repro.index.distributed``);
* ``ShardPlacement`` — one cobs-jax-v2 *manifest row* (shard file) per
  item. Since the out-of-core refactor the shard is the on-disk placement
  unit: a host opens a sub-store view of exactly the shard files assigned
  to it (``repro.core.store.open_substore``) and serves them through a
  ``repro.serve.ShardWorker``.

Both use rendezvous (highest-random-weight) hashing, so adding or removing
a node moves only ~replication/n of the items (elastic scaling); each item
is placed on ``replication`` distinct nodes, node failure flips queries to
the next-highest replica with zero data movement, and recovery rebuilds
only the lost node's items (not the whole index).

HRW balances item COUNTS. Items of very different sizes (COBS' blocks
follow the archive's document-size skew: one 1024-document block of the
paper's largest samples can hold a third of the arena) need balance by
weight instead: given ``weights``, placement is largest-first greedy
(``balanced_replicas``), ties broken and replicas ordered by each item's
HRW ranking.

This is host-side control-plane logic (pure python, deterministic), used
by the launcher to assign sub-indexes to pods/hosts; the data planes are
DistributedIndex (mesh) and the ShardWorker/Frontend pair (multi-host
serving).
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional, Sequence


def _weight(item_id: int, node: str) -> int:
    h = hashlib.blake2b(f"{item_id}:{node}".encode(), digest_size=8)
    return int.from_bytes(h.digest(), "big")


def _ranking(item_id: int, nodes: Sequence[str]) -> list[str]:
    return sorted(nodes, key=lambda n: _weight(item_id, n), reverse=True)


def balanced_replicas(weights: Sequence[int], nodes: Sequence[str],
                      replication: int) -> list[list[str]]:
    """Largest-first greedy placement of weighted items: per item, its
    ``replication`` nodes in HRW preference order.

    Items go heaviest first, each to the least-loaded nodes (every
    replica's weight counts against its node), ties broken by the item's
    HRW ranking. An item heavier than everything else stays alone on its
    node until the others outgrow it, and with many items lighter than a
    mean share the fullest node stays near max(the heaviest item, the
    mean share of the others)."""
    nodes = list(nodes)
    r = min(int(replication), len(nodes))
    w = [int(x) for x in weights]
    load = dict.fromkeys(nodes, 0)
    out: list[list[str]] = [[] for _ in w]
    for g in sorted(range(len(w)), key=lambda g: (-w[g], g)):
        ranked = _ranking(g, nodes)
        got = sorted(ranked, key=lambda n: load[n])[:r]   # stable: HRW ties
        out[g] = [n for n in ranked if n in got]
        for n in got:
            load[n] += w[g]
    return out


@dataclass
class RendezvousPlacement:
    """HRW placement of ``n_items`` integer-identified items over nodes."""

    nodes: list[str]
    n_items: int
    replication: int = 2
    _down: set[str] = field(default_factory=set)
    # per-item weights: None is plain HRW (balances item counts); given,
    # placement by weight (``balanced_replicas``)
    weights: Optional[Sequence[int]] = None
    _table: tuple = field(default=(), repr=False)

    def __post_init__(self):
        if not self.nodes:
            raise ValueError("need at least one node")
        if self.replication < 1:
            raise ValueError("replication >= 1")
        self.nodes = list(dict.fromkeys(self.nodes))  # dedupe, keep order
        if self.weights is not None and len(self.weights) != self.n_items:
            raise ValueError(f"{len(self.weights)} weights for "
                             f"{self.n_items} items")

    # -- placement ----------------------------------------------------------
    def replicas(self, item_id: int) -> list[str]:
        """All replica nodes for an item, preference order (HRW ranking)."""
        if self.weights is None:
            return _ranking(item_id, self.nodes)[
                : min(self.replication, len(self.nodes))]
        key = tuple(self.nodes)
        if not self._table or self._table[0] != key:
            self._table = (key, balanced_replicas(
                self.weights, self.nodes, self.replication))
        return list(self._table[1][item_id])

    def owner(self, item_id: int) -> str:
        """Preferred LIVE node for an item (failover-aware)."""
        for n in self.replicas(item_id):
            if n not in self._down:
                return n
        raise RuntimeError(f"item {item_id}: all replicas down")

    def assignment(self) -> dict[str, list[int]]:
        """node -> items currently served (live owners only)."""
        out: dict[str, list[int]] = {n: [] for n in self.nodes
                                     if n not in self._down}
        for b in range(self.n_items):
            out[self.owner(b)].append(b)
        return out

    def replica_assignment(self) -> dict[str, list[int]]:
        """node -> every item it REPLICATES (owner or backup). This is the
        set of shards a host must materialize to be able to take over as a
        failover/hedge target without data movement."""
        out: dict[str, list[int]] = {n: [] for n in self.nodes}
        for b in range(self.n_items):
            for n in self.replicas(b):
                out[n].append(b)
        return out

    def is_covered(self) -> bool:
        """Every item has at least one live replica."""
        try:
            for b in range(self.n_items):
                self.owner(b)
            return True
        except RuntimeError:
            return False

    # -- failures -----------------------------------------------------------
    def fail(self, node: str) -> list[int]:
        """Mark node down; returns items whose PRIMARY moved (these flip to
        a replica — no rebuild needed while replication holds)."""
        if node not in self.nodes:
            raise KeyError(node)
        moved = [b for b in range(self.n_items) if self.owner(b) == node]
        self._down.add(node)
        return moved

    def recover(self, node: str) -> list[int]:
        """Node back up; returns items to restore onto it (rebuild/copy set
        = exactly its replica set, nothing else)."""
        self._down.discard(node)
        return [b for b in range(self.n_items) if node in self.replicas(b)]

    @property
    def live_nodes(self) -> list[str]:
        return [n for n in self.nodes if n not in self._down]

    # -- elasticity ---------------------------------------------------------
    def add_node(self, node: str) -> list[int]:
        """Scale up; returns items that must MOVE to the new node (HRW
        guarantees expected n_items * replication / (n+1))."""
        before = {b: set(self.replicas(b)) for b in range(self.n_items)}
        self.nodes.append(node)
        return [b for b in range(self.n_items)
                if set(self.replicas(b)) != before[b]]

    def remove_node(self, node: str) -> list[int]:
        """Scale down; returns items that must be re-homed."""
        if node not in self.nodes:
            raise KeyError(node)
        before = {b: set(self.replicas(b)) for b in range(self.n_items)}
        self.nodes.remove(node)
        self._down.discard(node)
        return [b for b in range(self.n_items)
                if set(self.replicas(b)) != before[b]]


class BlockPlacement(RendezvousPlacement):
    """HRW placement at Bloom-filter-block granularity (legacy surface)."""

    def __init__(self, nodes: list[str], n_blocks: int, replication: int = 2):
        super().__init__(nodes, n_blocks, replication)

    @property
    def n_blocks(self) -> int:
        return self.n_items


class ShardPlacement(RendezvousPlacement):
    """HRW placement of cobs-jax-v2 manifest rows (shard files) over hosts.

    The shard is the multi-host serving unit: ``replica_assignment()[h]``
    is exactly the shard subset host ``h`` opens via ``open_substore``, and
    ``owner``/``replicas`` drive the frontend's scatter and hedged-failover
    dispatch.
    """

    def __init__(self, nodes: list[str], n_shards: int, replication: int = 2,
                 weights: Optional[Sequence[int]] = None):
        super().__init__(nodes, n_shards, replication, weights=weights)

    @property
    def n_shards(self) -> int:
        return self.n_items

    @classmethod
    def for_store(cls, path, nodes: list[str], replication: int = 2,
                  balance: str = "shards") -> "ShardPlacement":
        """Placement over the manifest rows of a v2 store directory.
        ``balance`` "shards" is plain HRW; "bytes" weighs each shard by
        its manifest row count (``balanced_replicas``)."""
        import json

        from ..core.store import FORMAT_V2
        if balance not in ("shards", "bytes"):
            raise ValueError(f"balance {balance!r}: 'shards' or 'bytes'")
        manifest = json.loads((Path(path) / "manifest.json").read_text())
        if manifest.get("format") != FORMAT_V2:
            raise ValueError(f"not a {FORMAT_V2} store: {path}")
        shards = manifest["shards"]
        weights = ([s["rows"][1] - s["rows"][0] for s in shards]
                   if balance == "bytes" else None)
        return cls(nodes, len(shards), replication, weights=weights)
