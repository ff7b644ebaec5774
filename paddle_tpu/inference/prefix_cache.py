"""Cross-request radix prefix index over the paged KV pool — now tiered.

SGLang's RadixAttention observation, applied to the PagedContinuousBatcher:
a million-user workload shares a handful of system prompts, so the KV rows
for those shared prefixes are recomputed on every admission unless someone
remembers which physical pages already hold them. This module is that
memory — a radix tree at BLOCK granularity (one node == one full
``block_size``-token block == one physical page), host-side only:

  * ``match(tokens)``   — longest cached prefix as a node path; admission
    points the slot's block-table entries at those pages and prefills only
    the suffix (``paged_prefill_into``'s ``dec_base`` append mode).
  * ``pin``/``unpin``   — per-node refcounts. A page referenced by a live
    slot is never evicted; release decrements and stamps LRU recency.
  * ``insert``          — after prefill, the request's full prompt blocks
    are adopted into the tree (page ownership moves from the slot to the
    cache), so the NEXT request with this prefix hits.
  * ``evict(n)``        — LRU eviction of unpinned device chains under page
    pressure; returns the freed physical page ids to the batcher's pool.
    Interior nodes are protected while any device descendant lives (a
    child's rows attend the whole prefix, so ancestors must stay resident).

Tiered residency (CachedAttention/AttentionStore-style hierarchical KV):
each node carries a ``residency`` in the monotone chain
``device -> host -> disk -> gone``. With a ``HostTier`` attached,
``evict()`` DEMOTES the victim's KV rows to a pinned host-DRAM blob (read
back off the pool by the batcher's spill callback) instead of dropping
them; the node stays in the tree, pageless, and a later ``match`` that
lands on it triggers an async ``device_put`` promotion (driven by the
batcher — this module only tracks residency and blob bytes). The host
tier is byte-capacity-bounded (``PADDLE_KV_HOST_GIB``); overflow demotes
host-LRU nodes to an optional ``DiskTier`` behind the same interface, or
drops them. The residency rank is NON-DECREASING with depth along any
root->leaf path (eviction takes deepest device nodes first, promotion
installs top-down), which is what lets ``match`` split any path into a
device prefix + a promotable tail.

Page groups (``PageGroup``): a model whose layers do not all keep every row
(window layers beside full ones) holds its rows in more than one pool of
pages. The tree's ``page`` is the page of the group that keeps every row;
a node carries, besides, the page of each group that keeps a window of
rows, *while it still has one*: a running sequence hands the pages behind
its window back, they stay with their nodes, evictable, until they are
reused, and are then reclaimed oldest release first from a queue (no walk
of the tree). ``usable`` cuts a match back to the longest boundary at which
a group still has every page of the window before it.

State snapshots (``StateSnapshots``): a model whose layers carry a recurrent
state beside their pages can resume a sequence behind a cached prefix only
where that state was kept. The device holds a store of snapshots; this owner
follows ``PageGroup``'s protocol over its indices: the node of the block
that ends at a boundary adopts the snapshot the prefill wrote there, the
snapshot goes free with the node, and when the store is full the one held
longest without use is handed out anew (its node stays, with its pages, and
stops being a boundary a match can end at). ``usable`` cuts a match back to
the deepest node that still has its snapshot.

Only FULL blocks are cached: a partially-filled page is still being
appended to by its owner and cannot be shared. Generated tokens are
cacheable too — a preempted/failed-over request resumes with
``prompt ⧺ generated`` as its admission ids, and re-matching those blocks
is exactly what makes failover re-prefill cheap.

Routing support: every node carries a chain hash
(``h_i = H(h_{i-1}, block_tokens)``); ``summary()`` exposes the hash set
plus a per-hash residency map so gateway replicas can advertise WHAT they
have cached — and in which tier — without shipping token arrays.
``chain_hashes()`` lets the router compute a request's chain once and find
the deepest advertised match per replica, preferring device-resident
depth. The advertisement is cached and invalidated on every mutation
(insert/evict/demote/promote), so the router never chases dead prefixes.
Hashes are a routing hint only — correctness never depends on them (the
tree itself compares real token blocks).
"""
from __future__ import annotations

import collections
import hashlib
import os
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["RadixPrefixCache", "HostTier", "DiskTier", "PageGroup",
           "StateSnapshots", "chain_hashes", "blob_nbytes"]

_ROOT_HASH = 0

# residency ranks: monotone non-decreasing with depth along any path
_TIER_RANK = {"device": 0, "host": 1, "disk": 2}


def _block_hash(parent_hash: int, block: Tuple[int, ...]) -> int:
    """Stable 64-bit chain hash of one block given its parent's hash."""
    h = hashlib.blake2b(digest_size=8)
    h.update(int(parent_hash).to_bytes(8, "little", signed=False))
    h.update(np.asarray(block, np.int64).tobytes())
    return int.from_bytes(h.digest(), "little")


def chain_hashes(tokens, block_size: int) -> List[int]:
    """Chain hashes of every FULL block prefix of ``tokens`` — the
    request-side half of the replica prefix-summary protocol."""
    toks = np.asarray(tokens, np.int64).reshape(-1)
    out: List[int] = []
    h = _ROOT_HASH
    for i in range(len(toks) // block_size):
        blk = tuple(int(t) for t in
                    toks[i * block_size:(i + 1) * block_size])
        h = _block_hash(h, blk)
        out.append(h)
    return out


def blob_nbytes(blob) -> int:
    """Total bytes of every ndarray leaf in a spilled KV blob (a pytree of
    lists/tuples/dicts of numpy arrays) — the tier accounting unit."""
    if isinstance(blob, np.ndarray):
        return int(blob.nbytes)
    if isinstance(blob, dict):
        return sum(blob_nbytes(v) for v in blob.values())
    if isinstance(blob, (list, tuple)):
        return sum(blob_nbytes(v) for v in blob)
    return 0


class HostTier:
    """Byte-capacity-bounded host-DRAM blob store for demoted KV blocks.

    The radix tree owns victim selection (LRU over host-resident nodes)
    and the residency state machine; the tier owns storage + byte
    accounting. ``next_tier`` (a :class:`DiskTier`) receives this tier's
    overflow; without one, overflow is dropped (residency ``gone``).
    """

    name = "host"

    def __init__(self, capacity_bytes: int, next_tier: Optional["DiskTier"] = None):
        if capacity_bytes < 1:
            raise ValueError("host tier capacity must be >= 1 byte")
        self.capacity_bytes = int(capacity_bytes)
        self.next_tier = next_tier
        self._blobs: Dict[int, Tuple[object, int]] = {}  # id -> (blob, nbytes)
        self.used_bytes = 0
        self.stored = 0
        self.evicted = 0  # pushed out of THIS tier (to next tier or gone)

    def put(self, key: int, blob) -> int:
        nbytes = blob_nbytes(blob)
        self._blobs[key] = (blob, nbytes)
        self.used_bytes += nbytes
        self.stored += 1
        return nbytes

    def get(self, key: int):
        return self._blobs[key][0]

    def nbytes_of(self, key: int) -> int:
        return self._blobs[key][1]

    def discard(self, key: int) -> int:
        _, nbytes = self._blobs.pop(key)
        self.used_bytes -= nbytes
        return nbytes

    def __contains__(self, key: int) -> bool:
        return key in self._blobs

    def __len__(self) -> int:
        return len(self._blobs)

    def keys(self):
        return self._blobs.keys()


class DiskTier:
    """Disk-backed blob store behind the same interface as HostTier.

    Blobs land as one ``.npz`` file each under ``root`` (flattened with
    positional keys, rebuilt on ``get``). Capacity is byte-bounded like
    the host tier; there is no tier below — overflow is dropped.
    """

    name = "disk"
    next_tier = None

    def __init__(self, root: str, capacity_bytes: int = 16 << 30):
        self.root = root
        os.makedirs(root, exist_ok=True)
        self.capacity_bytes = int(capacity_bytes)
        self._files: Dict[int, Tuple[str, int]] = {}  # id -> (path, nbytes)
        self._seq = 0
        self.used_bytes = 0
        self.stored = 0
        self.evicted = 0

    @staticmethod
    def _flatten(blob, prefix: str, out: Dict[str, np.ndarray]):
        if isinstance(blob, np.ndarray):
            out[prefix] = blob
        elif isinstance(blob, dict):
            for k in sorted(blob):
                DiskTier._flatten(blob[k], f"{prefix}.d{k}", out)
        elif isinstance(blob, (list, tuple)):
            for i, v in enumerate(blob):
                DiskTier._flatten(v, f"{prefix}.l{i}", out)

    def put(self, key: int, blob) -> int:
        # keep the logical pytree alongside the arrays: store a flat dict
        # and a rebuild skeleton (array leaves replaced by their flat key)
        flat: Dict[str, np.ndarray] = {}
        self._flatten(blob, "b", flat)
        skeleton = _skeletonize(blob, "b")
        self._seq += 1
        path = os.path.join(self.root, f"kv_{self._seq:08d}.npz")
        np.savez(path, __skeleton__=np.frombuffer(
            repr(skeleton).encode(), dtype=np.uint8), **flat)
        nbytes = sum(int(a.nbytes) for a in flat.values())
        self._files[key] = (path, nbytes)
        self.used_bytes += nbytes
        self.stored += 1
        return nbytes

    def get(self, key: int):
        path, _ = self._files[key]
        with np.load(path) as z:
            skeleton = eval(  # noqa: S307 — repr of plain str/list/dict/tuple
                bytes(z["__skeleton__"]).decode())
            flat = {k: z[k] for k in z.files if k != "__skeleton__"}
        return _unskeletonize(skeleton, flat)

    def nbytes_of(self, key: int) -> int:
        return self._files[key][1]

    def discard(self, key: int) -> int:
        path, nbytes = self._files.pop(key)
        try:
            os.unlink(path)
        except OSError:
            pass
        self.used_bytes -= nbytes
        return nbytes

    def __contains__(self, key: int) -> bool:
        return key in self._files

    def __len__(self) -> int:
        return len(self._files)

    def keys(self):
        return self._files.keys()


def _skeletonize(blob, prefix: str):
    if isinstance(blob, np.ndarray):
        return prefix
    if isinstance(blob, dict):
        return {k: _skeletonize(blob[k], f"{prefix}.d{k}") for k in sorted(blob)}
    if isinstance(blob, (list, tuple)):
        out = [_skeletonize(v, f"{prefix}.l{i}") for i, v in enumerate(blob)]
        return tuple(out) if isinstance(blob, tuple) else out
    return blob


def _unskeletonize(skel, flat: Dict[str, np.ndarray]):
    if isinstance(skel, str) and skel in flat:
        return flat[skel]
    if isinstance(skel, dict):
        return {k: _unskeletonize(v, flat) for k, v in skel.items()}
    if isinstance(skel, tuple):
        return tuple(_unskeletonize(v, flat) for v in skel)
    if isinstance(skel, list):
        return [_unskeletonize(v, flat) for v in skel]
    return skel


class _Node:
    __slots__ = ("key", "page", "parent", "children", "ref", "last_use",
                 "hash", "depth", "residency", "promo", "spin", "gpages")

    def __init__(self, key: Tuple[int, ...], page: int, parent, hash_: int,
                 depth: int):
        self.key = key              # the block's tokens
        self.page = page            # physical pool row (-1 when off-device)
        self.parent = parent
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.ref = 0                # live slots using this node
        self.last_use = 0           # LRU stamp (monotonic tick)
        self.hash = hash_
        self.depth = depth          # blocks from root (root excluded)
        self.residency = "device"
        self.promo = None           # in-flight promotion record, if any
        self.spin = 0               # session pins (durable-session holds)
        self.gpages = None          # {group: page} of the window groups

    def __repr__(self):            # pragma: no cover - debug aid
        return (f"_Node(depth={self.depth}, page={self.page}, "
                f"ref={self.ref}, spin={self.spin}, tier={self.residency}, "
                f"kids={len(self.children)})")


class PageGroup:
    """The pages of the layers that keep only the trailing ``rows`` rows of
    a sequence: a pool and a page numbering of their own, beside the
    group whose pages the block table and the tree's ``page`` name.

    A running sequence ``slot`` holds the pages of blocks ``first[slot] ..
    upto[slot]`` of its timeline; block ``j`` lies at entry ``j % ring`` of
    its row of ``table`` (what the model is handed). ``advance`` lets the
    blocks wholly behind the window go and takes pages for the rows about
    to be written. A page that goes back and backs a prefix-cache node's
    block stays with the node, in ``released``, evictable; ``take`` hands
    out free pages first and then the oldest released one, from the queue's
    head. A cached page may be held by several sequences (``ref``). Never
    more than ``ring`` pages a slot: the batcher admits a sequence only
    while every running one could hold that many."""

    def __init__(self, name: str, rows: int, n_pages: int, block_size: int,
                 ring: int, max_batch: int):
        if rows < 1:
            raise ValueError(f"page group {name!r} keeps {rows} rows")
        self.name, self.rows, self.n_pages = name, int(rows), int(n_pages)
        self.block_size, self.ring = block_size, int(ring)
        self.scratch = self.n_pages          # the pool's reserved last row
        self.blocks_back = -(-(self.rows - 1) // block_size)
        self.free = list(range(self.n_pages))
        self.table = np.full((max_batch, self.ring), self.scratch, np.int32)
        self.first = np.zeros((max_batch,), np.int64)
        self.upto = np.zeros((max_batch,), np.int64)
        self.ref: Dict[int, int] = {}        # page -> sequences holding it
        self.owner: Dict[int, _Node] = {}    # page -> the node it backs
        self.released: "collections.OrderedDict[int, _Node]" = \
            collections.OrderedDict()        # held by nobody, oldest first
        self.released_total = 0              # pages sequences let go
        self.reclaimed_total = 0             # released pages handed out anew

    def of(self, node: _Node) -> int:
        """The node's page of this group, -1 once it was reclaimed."""
        return -1 if node.gpages is None else node.gpages.get(self.name, -1)

    def held(self, slot: int) -> int:
        return int(self.upto[slot] - self.first[slot])

    def usable(self, path: Sequence[_Node]) -> int:
        """The longest boundary m <= len(path) at which every block of the
        window before row ``m * block_size`` still has its page."""
        run, best = 0, 0
        for i, node in enumerate(path):
            run = run + 1 if self.of(node) >= 0 else 0
            if run >= min(self.blocks_back, i + 1):
                best = i + 1
        return best

    def take(self) -> Optional[int]:
        """A page for a sequence to write: a free one, else the oldest
        released (its node keeps its place in the tree and loses this
        group's page). None when every page is held."""
        if self.free:
            page = self.free.pop()
        elif self.released:
            page, node = self.released.popitem(last=False)
            del self.owner[page]
            del node.gpages[self.name]
            self.reclaimed_total += 1
        else:
            return None
        self.ref[page] = 1
        return page

    def start(self, slot: int, path: Sequence[_Node]):
        """A sequence enters ``slot`` behind the matched blocks ``path``
        (``usable`` has passed them): it holds the cached pages of the
        window before its first own row."""
        m = len(path)
        first = max(m - self.blocks_back, 0)
        self.first[slot], self.upto[slot] = first, m
        for j in range(first, m):
            page = self.of(path[j])
            if self.ref.get(page, 0) == 0:
                del self.released[page]
            self.ref[page] = self.ref.get(page, 0) + 1
            self.table[slot, j % self.ring] = page

    def _let_go(self, page: int):
        self.ref[page] -= 1
        if self.ref[page]:
            return
        del self.ref[page]
        self.released_total += 1
        node = self.owner.get(page)
        if node is None:
            self.free.append(page)
        else:
            self.released[page] = node

    def advance(self, slot: int, dec: int, upto_row: int) -> bool:
        """Before rows ``dec .. upto_row`` of the slot's timeline are
        written: the blocks that lie wholly behind the window of row
        ``dec`` go back, and the rows about to be written get pages. False
        (with what could be taken, taken) when the pool is held whole."""
        bs = self.block_size
        keep = max(dec - (self.rows - 1), 0) // bs
        first, upto = int(self.first[slot]), int(self.upto[slot])
        need = -(-upto_row // bs)
        if keep <= first and need <= upto:
            return True
        row = self.table[slot]
        for j in range(first, min(keep, upto)):
            self._let_go(int(row[j % self.ring]))
            row[j % self.ring] = self.scratch
        first = max(first, keep)
        upto = max(upto, first)
        self.first[slot] = first
        if need - first > self.ring:
            raise RuntimeError(
                f"page group {self.name!r}: blocks {first} .. {need} of "
                f"slot {slot} do not fit its ring of {self.ring}")
        while upto < need:
            page = self.take()
            if page is None:
                self.upto[slot] = upto
                return False
            row[upto % self.ring] = page
            upto += 1
        self.upto[slot] = upto
        return True

    def adopt(self, slot: int, block: int, node: _Node):
        """The node of ``block`` of the slot's path takes the slot's page
        of that block for its own, where it has none and the slot still
        holds the block: the page then outlives the sequence."""
        if not self.first[slot] <= block < self.upto[slot] \
                or self.of(node) >= 0:
            return
        page = int(self.table[slot, block % self.ring])
        if page in self.owner:
            return
        self.owner[page] = node
        if node.gpages is None:
            node.gpages = {}
        node.gpages[self.name] = page

    def drop_slot(self, slot: int):
        row = self.table[slot]
        for j in range(int(self.first[slot]), int(self.upto[slot])):
            self._let_go(int(row[j % self.ring]))
        row[:] = self.scratch
        self.first[slot] = self.upto[slot] = 0

    def forget(self, node: _Node):
        """The node leaves the tree: its page, if nobody holds it, is
        free; held, it goes free when its holders let it go."""
        page = self.of(node)
        if page < 0:
            return
        del node.gpages[self.name]
        del self.owner[page]
        if self.released.pop(page, None) is not None:
            self.free.append(page)

    def audit(self) -> int:
        """Free list, held pages and released pages cover the pool exactly
        once, the tables name what is held as often as it is held, and
        every released page backs a node. Raises on an anomaly; returns the
        pages unaccounted for (0 on the path that does not raise)."""
        free, held, idle = set(self.free), set(self.ref), set(self.released)
        named = collections.Counter(
            int(p) for slot in range(self.table.shape[0])
            for p in (self.table[slot, j % self.ring] for j in range(
                int(self.first[slot]), int(self.upto[slot]))))
        leaked = set(range(self.n_pages)) - free - held - idle
        if len(free) != len(self.free) or free & held or free & idle \
                or held & idle or leaked or dict(named) != self.ref \
                or any(self.of(n) != p for p, n in self.owner.items()) \
                or not idle <= set(self.owner):
            raise RuntimeError(
                f"page accounting bug in group {self.name!r}: "
                f"leaked={sorted(leaked)} free-and-held={sorted(free & held)} "
                f"free-and-released={sorted(free & idle)} "
                f"held={dict(self.ref)} named={dict(named)}")
        return len(leaked)


class StateSnapshots:
    """The owner of a device store of ``n`` recurrent-state snapshots, one
    for every ``rows`` rows of a cached prefix (``rows`` whole blocks), by
    ``PageGroup``'s protocol: what a node holds of it is an index under
    ``node.gpages[name]``.

    A prefill ``take``s an index for each boundary it is about to pass (a
    free one, else the one that has gone longest without use: ``owned`` is
    in that order, and a resume moves its snapshot to the end) and the
    executable writes the state there; when the block that ends at the
    boundary has entered the tree, its node ``adopt``s the index. Until
    then it is ``pending`` under its slot, and goes back if the prefill
    never got there. No walk of the tree anywhere: a take, an adoption and
    a reclaim are each a step or two on dicts (``steps`` counts them)."""

    def __init__(self, name: str, rows: int, n: int, block_size: int):
        if rows < block_size or rows % block_size:
            raise ValueError(f"a snapshot every {rows} rows: whole blocks "
                             f"of {block_size}")
        self.name, self.rows, self.n = name, int(rows), int(n)
        self.block_size = block_size
        self.blocks = self.rows // block_size   # blocks between boundaries
        self.free = list(range(self.n))
        self.owned: "collections.OrderedDict[int, _Node]" = \
            collections.OrderedDict()           # index -> node, oldest first
        self.pending: Dict[int, Dict[int, int]] = {}   # slot -> {block: idx}
        self.taken_total = 0
        self.restored_total = 0
        self.reclaimed_total = 0
        self.steps = 0

    def of(self, node: _Node) -> int:
        """The node's snapshot, -1 where it has none (any more)."""
        return -1 if node.gpages is None else node.gpages.get(self.name, -1)

    def usable(self, path: Sequence[_Node]) -> int:
        """The deepest boundary m <= len(path) whose node still holds the
        state after its block: a sequence can resume there and nowhere
        between."""
        for m in range(len(path) // self.blocks * self.blocks, 0,
                       -self.blocks):
            self.steps += 1
            if self.of(path[m - 1]) >= 0:
                return m
        return 0

    def resume(self, path: Sequence[_Node]) -> int:
        """The snapshot a sequence behind the matched blocks ``path``
        (``usable`` has passed them) starts from, -1 behind none; it counts
        as used now."""
        if not path:
            return -1
        idx = self.of(path[-1])
        self.owned.move_to_end(idx)
        self.restored_total += 1
        self.steps += 1
        return idx

    def take(self, slot: int, block: int) -> int:
        """An index for ``slot``'s prefill to write the state after
        ``block`` to: a free one, else the one longest unused (its node
        loses it), -1 when every one is pending."""
        self.steps += 1
        if self.free:
            idx = self.free.pop()
        elif self.owned:
            idx, node = self.owned.popitem(last=False)
            del node.gpages[self.name]
            self.reclaimed_total += 1
        else:
            return -1
        self.pending.setdefault(slot, {})[block] = idx
        self.taken_total += 1
        return idx

    def adopt(self, slot: int, block: int, node: _Node):
        """The node of ``block`` of the slot's path takes the snapshot the
        slot's prefill wrote after that block, where it wrote one; a node
        that has one already keeps it, and the slot's goes back."""
        idx = self.pending.get(slot, {}).pop(block, None)
        if idx is None:
            return
        self.steps += 1
        if self.of(node) >= 0:
            self.free.append(idx)
            return
        self.owned[idx] = node
        if node.gpages is None:
            node.gpages = {}
        node.gpages[self.name] = idx

    def drop_slot(self, slot: int):
        """What the slot's prefill took and no node adopted goes back."""
        self.free.extend(self.pending.pop(slot, {}).values())

    def forget(self, node: _Node):
        """The node leaves the tree: its snapshot is free."""
        idx = self.of(node)
        if idx < 0:
            return
        self.steps += 1
        del node.gpages[self.name]
        del self.owned[idx]
        self.free.append(idx)

    def audit(self) -> int:
        """Free, pending and owned indices cover the store exactly once and
        every owned one is its node's. Raises on an anomaly; returns the
        snapshots nobody owns (0 on the path that does not raise)."""
        free, owned = set(self.free), set(self.owned)
        pending = [i for taken in self.pending.values()
                   for i in taken.values()]
        lost = set(range(self.n)) - free - owned - set(pending)
        if len(free) != len(self.free) or len(set(pending)) != len(pending) \
                or free & owned or free & set(pending) \
                or owned & set(pending) or lost \
                or any(self.of(n) != i for i, n in self.owned.items()):
            raise RuntimeError(
                f"snapshot accounting bug in {self.name!r}: "
                f"lost={sorted(lost)} free-and-owned={sorted(free & owned)} "
                f"pending={sorted(pending)}")
        return len(lost)


class RadixPrefixCache:
    """Block-granular radix tree mapping token-block chains to pages,
    with optional host-DRAM (and disk) spill tiers beneath the pool.

    ``host_tier``/``spill``: attach a :class:`HostTier` and a callback
    ``spill(node) -> blob`` (the batcher reads the node's pool rows back
    to pinned numpy) to turn ``evict()`` into demotion. Without a tier
    the eviction semantics are byte-identical to the untiered cache.
    """

    def __init__(self, block_size: int,
                 host_tier: Optional[HostTier] = None,
                 spill: Optional[Callable[["_Node"], object]] = None):
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.block_size = block_size
        self._root = _Node((), -1, None, _ROOT_HASH, 0)
        self._tick = 0
        self._nodes = 0          # every resident node (any tier)
        self._dev_nodes = 0      # device-resident nodes (== pages owned)
        self.host_tier = host_tier
        self._spill = spill
        # cumulative counters (the batcher mirrors them into serving.*)
        self.hit_tokens = 0
        self.miss_tokens = 0
        self.host_hit_tokens = 0   # matched tokens served off host/disk
        self.evictions = 0
        self.demotions = 0
        self.demote_failures = 0
        self.demoted_bytes = 0
        self.promotions = 0        # pages promoted back to device
        self.promoted_bytes = 0
        self.promotion_failures = 0
        self.upgrades = 0          # off-device nodes re-adopted via insert
        self.session_pin_drops = 0  # session-pinned nodes lost anyway
        #   (untiered eviction or a failed spill: chaos/OOM wins; the
        #   session manifest's full-prefill fallback keeps correctness)
        # cached routing advertisement (satellite: invalidate on mutation)
        self._summary_cache: Optional[Dict[str, object]] = None
        self._dirty = True
        # the window groups whose pages nodes carry beside ``page``, and
        # the owner of recurrent-state snapshots where the model has one:
        # each is told when a node leaves the tree (``forget``)
        self.groups: Dict[str, PageGroup] = {}

    # -- bookkeeping ---------------------------------------------------------
    def _touch(self, node: _Node):
        self._tick += 1
        node.last_use = self._tick

    def _invalidate(self):
        self._dirty = True
        self._summary_cache = None

    def __len__(self) -> int:
        return self._nodes

    @property
    def cached_pages(self) -> int:
        return self._dev_nodes

    def pages(self) -> List[int]:
        """Every physical page the cache owns (the audit surface).
        Residency is monotone, so an off-device node has no device
        descendants and its whole subtree can be pruned from the walk."""
        out: List[int] = []
        stack = [n for n in self._root.children.values()
                 if n.residency == "device"]
        while stack:
            n = stack.pop()
            out.append(n.page)
            stack.extend(c for c in n.children.values()
                         if c.residency == "device")
        return out

    def evictable_pages(self) -> int:
        """Pages evict() could free right now — ONE walk sharing evict()'s
        victim rule (a device node frees when its entire device subtree is
        unpinned and promotion-free), so the two can never drift. The walk
        keeps its own stack: a document of 16k tokens is a chain of a
        thousand blocks, deeper than Python recurses."""
        order: List[_Node] = []
        stack = [c for c in self._root.children.values()
                 if c.residency == "device"]
        while stack:
            n = stack.pop()
            order.append(n)
            stack.extend(c for c in n.children.values()
                         if c.residency == "device")
        pinned_below: Dict[int, bool] = {}     # id(node): a child is held
        count = 0
        for n in reversed(order):              # children before parents
            free = n.ref == 0 and n.promo is None \
                and not pinned_below.get(id(n), False)
            count += free
            if not free:
                pinned_below[id(n.parent)] = True
        return count

    # -- the serving hot path ------------------------------------------------
    def _blocks(self, tokens, first: int = 0,
                upto: Optional[int] = None) -> List[Tuple[int, ...]]:
        toks = np.asarray(tokens, np.int64).reshape(-1)
        full = len(toks) // self.block_size
        return [tuple(int(t) for t in
                      toks[i * self.block_size:(i + 1) * self.block_size])
                for i in range(first, full if upto is None
                               else min(upto, full))]

    def match(self, tokens, max_blocks: Optional[int] = None) -> List[_Node]:
        """Longest cached prefix of ``tokens`` as the node path (root
        excluded), capped at ``max_blocks``. Does NOT pin — the caller
        pins the path it actually uses. With tiers the path can end in
        off-device nodes; ``split_device`` separates the promotable tail."""
        path: List[_Node] = []
        node = self._root
        for blk in self._blocks(tokens):
            if max_blocks is not None and len(path) >= max_blocks:
                break
            child = node.children.get(blk)
            if child is None:
                break
            path.append(child)
            node = child
        return path

    @staticmethod
    def split_device(path: Sequence[_Node]) -> Tuple[List[_Node], List[_Node]]:
        """Split a match path into (device prefix, off-device tail).
        Monotone residency guarantees the split point is unique."""
        for i, n in enumerate(path):
            if n.residency != "device":
                return list(path[:i]), list(path[i:])
        return list(path), []

    def pin(self, nodes: Iterable[_Node]):
        for n in nodes:
            n.ref += 1
            self._touch(n)

    def unpin(self, nodes: Iterable[_Node]):
        for n in nodes:
            if n.ref <= 0:
                raise RuntimeError(
                    "prefix-cache refcount underflow: unpin of an "
                    "already-free node (double release)")
            n.ref -= 1
            self._touch(n)

    def session_pin(self, nodes: Iterable[_Node]):
        """Durable-session hold: unlike ``pin`` (which freezes pages on
        device), a session pin lets churn demote the chain device -> host
        -> disk but forbids dropping it out of the LAST tier — a paused
        session stays promotable (or at worst disk-resident) until
        ``session_unpin``. No effect on page accounting."""
        for n in nodes:
            n.spin += 1
            self._touch(n)

    def session_unpin(self, nodes: Iterable[_Node]):
        for n in nodes:
            if n.spin <= 0:
                raise RuntimeError(
                    "prefix-cache session-pin underflow: session_unpin of "
                    "an unpinned node (double release)")
            n.spin -= 1
            self._touch(n)

    def insert(self, tokens, pages: Sequence[int],
               start_block: int, n_blocks: int,
               after: Optional[_Node] = None,
               walked: Optional[List[_Node]] = None) -> List[_Node]:
        """Adopt blocks [start_block, n_blocks) of ``tokens`` into the
        tree. ``pages[i]`` is the physical page holding block i's rows
        (the slot's block-table row). New nodes take ownership of their
        page and start pinned (ref=1, held by the inserting slot); blocks
        already device-resident are SKIPPED — the slot keeps its private
        copy and the tree keeps its own page (neither is pinned here). An
        off-device node with no promotion in flight is UPGRADED in place:
        it adopts the slot's freshly-prefilled page, its stale blob is
        discarded, and it joins the returned (pinned) list. Returns the
        newly created/upgraded nodes.

        ``after``: the node of block ``start_block - 1`` (an admission that
        inserts as its chunks complete): the walk starts there and reads
        the tokens of blocks ``start_block ..`` alone. ``walked``: a list
        that is given every node of those blocks, skipped ones too."""
        if after is None:
            first, node = 0, self._root
        else:
            first, node = start_block, after
        blocks = self._blocks(tokens, first, n_blocks)
        created: List[_Node] = []
        for i, blk in enumerate(blocks, first):
            child = node.children.get(blk)
            if child is None:
                if i < start_block:
                    # the caller said blocks < start_block are already in
                    # the tree (its matched path); a hole here means the
                    # match and insert disagree about tree state
                    raise RuntimeError(
                        "prefix-cache insert: matched prefix missing "
                        "from the tree (match/insert raced?)")
                child = _Node(blk, int(pages[i]), node,
                              _block_hash(node.hash, blk), i + 1)
                child.ref = 1
                node.children[blk] = child
                self._nodes += 1
                self._dev_nodes += 1
                created.append(child)
                self._invalidate()
            elif child.residency != "device" and child.promo is None:
                if i < start_block:
                    raise RuntimeError(
                        "prefix-cache insert: matched device prefix is "
                        "off-device (match/insert raced?)")
                self._discard_blob(child)
                child.page = int(pages[i])
                child.residency = "device"
                child.ref += 1
                self._dev_nodes += 1
                self.upgrades += 1
                created.append(child)
                self._invalidate()
            self._touch(child)
            if walked is not None and i >= start_block:
                walked.append(child)
            node = child
        return created

    # -- eviction / demotion -------------------------------------------------
    def evict(self, n_pages: int) -> List[int]:
        """Free up to ``n_pages`` device pages. Victims are LRU device
        nodes with no pinned/promoting device descendants, taken
        deepest-first so an idle chain frees bottom-up. With a host tier
        attached each victim's KV rows are DEMOTED (spilled to a host
        blob; the node stays matchable); without one — or if the spill
        itself fails — the subtree is dropped. Either way the physical
        page ids are returned to the batcher's pool."""
        freed: List[int] = []
        while len(freed) < n_pages:
            victim = self._lru_device_evictable()
            if victim is None:
                break
            page = victim.page
            if self.host_tier is not None and self._spill is not None:
                self._demote(victim)
            else:
                # untiered: victim has no children at all (no device child
                # by the rule, no off-device child without a tier)
                if victim.spin > 0:
                    self.session_pin_drops += 1
                for group in self.groups.values():
                    group.forget(victim)
                del victim.parent.children[victim.key]
                self._nodes -= 1
                self._dev_nodes -= 1
            self.evictions += 1
            freed.append(page)
            self._invalidate()
        return freed

    def _lru_device_evictable(self) -> Optional[_Node]:
        # Every pin covers a contiguous root-path (admission pins matched
        # prefixes, promotion pins device prefix + tail, insert's new and
        # upgraded nodes extend an already-pinned path), so ref == 0 here
        # implies no pinned/promoting descendant hides in the off-device
        # subtree either — _drop_subtree on a failed demotion stays safe.
        best: Optional[_Node] = None
        stack = [n for n in self._root.children.values()
                 if n.residency == "device"]
        while stack:
            n = stack.pop()
            dev_kids = [c for c in n.children.values()
                        if c.residency == "device"]
            if not dev_kids and n.ref == 0 and n.promo is None:
                if best is None or n.last_use < best.last_use:
                    best = n
            stack.extend(dev_kids)
        return best

    def _demote(self, victim: _Node):
        """device -> host for one node: spill its pool rows to a blob.
        A failed spill (chaos, OOM) drops the subtree instead — pages
        stay clean, the prefix just recomputes next time."""
        from ..resilience.chaos import fault_point
        try:
            fault_point("kv.host_demote")
            blob = self._spill(victim)
        except Exception:
            self.demote_failures += 1
            blob = None
        if blob is None:
            self._drop_subtree(victim)
            return
        victim.page = -1
        victim.residency = "host"
        self._dev_nodes -= 1
        if self._store(self.host_tier, victim, blob):
            self.demotions += 1
            self.demoted_bytes += self.host_tier.nbytes_of(id(victim))
        else:
            self._drop_subtree(victim)

    def _store(self, tier, node: _Node, blob) -> bool:
        """Place a blob in ``tier``, demoting the tier's own LRU overflow
        down-chain (host -> disk -> gone) to make room. False if even
        after overflow eviction the blob cannot fit."""
        nbytes = blob_nbytes(blob)
        while tier.used_bytes + nbytes > tier.capacity_bytes:
            v = self._lru_tier_evictable(tier)
            if v is None:
                break
            self._evict_from_tier(v, tier)
        if tier.used_bytes + nbytes > tier.capacity_bytes:
            return False
        tier.put(id(node), blob)
        node.residency = tier.name
        return True

    def _lru_tier_evictable(self, tier) -> Optional[_Node]:
        """LRU node of ``tier`` whose demotion keeps residency monotone:
        no pinned/promoting state and no child in the SAME tier (deeper
        children already sit in a lower tier or are gone). When the tier
        has no ``next_tier`` eviction means DROP, so session-pinned nodes
        (``spin > 0``) are skipped there — churn can cascade a paused
        session down the tier chain but never out of the last tier."""
        last = tier.next_tier is None
        best: Optional[_Node] = None
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if n.residency != tier.name or n.ref > 0 or n.promo is not None:
                continue
            if last and n.spin > 0:
                continue
            if id(n) not in tier:
                # mid-transition: _demote/_evict_from_tier flip residency
                # before _store lands the blob — the node being stored
                # must not be picked as its own overflow victim
                continue
            if any(c.residency == tier.name for c in n.children.values()):
                continue
            if best is None or n.last_use < best.last_use:
                best = n
        return best

    def _evict_from_tier(self, node: _Node, tier):
        """Push one node out of ``tier``: down to ``next_tier`` if it fits,
        else gone (subtree dropped)."""
        tier.evicted += 1
        nxt = tier.next_tier
        if nxt is not None:
            blob = tier.get(id(node))
            tier.discard(id(node))
            node.residency = "_moving"  # off-tier while _store re-homes it
            if self._store(nxt, node, blob):
                self._invalidate()
                return
            node.residency = tier.name  # restore for a clean subtree drop
            tier.put(id(node), blob)
            tier.stored -= 1  # the put above is a restore, not a new store
        self._drop_subtree(node)

    def _drop_subtree(self, node: _Node):
        """Remove a node and everything below it from the tree, returning
        blob bytes to their tiers. Never called with device descendants
        (monotone residency) — device pages are never dropped here."""
        stack = [node]
        order: List[_Node] = []
        while stack:
            n = stack.pop()
            order.append(n)
            stack.extend(n.children.values())
        for n in order:
            if n.residency == "device":
                self._dev_nodes -= 1
            else:
                self._discard_blob(n)
            if n.spin > 0:
                self.session_pin_drops += 1
            self._nodes -= 1
        del node.parent.children[node.key]
        self._invalidate()

    def _tier_of(self, node: _Node):
        t = self.host_tier
        while t is not None:
            if t.name == node.residency:
                return t
            t = t.next_tier
        return None

    def _discard_blob(self, node: _Node):
        tier = self._tier_of(node)
        if tier is not None and id(node) in tier:
            tier.discard(id(node))

    # -- promotion bookkeeping (the batcher drives the async transfer) ------
    def node_blob(self, node: _Node):
        """The spilled KV blob backing an off-device node."""
        tier = self._tier_of(node)
        if tier is None:
            raise KeyError(f"node {node!r} has no tier blob")
        return tier.get(id(node))

    def promote_node(self, node: _Node, page: int, nbytes: int = 0):
        """host/disk -> device: the batcher landed the node's rows in pool
        ``page``; drop the blob and flip residency."""
        self._discard_blob(node)
        node.page = int(page)
        node.residency = "device"
        self._dev_nodes += 1
        self.promotions += 1
        self.promoted_bytes += int(nbytes)
        self._touch(node)
        self._invalidate()

    # -- the routing surface -------------------------------------------------
    def summary(self) -> Dict[str, object]:
        """Hashed prefix advertisement for the gateway router:
        ``{"block_size": B, "hashes": {chain_hash: depth_blocks},
        "tiers": {chain_hash: residency}}``. Cached; every mutation
        (insert/evict/demote/promote) invalidates it, so evicted chains
        vanish from routing immediately, not at the next insert."""
        if not self._dirty and self._summary_cache is not None:
            return self._summary_cache
        hashes: Dict[int, int] = {}
        tiers: Dict[int, str] = {}
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            hashes[n.hash] = n.depth
            tiers[n.hash] = n.residency
            stack.extend(n.children.values())
        self._summary_cache = {"block_size": self.block_size,
                               "hashes": hashes, "tiers": tiers}
        self._dirty = False
        return self._summary_cache

    # -- audits / stats ------------------------------------------------------
    def audit_tiers(self) -> Dict[str, int]:
        """Prove tier byte accounting leaks zero: every off-device node
        has exactly one blob in its tier, every tier blob belongs to a
        live node, and per-tier used_bytes equals the sum over live
        blobs. Raises on any mismatch."""
        by_tier: Dict[str, Dict[int, _Node]] = {}
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if n.residency != "device":
                by_tier.setdefault(n.residency, {})[id(n)] = n
        report: Dict[str, int] = {}
        tier = self.host_tier
        while tier is not None:
            nodes = by_tier.pop(tier.name, {})
            keys = set(tier.keys())
            if keys != set(nodes):
                raise RuntimeError(
                    f"kv {tier.name}-tier leak: {len(keys - set(nodes))} "
                    f"orphan blobs, {len(set(nodes) - keys)} blobless nodes")
            total = sum(tier.nbytes_of(k) for k in keys)
            if total != tier.used_bytes:
                raise RuntimeError(
                    f"kv {tier.name}-tier byte drift: accounted "
                    f"{tier.used_bytes} != live {total}")
            report[f"{tier.name}_bytes"] = tier.used_bytes
            report[f"{tier.name}_nodes"] = len(keys)
            tier = tier.next_tier
        if by_tier:
            raise RuntimeError(
                f"kv tier leak: nodes resident in unattached tiers "
                f"{sorted(by_tier)}")
        return report

    def session_pinned_nodes(self) -> int:
        count = 0
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if n.spin > 0:
                count += 1
        return count

    def stats(self) -> Dict[str, int]:
        host = self.host_tier
        disk = host.next_tier if host is not None else None
        return {"nodes": self._nodes,
                "cached_pages": self._dev_nodes,
                "hit_tokens": self.hit_tokens,
                "miss_tokens": self.miss_tokens,
                "host_hit_tokens": self.host_hit_tokens,
                "evictions": self.evictions,
                "demotions": self.demotions,
                "demote_failures": self.demote_failures,
                "demoted_bytes": self.demoted_bytes,
                "promotions": self.promotions,
                "promoted_bytes": self.promoted_bytes,
                "promotion_failures": self.promotion_failures,
                "upgrades": self.upgrades,
                "session_pinned_nodes": self.session_pinned_nodes(),
                "session_pin_drops": self.session_pin_drops,
                "host_nodes": len(host) if host is not None else 0,
                "host_bytes": host.used_bytes if host is not None else 0,
                "disk_nodes": len(disk) if disk is not None else 0,
                "disk_bytes": disk.used_bytes if disk is not None else 0}
