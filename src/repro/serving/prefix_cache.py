"""Host-side radix-style prefix cache (token-id trie).

Maps token prefixes to (slot, length) of a sequence whose KV covers that
prefix; the engine copies the prefix KV instead of recomputing prefill.
Eviction is LRU over leaf chains and runs in a loop until the trie is
back under ``max_entries`` (one ``insert`` may add one node per token).
``invalidate_slot`` prunes dead slotless chains so the trie never
accumulates unreachable nodes.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple


@dataclass
class _Node:
    children: Dict[int, "_Node"] = field(default_factory=dict)
    slot: Optional[int] = None  # slot whose cache covers the path to here
    depth: int = 0
    stamp: int = 0


class PrefixCache:
    def __init__(self, max_entries: int = 1024):
        self.root = _Node()
        self.max_entries = max_entries
        self.entries = 0
        self.clock = 0

    def insert(self, tokens: Sequence[int], slot: int) -> None:
        self.clock += 1
        node = self.root
        fresh = []  # nodes created by THIS insert (never evicted below)
        for t in tokens:
            if t not in node.children:
                node.children[t] = _Node(depth=node.depth + 1)
                self.entries += 1
                fresh.append(node.children[t])
            node = node.children[t]
            node.stamp = self.clock
        node.slot = slot
        protect = set(map(id, fresh))
        while self.entries > self.max_entries:
            if not self._evict(protect):
                break  # only the just-inserted chain remains

    def longest_prefix(self, tokens: Sequence[int]) -> Tuple[int, Optional[int]]:
        """Returns (matched_length, slot) of the longest cached prefix.

        Every leaf carries a slot (prefix-closed: a slotless leaf is pruned
        or evicted), and a slot's KV covers every prefix of its sequence.
        So the deepest node the prompt reaches is served by any slot found
        below it, even where the prompt then diverges from every cached
        sequence — the shared-system-prompt case.
        """
        self.clock += 1
        node = self.root
        for t in tokens:
            nxt = node.children.get(t)
            if nxt is None:
                break
            node = nxt
            node.stamp = self.clock
        below = node
        while below.slot is None and below.children:
            below = next(iter(below.children.values()))
        if node is self.root or below.slot is None:
            return (0, None)
        return (node.depth, below.slot)

    def invalidate_slot(self, slot: int) -> None:
        """Forget every entry backed by ``slot`` and prune the now-dead
        chains: a childless node with no slot serves no lookup and would
        otherwise live in the trie (and count against ``entries``)
        forever.  Iterative: a chain is as deep as its longest prompt."""
        order = [(None, None, self.root)]  # (parent, token, node), pre-order
        i = 0
        while i < len(order):
            node = order[i][2]
            if node.slot == slot:
                node.slot = None
            order.extend((node, t, c) for t, c in node.children.items())
            i += 1
        for parent, t, node in reversed(order):  # children before parents
            if parent is not None and not node.children and node.slot is None:
                del parent.children[t]
                self.entries -= 1

    def _evict(self, protect=frozenset()) -> bool:
        """Drop the oldest evictable leaf and its exclusive (childless
        once the leaf is gone, slotless) ancestor chain.  Returns False
        when nothing outside ``protect`` can be evicted."""
        up = {}  # id(node) -> (parent, token)
        oldest, leaf = float("inf"), None
        stack = [self.root]
        while stack:
            node = stack.pop()
            for t, c in node.children.items():
                up[id(c)] = (node, t)
                stack.append(c)
            if (not node.children and node is not self.root
                    and id(node) not in protect and node.stamp < oldest):
                oldest, leaf = node.stamp, node
        if leaf is None:
            return False
        node = leaf
        while True:
            parent, t = up[id(node)]
            if node.children or id(node) in protect:
                break
            del parent.children[t]
            self.entries -= 1
            if parent.slot is not None or parent is self.root:
                break
            node = parent
        return True
