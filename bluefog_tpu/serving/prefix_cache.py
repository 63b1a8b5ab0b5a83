"""Chunk-hashed prefix/KV reuse for the serving slot pool.

Fleet traffic is prefix-heavy: every request behind one front end opens
with the same system prompt, and chat turns replay their whole history.
Cold chunked prefill re-runs the model over those shared tokens on every
admission.  This module keeps the K/V of already-computed PROMPT CHUNKS
in a bounded host-side cache so a new request admits by COPYING cached
chunks into its slot and prefilling only its novel tail.

Design points that make this exact rather than approximate:

* **Chain hashing over whole chunks.**  A chunk's K/V depends on every
  token before it (attention is causal but K/V projections see the whole
  prefix through earlier layers' attention), so chunk *i*'s key is
  ``sha256(key_{i-1} ‖ tokens_i)`` — two requests share a cached chunk
  iff they share the ENTIRE token prefix up to its end.  A hash hit is a
  semantic guarantee, not a heuristic.
* **Only FULL chunks of ``prompt[:-1]`` are cached.**  Chunked prefill
  covers ``prompt[:-1]`` (the last prompt token rides the first decode
  step), and a partial tail chunk's K/V window is not aligned to the
  chunk grid — misaligned tails simply prefill cold, which keeps the
  restore path a pure chunk-grid copy and the exactness argument one
  sentence: a restored chunk is bit-identical to the chunk prefill that
  produced it.
* **The cache stores device bytes, not activations.**  Extraction
  slices a chunk window out of every seq-axis leaf of the pooled cache
  (one jitted gather program); restore writes it back at the same grid
  position in another slot and sets the slot's ``cache_index`` — the
  same "garbage above the index is invisible" invariant the engine's
  padded chunks already rely on covers everything above the restored
  prefix.
* **Bounded, LRU.**  Host memory is the budget
  (``BLUEFOG_PREFIX_CACHE_MB``); insertion evicts least-recently-USED
  entries.  Eviction only loses a future shortcut, never correctness.

The per-leaf sequence axis is detected structurally: the cache tree is
shape-evaluated at two ``max_len`` values and the axis that scales is
the sequence axis (leaves with no scaling axis — ``cache_index`` — are
index leaves).  That keeps this module layout-agnostic: full-precision
and int8+scale K/V layouts, unrolled and scanned layer stacks, all work
from the same two programs.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bluefog_tpu.serving import protocol

__all__ = ["PrefixCache", "seq_axes"]


def seq_axes(cfg, max_len: int,
             kv_quant: str = "none") -> Tuple[Optional[int], ...]:
    """Per-leaf sequence axis of the SINGLE-REQUEST cache tree, in
    ``jax.tree.leaves`` order (None for index leaves).  Detected by
    comparing the cache's shapes at two cache lengths — the axis that
    scales with ``max_len`` is the sequence axis — so new layouts never
    need a registry entry here."""
    def shapes(n):
        dcfg = cfg.serving_layout(n, kv_quant=kv_quant)
        return jax.eval_shape(lambda: dcfg.init_cache(1, n))

    a, b = shapes(max_len), shapes(2 * max_len)
    for path, _ in jax.tree_util.tree_flatten_with_path(a)[0]:
        if protocol.leaf_kind(path) == protocol.WINDOW:
            # a ring's rows are not a prefix's positions: once it has
            # wrapped, a chunk cut out of it is another chunk's keys
            raise ValueError(
                f"cache leaf {jax.tree_util.keystr(path)} is a ring of "
                "about a window of positions; a prefix cache cannot "
                "restore chunks into a model with window layers")
        if protocol.leaf_kind(path) == protocol.STATE:
            # the state after a prefix is no row of any chunk: copying
            # the prefix's rows back would leave the state behind
            raise ValueError(
                f"cache leaf {jax.tree_util.keystr(path)} is a recurrent "
                "state, not positions; a prefix cache cannot restore "
                "chunks into a model with a state leaf")
    axes: List[Optional[int]] = []
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        diff = [i for i, (sa, sb) in enumerate(zip(la.shape, lb.shape))
                if sa != sb]
        if not diff:
            axes.append(None)  # cache_index-style leaf
        elif len(diff) == 1:
            axes.append(diff[0])
        else:
            raise ValueError(
                f"cache leaf {la.shape} scales {len(diff)} axes with "
                f"max_len; prefix extraction needs exactly one")
    return tuple(axes)


@partial(jax.jit, static_argnames=("axes", "chunk"))
def _extract_chunk_prog(pool, slot, pos, axes, chunk: int):
    """Slice ``slot``'s K/V window ``[pos, pos+chunk)`` out of every
    seq-axis leaf (index leaves skipped).  Shapes depend on
    ``(axes, chunk)`` only — one compiled program per pool layout."""
    out = []
    for leaf, ax in zip(jax.tree.leaves(pool), axes):
        if ax is None:
            continue
        row = lax.dynamic_index_in_dim(leaf, slot, 0, keepdims=False)
        out.append(lax.dynamic_slice_in_dim(row, pos, chunk, axis=ax))
    return out


@partial(jax.jit, static_argnames=("axes", "chunk"), donate_argnums=(0,))
def _restore_chunk_prog(pool, slot, pos, chunk_leaves, axes, chunk: int):
    """Write one cached chunk back into ``slot`` at grid position
    ``pos`` and set the slot's ``cache_index`` leaves to ``pos+chunk``
    (restores run in ascending chunk order, so the last write leaves the
    index at the full restored length).  The donated in-place update is
    the same cost shape as a prefill chunk's K/V write — without the
    model forward in front of it."""
    leaves = jax.tree.leaves(pool)
    treedef = jax.tree.structure(pool)
    it = iter(chunk_leaves)
    new = []
    for leaf, ax in zip(leaves, axes):
        if ax is None:
            row = jnp.full(leaf.shape[1:], pos + chunk, leaf.dtype)
            new.append(lax.dynamic_update_index_in_dim(leaf, row, slot, 0))
            continue
        row = lax.dynamic_index_in_dim(leaf, slot, 0, keepdims=False)
        row = lax.dynamic_update_slice_in_dim(row, next(it), pos, axis=ax)
        new.append(lax.dynamic_update_index_in_dim(leaf, row, slot, 0))
    return jax.tree.unflatten(treedef, new)


class PrefixCache:
    """Bounded host-side LRU of prompt-chunk K/V, keyed by chain hash.

    One instance serves one :class:`~bluefog_tpu.serving.SlotPool` (the
    speculative engine runs a lockstep PAIR — target and draft K/V are
    different tensors for the same tokens).  ``capacity_bytes`` bounds
    the numpy payload; ``0`` disables retention (every ``insert`` is
    dropped), which is also the ``BLUEFOG_PREFIX_CACHE_MB=0`` escape
    hatch."""

    def __init__(self, chunk: int, capacity_bytes: Optional[int] = None):
        if chunk < 1:
            raise ValueError(f"chunk ({chunk}) must be >= 1")
        if capacity_bytes is None:
            from bluefog_tpu import config as bfconfig

            capacity_bytes = bfconfig.prefix_cache_mb() << 20
        self.chunk = int(chunk)
        self.capacity_bytes = int(capacity_bytes)
        self._store: "OrderedDict[str, List[np.ndarray]]" = OrderedDict()
        self._nbytes = 0
        # observability (the engine folds these into its summary)
        self.hits = 0
        self.misses = 0
        self.insertions = 0
        self.evictions = 0

    # -- keys ---------------------------------------------------------- #
    def chunk_keys(self, prompt: np.ndarray) -> List[str]:
        """Chain-hash keys of the FULL chunks of ``prompt[:-1]`` (the
        prefill region).  ``keys[i]`` commits to every token through the
        end of chunk *i*, so equal keys mean equal whole prefixes."""
        toks = np.asarray(prompt, np.int32).reshape(-1)
        n_full = max(toks.size - 1, 0) // self.chunk
        h = hashlib.sha256(f"prefix:{self.chunk}".encode())
        keys = []
        for i in range(n_full):
            h = h.copy()
            h.update(toks[i * self.chunk:(i + 1) * self.chunk].tobytes())
            keys.append(h.hexdigest())
        return keys

    # -- store --------------------------------------------------------- #
    def match(self, keys: Sequence[str]) -> int:
        """Length (in chunks) of the longest cached prefix of ``keys``,
        touching each hit for LRU.  Chain keys make this a simple walk:
        a miss at chunk *i* means chunk *j > i* can never hit (its key
        commits to *i*'s tokens too — it was inserted through the same
        chain or not at all)."""
        n = 0
        for k in keys:
            if k not in self._store:
                self.misses += 1
                break
            self._store.move_to_end(k)
            self.hits += 1
            n += 1
        return n

    def get(self, key: str) -> List[np.ndarray]:
        return self._store[key]

    def insert(self, key: str, leaves: Sequence[np.ndarray]) -> None:
        if key in self._store:
            self._store.move_to_end(key)
            return
        payload = [np.asarray(leaf) for leaf in leaves]
        size = sum(a.nbytes for a in payload)
        if size > self.capacity_bytes:
            return  # a chunk larger than the whole budget never fits
        while self._nbytes + size > self.capacity_bytes and self._store:
            _, old = self._store.popitem(last=False)
            self._nbytes -= sum(a.nbytes for a in old)
            self.evictions += 1
        self._store[key] = payload
        self._nbytes += size
        self.insertions += 1

    @property
    def nbytes(self) -> int:
        return self._nbytes

    def __len__(self) -> int:
        return len(self._store)

    def __contains__(self, key: str) -> bool:
        """Membership WITHOUT touching LRU order or hit/miss counters —
        the drain flush asks "already cached?" before paying a device
        extract; that probe must not distort the reuse statistics."""
        return key in self._store

    def stats(self) -> Dict[str, float]:
        total = self.hits + self.misses
        return {
            "entries": len(self._store),
            "bytes": self._nbytes,
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": (self.hits / total) if total else 0.0,
            "insertions": self.insertions,
            "evictions": self.evictions,
        }
