"""Slot-pooled K/V caches for the continuous-batching engine.

One resident jitted program serves many requests by giving every request
a SLOT: index ``i`` of a fixed-capacity stacked cache tree whose leaves
are ``[capacity, *single_request_cache_shape]`` (the shapes
:func:`bluefog_tpu.models.generate.init_cache` builds for batch size 1,
in either the full-precision or the int8+scale layout).  Slot shapes are
functions of ``(capacity, max_len)`` only — never of the arrival
pattern — which is what keeps the engine free of recompiles.

Allocation is host-side bookkeeping (a free list); the device tree is
mutated only through the engine's jitted programs.  Freeing a slot
resets its ``cache_index`` leaves (one tiny jitted scatter) — that alone
makes reuse exact, because everything above the index sits behind the
causal mask and the next request overwrites positions as it writes them
(and a recurrent layer's ``state_*`` leaf is read as zeros by a call at
index 0: ``serving/protocol.py``).
``BLUEFOG_KV_ZERO_ON_FREE=1`` (or ``zero_on_free=True``) additionally
zeroes the slot's contents: a whole-slot HBM write per retirement that
buys nothing for correctness (tests assert bit-exactness BOTH ways) but
makes "reuse leaves no trace" literal — the debugging mode.  It also
destroys K/V a :class:`~bluefog_tpu.serving.prefix_cache.PrefixCache`
could have stashed, which is why retention-friendly index-reset is the
default.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bluefog_tpu.serving import protocol

__all__ = ["SlotPool", "pack_stats"]


def _stat_leaves(cache):
    """``[(name, leaf)]``: the model's ``stat_*`` leaves in the order
    the tree flattens them."""
    return [(path[-1].key, leaf) for path, leaf in
            jax.tree_util.tree_flatten_with_path(cache)[0]
            if protocol.leaf_kind(path) == protocol.STAT]


def pack_stats(cache):
    """The ``stat_*`` leaves of a pool ``cache`` as int32 rows ``[n,
    capacity]``, a row a number a slot holds (traced inside the decode
    program, which returns them below its token rows: one small array
    to copy, and none of the pool's own buffers, which the next program
    takes by donation); a float32 leaf travels as its bits.
    :meth:`SlotPool.unpack_stats` undoes it on the host."""
    as_int = lambda leaf: jax.lax.bitcast_convert_type(leaf, jnp.int32) \
        if leaf.dtype == jnp.float32 else leaf.astype(jnp.int32)
    leaves = [as_int(leaf).reshape(leaf.shape[0], -1).T
              for _, leaf in _stat_leaves(cache)]
    capacity = jax.tree.leaves(cache)[0].shape[0]
    return (jnp.concatenate(leaves) if leaves
            else jnp.zeros((0, capacity), jnp.int32))


@partial(jax.jit, donate_argnums=(0,))
def _zero_slot(pool, slot):
    return jax.tree.map(
        lambda leaf: leaf.at[slot].set(jnp.zeros((), leaf.dtype)), pool)


@partial(jax.jit, donate_argnums=(0,))
def _reset_index_slot(pool, slot):
    """Zero only ``slot``'s ``cache_index`` leaves — scalar writes
    instead of a whole-slot scatter.  The index is the only state a
    fresh admission observes: K/V above it is causally masked and gets
    overwritten position by position as the new request prefills."""
    def fix(path, leaf):
        if protocol.leaf_kind(path) == protocol.INDEX:
            return leaf.at[slot].set(jnp.zeros((), leaf.dtype))
        return leaf

    return jax.tree_util.tree_map_with_path(fix, pool)


class SlotPool:
    """Fixed-capacity pool of per-request K/V caches.

    Args:
      cfg: the model's config (:class:`~bluefog_tpu.serving.protocol.
        ServedModel`; training layout fine — normalized through its
        ``serving_layout``, same as ``llama_generate``).
      capacity: number of resident request slots.  Decode advances ALL
        slots every step (inactive ones are masked), so capacity is the
        decode batch size the hardware is sized for.
      max_len: per-slot cache length (prompt + generation budget ceiling
        for any single request).
      kv_quant: "none" | "int8" — the cache layout
        (``models/generate.py``); int8 halves decode's cache traffic.
      zero_on_free: ``True`` zeroes a freed slot's whole cache; the
        default (``None``) follows ``BLUEFOG_KV_ZERO_ON_FREE`` (off —
        only the ``cache_index`` leaves reset, see module docstring).
      prefix: an optional
        :class:`~bluefog_tpu.serving.prefix_cache.PrefixCache` whose
        ``chunk`` is the engine's prefill chunk; enables
        :meth:`restore_prefix` / :meth:`stash_chunk`.
      chunk: the most tokens one cached call writes (the engine's
        prefill chunk): the slack a window layer's ring leaf needs.

    The pool stacks whatever leaves the model declares: a window
    layer's ring is about a window long and a recurrent layer's state
    of one size whatever ``max_len`` is (:meth:`cache_bytes` reports
    each kind).
    """

    def __init__(self, cfg, capacity: int, max_len: int,
                 kv_quant: str = "none",
                 zero_on_free: Optional[bool] = None,
                 prefix=None, chunk: int = 1):
        if capacity < 1:
            raise ValueError(f"capacity ({capacity}) must be >= 1")
        if zero_on_free is None:
            from bluefog_tpu import config as bfconfig

            zero_on_free = bfconfig.kv_zero_on_free()
        dcfg = cfg.serving_layout(max_len, chunk=chunk, kv_quant=kv_quant)
        slot_shapes = jax.eval_shape(lambda: dcfg.init_cache(1, max_len))
        self.cache = jax.tree.map(
            lambda s: jnp.zeros((capacity,) + s.shape, s.dtype),
            slot_shapes)
        self.capacity = capacity
        self.max_len = max_len
        self.kv_quant = kv_quant
        self.zero_on_free = bool(zero_on_free)
        self.prefix = prefix
        self._seq_axes = None
        if prefix is not None:
            from bluefog_tpu.serving.prefix_cache import seq_axes

            if max_len % prefix.chunk != 0:
                raise ValueError(
                    f"prefix cache chunk ({prefix.chunk}) must divide "
                    f"max_len ({max_len}) — restores land on the same "
                    f"chunk grid prefill writes")
            self._seq_axes = seq_axes(cfg, max_len, kv_quant)
        self._free: List[int] = list(range(capacity - 1, -1, -1))
        self._in_use: set = set()
        # (name, shape, dtype) of the model's stat_* leaves, as
        # pack_stats lays them out
        self._stat_shapes = [(name, leaf.shape, leaf.dtype)
                             for name, leaf in _stat_leaves(self.cache)]
        self.has_stats = bool(self._stat_shapes)
        # the recurrent layers' state_* leaves, by path
        self.state_leaves = [
            jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(self.cache)[0]
            if protocol.leaf_kind(path) == protocol.STATE]

    def cache_bytes(self) -> dict:
        """``{"full" | "window" | "state": bytes}`` the pool reserves in
        leaves of that kind, all slots together (index and stat leaves
        left out)."""
        out = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self.cache)[0]:
            kind = protocol.leaf_kind(path)
            if kind in (protocol.FULL, protocol.WINDOW, protocol.STATE):
                out[kind] = out.get(kind, 0) + leaf.size * leaf.dtype.itemsize
        return out

    @property
    def stat_rows(self) -> int:
        """The rows :func:`pack_stats` makes of this pool's leaves."""
        return sum(int(np.prod(shape[1:]))
                   for _, shape, _ in self._stat_shapes)

    def unpack_stats(self, rows: np.ndarray) -> dict:
        """A decode program's packed ``stat_*`` leaves (``pack_stats``,
        on the host) by name, ``{name: [leaf [capacity, ...] a layer
        that declares it]}``."""
        out, at = {}, 0
        for name, shape, dtype in self._stat_shapes:
            n = int(np.prod(shape[1:]))
            leaf = np.ascontiguousarray(rows[at:at + n].T).reshape(shape)
            out.setdefault(name, []).append(
                leaf.view(np.float32) if dtype == jnp.float32 else leaf)
            at += n
        return out

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return len(self._in_use)

    def occupancy(self) -> float:
        """Fraction of slots holding a live request (a serving metric:
        idle slots are decode compute spent on nothing)."""
        return len(self._in_use) / self.capacity

    def alloc(self) -> Optional[int]:
        """Claim a slot, or ``None`` when the pool is full (the scheduler
        turns ``None`` into queueing/backpressure — the pool never
        blocks)."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._in_use.add(slot)
        return slot

    def free(self, slot: int) -> None:
        """Return ``slot`` to the pool.  Resets the slot's cache index
        (always — a stale index would misplace the next request's
        prefill); zeroes the contents too only under ``zero_on_free``."""
        if slot not in self._in_use:
            raise ValueError(f"slot {slot} is not allocated")
        self._in_use.remove(slot)
        self._free.append(slot)
        if self.zero_on_free:
            self.cache = _zero_slot(self.cache, jnp.int32(slot))
        else:
            self.cache = _reset_index_slot(self.cache, jnp.int32(slot))

    # -- prefix reuse --------------------------------------------------- #
    def restore_prefix(self, slot: int, keys,
                       n: Optional[int] = None) -> int:
        """Copy the longest cached run of ``keys``'s chunks into
        ``slot`` (ascending, so ``cache_index`` ends at the restored
        length) and return how many chunks were restored.  ``n`` caps
        the run when the caller already matched (the speculative engine
        restores the MINIMUM of the target/draft matches into both
        pools).  Each restore is one device copy — the prefill forward
        it replaces is the savings."""
        if self.prefix is None:
            return 0
        matched = self.prefix.match(keys) if n is None else int(n)
        for i in range(matched):
            self._restore_one(slot, keys[i], i * self.prefix.chunk)
        return matched

    def _restore_one(self, slot: int, key: str, pos: int) -> None:
        from bluefog_tpu.serving.prefix_cache import _restore_chunk_prog

        self.cache = _restore_chunk_prog(
            self.cache, jnp.int32(slot), jnp.int32(pos),
            [jnp.asarray(a) for a in self.prefix.get(key)],
            axes=self._seq_axes, chunk=self.prefix.chunk)

    def stash_chunk(self, slot: int, key: str, pos: int) -> None:
        """Pull the chunk at grid position ``pos`` out of ``slot`` and
        retain it under ``key`` (no-op without a prefix cache).  Called
        by the engine right after a FULL cold chunk prefills — the K/V
        is extracted while it provably matches the chain hash."""
        if self.prefix is None:
            return
        from bluefog_tpu.serving.prefix_cache import _extract_chunk_prog

        leaves = _extract_chunk_prog(self.cache, jnp.int32(slot),
                                     jnp.int32(pos), axes=self._seq_axes,
                                     chunk=self.prefix.chunk)
        self.prefix.insert(key, [np.asarray(leaf) for leaf in leaves])
