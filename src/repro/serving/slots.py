"""Slot-pooled decode-state manager for continuous batching.

The pool owns ONE device-resident decode state sized for ``n_slots``
concurrent requests, with a per-slot cache position (``per_slot=True``
states).  Requests borrow a slot for their lifetime:

    acquire() -> slot          take the lowest free slot (deterministic)
    insert(slot, src_state)    splice a freshly-prefilled single-request
                               state into the pooled caches
    release(slot)              zero the slot and return it to the free list

``insert`` and ``release`` are jitted once with the slot index / slot mask
as traced arguments, so admitting or evicting a request never recompiles —
the fixed-shape decode step keeps running over the whole pool while slots
turn over underneath it.

The pooled state buffers are DONATED through insert/reset (and through the
engine's prefill/macro-step programs): cache updates are in-place on
device, never copy-on-write, and a stale reference to a pre-donation buffer
raises instead of silently reading freed memory.  Occupancy (``active_mask``)
and per-slot positions are HOST MIRRORS maintained by acquire/release/
``advance`` — reading them never synchronizes with the device (the old
``np.asarray(self.state["pos"])`` per call was one hidden host sync each).
"""

from __future__ import annotations

import math
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model
from repro.models.transformer import POOL_LEAVES
from repro.serving.paging import BlockPool


def _cow_copy_fn(state, src, dst):
    """Duplicate physical page ``src`` into ``dst`` across every page-pool
    leaf (``POOL_LEAVES``; jit-able: traced scalar indices, fixed shapes —
    admitting a partial prefix-tail hit never recompiles)."""
    def copy_leaf(path, leaf):
        key = getattr(path[-1], "key", None)
        if key not in POOL_LEAVES:
            return leaf
        # block axis: 0, or 1 under a stacked-layer lead; a pool has
        # (n_blocks, block_size, heads, head_dim) or, latent, (n_blocks,
        # block_size, width)
        axis = leaf.ndim - (3 if key == "pl" else 4)
        blk = jax.lax.dynamic_slice_in_dim(leaf, src, 1, axis)
        return jax.lax.dynamic_update_slice_in_dim(leaf, blk, dst, axis)

    out = dict(state)
    out["layers"] = jax.tree_util.tree_map_with_path(
        copy_leaf, state["layers"])
    return out


class SlotPool:
    def __init__(self, model: Model, n_slots: int, max_len: int,
                 shardings=None, block_size: Optional[int] = None,
                 kv_blocks: Optional[int] = None):
        """``shardings`` (optional) is a pytree of NamedShardings matching the
        pooled state: the state is placed onto the mesh up front and every
        slot-surgery program pins its output to the same layout
        (``out_shardings``), so donation stays in-place across shards and no
        resharding copy sneaks in between insert/reset and the decode step.

        ``block_size`` switches full-attention KV storage to a PAGED pool:
        ``kv_blocks`` shared pages (default: enough that every slot can run
        to ``max_len``, so allocation never fails) with per-slot block
        tables kept as a host mirror and handed to the jitted programs as a
        fresh (non-donated) device array per dispatch — fixed shape, so
        slot turnover stays recompile-free, and the transfer is async, so
        no host sync."""
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.model = model
        self.n_slots = n_slots
        self.max_len = max_len
        self.paged = block_size is not None
        paging = None
        if self.paged:
            if kv_blocks is None:
                from repro.serving.paging import default_kv_blocks
                kv_blocks = default_kv_blocks(n_slots, max_len, block_size)
            self.block_size = int(block_size)
            self.max_blocks = math.ceil(max_len / self.block_size)
            self.blocks = BlockPool(kv_blocks, self.block_size)
            # host mirror of the per-slot block tables; entry 0 = null block
            self._table = np.zeros((n_slots, self.max_blocks), np.int32)
            self._slot_nblocks = np.zeros((n_slots,), np.int32)
            paging = (kv_blocks, self.block_size)
        else:
            self.blocks = None
        if paging is not None:
            self.state = model.init_decode_state(
                n_slots, max_len, per_slot=True, paging=paging)
        else:  # enc-dec models' init_decode_state has no paging parameter
            self.state = model.init_decode_state(
                n_slots, max_len, per_slot=True)
        self._shardings = shardings
        self._bt_sharding = None
        # donate the pooled state: slot surgery updates buffers in place
        if shardings is not None:
            self.state = jax.device_put(self.state, shardings)
            self._insert = jax.jit(model.insert_decode_slot,
                                   donate_argnums=(0,),
                                   out_shardings=shardings)
            self._reset = jax.jit(model.reset_decode_slots,
                                  donate_argnums=(0,),
                                  out_shardings=shardings)
            if self.paged:
                from jax.sharding import NamedSharding, PartitionSpec
                mesh = jax.tree.leaves(shardings)[0].mesh
                self._bt_sharding = NamedSharding(
                    mesh, PartitionSpec(None, None))
                self._cow = jax.jit(_cow_copy_fn, donate_argnums=(0,),
                                    out_shardings=shardings)
        else:
            self._insert = jax.jit(model.insert_decode_slot,
                                   donate_argnums=(0,))
            self._reset = jax.jit(model.reset_decode_slots,
                                  donate_argnums=(0,))
            if self.paged:
                self._cow = jax.jit(_cow_copy_fn, donate_argnums=(0,))
        self._free: List[int] = list(range(n_slots))
        self._owner: List[Optional[object]] = [None] * n_slots
        # host mirrors: no device sync to inspect occupancy or positions
        self._active = np.zeros((n_slots,), bool)
        self._host_pos = np.zeros((n_slots,), np.int64)
        self.dispatch_count = 0  # insert/reset programs launched

    # ------------------------------------------------------------------

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def active_count(self) -> int:
        return self.n_slots - len(self._free)

    def active_slots(self) -> List[int]:
        return [i for i in range(self.n_slots) if self._owner[i] is not None]

    def active_mask(self) -> np.ndarray:
        return self._active.copy()

    def owner(self, slot: int):
        return self._owner[slot]

    # ------------------------------------------------------------------

    def acquire(self, owner) -> int:
        """Take the lowest-numbered free slot for ``owner``."""
        if not self._free:
            raise RuntimeError("slot pool exhausted")
        self._free.sort()
        slot = self._free.pop(0)
        self._owner[slot] = owner
        self._active[slot] = True
        return slot

    def insert(self, slot: int, src_state) -> None:
        """Overwrite slot ``slot`` with a single-request per-slot state."""
        pos = int(np.asarray(src_state["pos"]).reshape(-1)[0])
        self.state = self._insert(self.state, src_state, jnp.int32(slot))
        self.dispatch_count += 1
        self._host_pos[slot] = pos

    def release(self, slot: int) -> None:
        """Evict the slot's request: zero its decode state (position 0,
        empty caches) and return it to the free list.  Paged mode also
        drops the slot's block references (shared prefix blocks survive in
        the trie; private blocks return to the free list) and zeroes the
        table row so any still-inflight masked write self-redirects to the
        null block."""
        if self._owner[slot] is None:
            raise ValueError(f"slot {slot} is not in use")
        mask = np.zeros((self.n_slots,), bool)
        mask[slot] = True
        self.state = self._reset(self.state, jnp.asarray(mask))
        self.dispatch_count += 1
        if self.paged:
            n = int(self._slot_nblocks[slot])
            self.blocks.release(self._table[slot, :n])
            self._table[slot] = 0
            self._slot_nblocks[slot] = 0
        self._owner[slot] = None
        self._active[slot] = False
        self._host_pos[slot] = 0
        self._free.append(slot)

    def drain(self) -> None:
        """Failure-path reset: release every slot and restore a valid,
        donation-ready pooled state no matter what the aborted step left
        behind.  The happy path is the jitted reset-all program over the
        existing buffers; if an abandoned step consumed them (donation
        means a stale reference RAISES, by design), fall back to a fresh
        ``init_decode_state`` so the engine is reusable either way.  Paged
        mode reclaims the WHOLE BlockPool, trie included — the drain
        invariant extends to block references."""
        try:
            mask = np.ones((self.n_slots,), bool)
            self.state = self._reset(self.state, jnp.asarray(mask))
            self.dispatch_count += 1
        except RuntimeError:
            if self.paged:
                self.state = self.model.init_decode_state(
                    self.n_slots, self.max_len, per_slot=True,
                    paging=(self.blocks.n_blocks, self.block_size))
            else:
                self.state = self.model.init_decode_state(
                    self.n_slots, self.max_len, per_slot=True)
            if self._shardings is not None:
                self.state = jax.device_put(self.state, self._shardings)
        if self.paged:
            self.blocks.drain()
            self._table[:] = 0
            self._slot_nblocks[:] = 0
        self._free = list(range(self.n_slots))
        self._owner = [None] * self.n_slots
        self._active[:] = False
        self._host_pos[:] = 0

    # ------------------------------------------------------------------
    # Paged block tables (host mirrors + per-dispatch device upload)
    # ------------------------------------------------------------------

    def block_tables(self):
        """Fresh device copy of the (n_slots, max_blocks) block-table
        mirror.  Fixed shape (never triggers recompilation), asynchronous
        upload (never a host sync), NOT donated — the jitted programs read
        it, all mutation happens host-side here."""
        if self._bt_sharding is not None:
            return jax.device_put(self._table, self._bt_sharding)
        return jnp.asarray(self._table)

    def ensure_blocks(self, slot: int, n_tokens: int) -> None:
        """Grow ``slot``'s table to cover ``n_tokens`` logical positions,
        allocating private pages (and LRU-evicting idle trie blocks) as
        needed.  Raises RuntimeError if the pool is exhausted."""
        need = math.ceil(min(n_tokens, self.max_len) / self.block_size)
        have = int(self._slot_nblocks[slot])
        if need <= have:
            return
        fresh = self.blocks.alloc(need - have)
        self._table[slot, have:need] = fresh
        self._slot_nblocks[slot] = need

    def assign_prefix(self, slot: int, block_ids) -> None:
        """Point the (freshly-acquired, empty) slot's table at pinned
        prefix-cache blocks.  The caller owns the pins (one slot reference
        per block, taken by ``BlockPool.lookup``)."""
        n = len(block_ids)
        if int(self._slot_nblocks[slot]) != 0:
            raise RuntimeError(
                f"assign_prefix on slot {slot} with live blocks")
        self._table[slot, :n] = np.asarray(block_ids, np.int32)
        self._slot_nblocks[slot] = n

    def cow_block(self, slot: int, donor: int) -> int:
        """Copy-on-write: duplicate pinned ``donor`` into a fresh private
        page appended to ``slot``'s table (one jitted dispatch, traced
        indices — never recompiles).  Releases the donor pin.  Returns the
        new block id."""
        (fresh,) = self.blocks.alloc(1)
        self.state = self._cow(self.state, jnp.int32(donor),
                               jnp.int32(fresh))
        self.dispatch_count += 1
        idx = int(self._slot_nblocks[slot])
        self._table[slot, idx] = fresh
        self._slot_nblocks[slot] = idx + 1
        self.blocks.decref(donor)
        return fresh

    def slot_table(self, slot: int) -> np.ndarray:
        """This slot's live table entries (host mirror)."""
        return self._table[slot, : int(self._slot_nblocks[slot])].copy()

    def apply_swaps(self, slot: int, swaps) -> None:
        """Apply trie-insert dedupe swaps ((index, old, new) triples from
        ``BlockPool.insert``) to the table mirror — refcounts were already
        moved by insert; contents are identical under greedy determinism."""
        for idx, old, new in swaps:
            if self._table[slot, idx] != old:
                raise RuntimeError(
                    f"dedupe swap mismatch at slot {slot} block {idx}")
            self._table[slot, idx] = new

    # ------------------------------------------------------------------
    # Host position mirror (the engine advances it as tokens land)
    # ------------------------------------------------------------------

    def set_pos(self, slot: int, pos: int) -> None:
        self._host_pos[slot] = pos

    def advance(self, slot: int, n: int) -> None:
        self._host_pos[slot] += n

    def positions(self) -> np.ndarray:
        """Per-slot cache positions (host mirror — no device sync)."""
        return self._host_pos.copy()
