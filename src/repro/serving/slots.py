"""Slot-pooled decode-state manager for continuous batching.

The pool owns ONE device-resident decode state sized for ``n_slots``
concurrent requests, with a per-slot cache position (``per_slot=True``
states).  Full-attention KV lives in a PAGED pool of ``block_size``-token
pages shared by every slot (a ``BlockPool`` with a radix prefix trie), each
slot reading its pages through a block table; window rings and recurrent
states stay per slot.  Requests borrow a slot for their lifetime:

    acquire() -> slot          take the lowest free slot (deterministic)
    release(slot)              zero the slot, drop its page references and
                               return it to the free list

Admission prefills straight into the pooled slots (the engine's batched
prefill program), so the pool itself only resets slots and copies pages.
``release`` is jitted once with the slot mask as a traced argument, so
evicting a request never recompiles — the fixed-shape decode step keeps
running over the whole pool while slots turn over underneath it.

The pooled state buffers are DONATED through reset and copy-on-write (and
through the engine's prefill/macro-step programs): cache updates are
in-place on device, and a stale reference to a pre-donation buffer raises
instead of silently reading freed memory.  Occupancy (``active_mask``),
per-slot positions and block tables are HOST MIRRORS maintained by
acquire/release/``advance`` — reading them never synchronizes with the
device.
"""

from __future__ import annotations

import math
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.models.model import Model
from repro.models.transformer import POOL_LEAVES
from repro.serving.paging import BlockPool, default_kv_blocks


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
                 shardings=None, *, block_size: int,
                 kv_blocks: Optional[int] = None):
        """Full-attention KV is stored in ``kv_blocks`` shared pages of
        ``block_size`` tokens (default: enough that every slot can run to
        ``max_len``, so allocation never fails), with per-slot block tables
        kept as a host mirror and handed to the jitted programs as a fresh
        (non-donated) device array per dispatch — fixed shape, so slot
        turnover stays recompile-free, and the transfer is async, so no
        host sync.

        ``shardings`` (optional) is a pytree of NamedShardings matching the
        pooled state: the state is placed onto the mesh up front and every
        slot-surgery program pins its output to the same layout
        (``out_shardings``), so donation stays in-place across shards and no
        resharding copy sneaks in between reset and the decode step."""
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.model = model
        self.n_slots = n_slots
        self.max_len = max_len
        if kv_blocks is None:
            kv_blocks = default_kv_blocks(n_slots, max_len, block_size)
        self.block_size = int(block_size)
        self.max_blocks = math.ceil(max_len / self.block_size)
        self.blocks = BlockPool(kv_blocks, self.block_size)
        # host mirror of the per-slot block tables; entry 0 = null block
        self._table = np.zeros((n_slots, self.max_blocks), np.int32)
        self._slot_nblocks = np.zeros((n_slots,), np.int32)
        self.state = self._fresh_state()
        self._shardings = shardings
        self._bt_sharding = None
        # donate the pooled state: slot surgery updates buffers in place
        kw = {}
        if shardings is not None:
            self.state = jax.device_put(self.state, shardings)
            kw["out_shardings"] = shardings
            mesh = jax.tree.leaves(shardings)[0].mesh
            self._bt_sharding = NamedSharding(mesh, PartitionSpec(None, None))
        self._reset = jax.jit(model.reset_decode_slots, donate_argnums=(0,),
                              **kw)
        self._cow = jax.jit(_cow_copy_fn, donate_argnums=(0,), **kw)
        self._free: List[int] = list(range(n_slots))
        self._owner: List[Optional[object]] = [None] * n_slots
        # host mirrors: no device sync to inspect occupancy or positions
        self._active = np.zeros((n_slots,), bool)
        self._host_pos = np.zeros((n_slots,), np.int64)
        self.dispatch_count = 0  # reset/copy-on-write programs launched

    def _fresh_state(self):
        return self.model.init_decode_state(
            self.n_slots, self.max_len, per_slot=True,
            paging=(self.blocks.n_blocks, self.block_size))

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

    def release(self, slot: int) -> None:
        """Evict the slot's request: zero its decode state (position 0,
        empty caches), drop its block references (shared prefix blocks
        survive in the trie; private blocks return to the free list), zero
        its table row so any still-inflight masked write self-redirects to
        the null block, and return it to the free list."""
        if self._owner[slot] is None:
            raise ValueError(f"slot {slot} is not in use")
        mask = np.zeros((self.n_slots,), bool)
        mask[slot] = True
        self.state = self._reset(self.state, jnp.asarray(mask))
        self.dispatch_count += 1
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
        ``init_decode_state`` so the engine is reusable either way.  The
        WHOLE BlockPool is reclaimed, trie included — the drain invariant
        extends to block references."""
        try:
            mask = np.ones((self.n_slots,), bool)
            self.state = self._reset(self.state, jnp.asarray(mask))
            self.dispatch_count += 1
        except RuntimeError:
            self.state = self._fresh_state()
            if self._shardings is not None:
                self.state = jax.device_put(self.state, self._shardings)
        self.blocks.drain()
        self._table[:] = 0
        self._slot_nblocks[:] = 0
        self._free = list(range(self.n_slots))
        self._owner = [None] * self.n_slots
        self._active[:] = False
        self._host_pos[:] = 0

    # ------------------------------------------------------------------
    # Block tables (host mirrors + per-dispatch device upload)
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
