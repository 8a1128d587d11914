"""Paged KV cache: a block-pool arena with per-slot block tables.

The contiguous decode cache (``factory.init_cache``) charges every slot
``max_len`` rows up front, so B slots of wildly different sequence lengths
pay B * max_len.  Here the sequence-indexed leaves (k / v and their int8
scales) live in one shared arena of ``num_blocks`` fixed-size blocks, and
each slot owns an ordered block table mapping logical block -> physical
block.  Blocks are allocated lazily as a slot's length grows and returned
to the pool when the request finishes, so the arena can be sized for the
*expected* total tokens in flight instead of the worst case per slot.

Admission control is reservation-based: a request reserves its worst-case
block count (prompt + max_new tokens) before taking a slot, and ``ensure``
then draws from the free list as the sequence actually grows — the
invariant ``free >= outstanding reservations`` means a mid-flight
allocation can never fail.

The decode/prefill steps keep the existing contiguous cache contract of
``models/factory.py``: ``gather_view`` materializes a (Lx, B, S_view, ...)
view from the pages (one jitted take per leaf), ``apply_decode`` writes
each active slot's newly written row into its page in place, and
``scatter_chunk`` splices a prefill chunk's rows.  The view is kept as the
last decode step's output and rebuilt only after a write that bypasses it
(a prefill splice, a scrub, a tick whose writes were not all committed).
A production Pallas paged-attention
kernel would consume the block table directly; the view keeps every model
family working unmodified.

Recurrent per-slot states (ssm / conv / wkv / tm_x / cm_x, whisper's cross
caches) are O(1) per slot and stay slot-dense; ``len`` is host-managed by
the engine.

``ContiguousKVCache`` wraps the classic single-arena cache behind the same
interface so the engine has one code path and the benchmark can check
bit-parity between the two.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import factory

__all__ = ["classify_cache", "PagedKVCache", "ContiguousKVCache",
           "make_kv_cache"]

# leaves indexed (Lx, B, S, ...) along the decode sequence — pageable
_SEQ_NAMES = ("k", "v", "k_scale", "v_scale")


def classify_cache(proto: dict, max_len: int):
    """Split a ``factory.init_cache`` pytree into sequence-indexed leaves
    (pageable) and per-slot state leaves.  Whisper's cross_k/cross_v are
    encoder-length and never paged."""
    seq, state = [], []
    for name, leaf in proto.items():
        if name == "len":
            continue
        if (name in _SEQ_NAMES and leaf.ndim >= 3
                and leaf.shape[2] == max_len):
            seq.append(name)
        else:
            state.append(name)
    return seq, state


# ------------------------------------------------------------- device programs
# The function names become the programs' XLA module names (``jit_kv_*``),
# which the device-trace readers match.  The three writers take the arenas
# donated and write them with ``dynamic_update_slice`` only: XLA then
# updates the buffers in place, where an out-of-place ``.at[].set`` scatter
# copies every arena (twice over, through relayouts) to write a few rows.
# A write marked dropped (physical block ``num_blocks``) reads the row at
# the clamped index and writes it back unchanged.

def _row_start(arena, blk, off):
    return (0, blk, off) + (0,) * (arena.ndim - 3)


@jax.jit
def kv_gather_view(pages, block_tables):
    """(Lx, num_blocks, bs, ...) arenas -> (Lx, B, mb * bs, ...) views."""
    b, mb = block_tables.shape
    out = {}
    for n, arena in pages.items():
        v = jnp.take(arena, block_tables.reshape(-1), axis=1)
        out[n] = v.reshape((arena.shape[0], b, mb * arena.shape[2])
                           + arena.shape[3:])
    return out


@functools.partial(jax.jit, donate_argnums=0)
def kv_scatter_decode(pages, view, idx):
    """Write each slot's row ``view[:, i, lens[i]]`` at ``(phys[i],
    off[i])``.  idx: (3, B) int32 rows = (lens, phys, off), one
    device_put per tick."""
    lens, phys, off = idx[0], idx[1], idx[2]

    def write(i, pages):
        out = {}
        for n, arena in pages.items():
            row_shape = (arena.shape[0], 1, 1) + arena.shape[3:]
            nb = arena.shape[1]
            at = _row_start(arena, jnp.minimum(phys[i], nb - 1), off[i])
            new = lax.dynamic_slice(view[n], _row_start(arena, i, lens[i]),
                                    row_shape).astype(arena.dtype)
            row = jnp.where(phys[i] < nb, new,
                            lax.dynamic_slice(arena, at, row_shape))
            out[n] = lax.dynamic_update_slice(arena, row, at)
        return out

    return lax.fori_loop(0, idx.shape[1], write, pages)


@functools.partial(jax.jit, donate_argnums=0)
def kv_scatter_chunk(pages, rows, phys, span):
    """Splice chunk rows (Lx, C, ...) into consecutive positions, one
    whole block per write.  phys: (nblk,) physical block of each block
    the chunk may touch, in order (``num_blocks``: drop); span: (2,) int32
    = (offset of the chunk's first row in the first block, valid rows)."""
    first, count = span[0], span[1]
    nblk = phys.shape[0]
    # rows as (Lx, 1, C, ...) with a block of padding before and enough
    # after that block k's slice never clamps.  Built outside the loop:
    # expanding each block's rows inside it makes XLA relayout the arenas
    padded = {}
    for n, arena in pages.items():
        bs, c = arena.shape[2], rows[n].shape[1]
        pad = [(0, 0)] * (rows[n].ndim + 1)
        pad[2] = (bs, nblk * bs - c)
        padded[n] = jnp.pad(jnp.expand_dims(rows[n], 1), pad)

    def write(k, pages):
        out = {}
        for n, arena in pages.items():
            nb, bs = arena.shape[1], arena.shape[2]
            new = lax.dynamic_slice_in_dim(padded[n], bs + k * bs - first,
                                           bs, axis=2).astype(arena.dtype)
            q = k * bs + jnp.arange(bs) - first      # chunk row of each
            keep = (q >= 0) & (q < count) & (phys[k] < nb)
            at = _row_start(arena, jnp.minimum(phys[k], nb - 1), 0)
            old = lax.dynamic_slice(arena, at, new.shape)
            m = keep.reshape((1, 1, bs) + (1,) * (arena.ndim - 3))
            out[n] = lax.dynamic_update_slice(arena, jnp.where(m, new, old),
                                              at)
        return out

    return lax.fori_loop(0, nblk, write, pages)


@jax.jit
def kv_mask_state(old, new, active):
    def leaf(o, nw):
        m = active.reshape((1, -1) + (1,) * (o.ndim - 2))
        return jnp.where(m, nw.astype(o.dtype), o)
    return jax.tree.map(leaf, old, new)


@functools.partial(jax.jit, donate_argnums=0)
def kv_scrub(pages, idx):
    """Zero one row of every arena.  idx: (2,) int32 = (phys, off)."""
    return {n: lax.dynamic_update_slice(
                arena,
                jnp.zeros((arena.shape[0], 1, 1) + arena.shape[3:],
                          arena.dtype),
                _row_start(arena, idx[0], idx[1]))
            for n, arena in pages.items()}


class _KVCacheBase:
    """Shared bookkeeping: leaf classification and slot-state splicing."""

    def __init__(self, cfg: ModelConfig, batch_slots: int, max_len: int):
        self.cfg = cfg
        self.b = batch_slots
        self.max_len = max_len
        # shapes only — the full contiguous cache is never materialized in
        # paged mode (it is the allocation the block pool exists to avoid)
        proto = jax.eval_shape(
            lambda: factory.init_cache(cfg, batch_slots, max_len))
        self.seq_names, self.state_names = classify_cache(proto, max_len)
        self.seq_shapes = {n: proto[n] for n in self.seq_names}
        self.state = {n: jnp.zeros(proto[n].shape, proto[n].dtype)
                      for n in self.state_names}

    def set_slot_state(self, slot: int, state_rows: dict) -> None:
        """Install a finished prefill's recurrent states for one slot.
        state_rows: {name: (Lx, ...)} with the batch dim squeezed out."""
        for name in self.state_names:
            if name in state_rows:
                self.state[name] = self.state[name].at[:, slot].set(
                    state_rows[name])

    def zero_slot_state(self, slot: int) -> None:
        for name in self.state_names:
            self.state[name] = self.state[name].at[:, slot].set(0)


class PagedKVCache(_KVCacheBase):
    def __init__(self, cfg: ModelConfig, batch_slots: int, max_len: int,
                 block_size: int = 16, num_blocks: int | None = None):
        super().__init__(cfg, batch_slots, max_len)
        self.block_size = block_size
        self.blocks_per_slot = -(-max_len // block_size)
        if num_blocks is None:
            num_blocks = batch_slots * self.blocks_per_slot
        self.num_blocks = num_blocks
        self.view_len = self.blocks_per_slot * block_size
        # arenas: (Lx, B, S, ...) -> (Lx, num_blocks, block_size, ...)
        self.pages = {
            n: jnp.zeros(
                (s.shape[0], num_blocks, block_size) + s.shape[3:],
                s.dtype)
            for n, s in self.seq_shapes.items()
        }
        # host-side allocator
        self.block_tables = np.zeros((batch_slots, self.blocks_per_slot),
                                     np.int32)
        self.n_blocks = np.zeros(batch_slots, np.int32)
        self._resv = np.zeros(batch_slots, np.int64)
        self._free = list(range(num_blocks - 1, -1, -1))
        self._quarantined: list = []    # fault-drill OOM pressure pool
        self._view = None
        self._view_dirty = True
        # instance attributes, so a test can wrap one cache's programs
        self._gather = kv_gather_view
        self._scatter_decode = kv_scatter_decode
        self._scatter_chunk = kv_scatter_chunk
        self._mask_state = kv_mask_state
        self._scrub = kv_scrub

    # ----------------------------------------------------------- allocator
    def blocks_needed(self, n_tokens: int) -> int:
        return min(-(-n_tokens // self.block_size), self.blocks_per_slot)

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def blocks_in_use(self) -> int:
        return self.num_blocks - len(self._free)

    def reserve(self, slot: int, n_tokens: int) -> bool:
        """Admission control: reserve the worst-case block count for a
        request.  False when the unreserved pool cannot cover it."""
        need = self.blocks_needed(n_tokens) - int(self.n_blocks[slot])
        avail = len(self._free) - int(self._resv.sum())
        if need > avail:
            return False
        self._resv[slot] = max(need, 0)
        return True

    def ensure(self, slot: int, n_tokens: int) -> None:
        """Grow the slot's block table to address ``n_tokens`` tokens
        (draws from the reservation, so it cannot fail post-admission)."""
        need = self.blocks_needed(n_tokens)
        while self.n_blocks[slot] < need:
            if not self._free:
                raise RuntimeError(
                    "paged KV cache exhausted despite reservation — "
                    "allocator invariant violated")
            phys = self._free.pop()
            self.block_tables[slot, self.n_blocks[slot]] = phys
            self.n_blocks[slot] += 1
            if self._resv[slot] > 0:
                self._resv[slot] -= 1

    def free_slot(self, slot: int) -> None:
        for j in range(int(self.n_blocks[slot])):
            self._free.append(int(self.block_tables[slot, j]))
        self.n_blocks[slot] = 0
        self._resv[slot] = 0
        self.block_tables[slot] = 0
        self.zero_slot_state(slot)

    def quarantine_blocks(self, n: int) -> int:
        """Fault drill: withhold up to ``n`` free blocks to simulate arena
        pressure.  Only blocks beyond the outstanding reservations are
        taken — admitted requests keep their "ensure cannot fail"
        guarantee; the pressure lands on *admission* (reserve), which is
        the contract's pushback point.  Returns how many were taken."""
        take = max(0, min(n, len(self._free) - int(self._resv.sum())))
        for _ in range(take):
            self._quarantined.append(self._free.pop())
        return take

    def release_quarantined(self) -> int:
        n = len(self._quarantined)
        self._free.extend(self._quarantined)
        self._quarantined = []
        return n

    def arena_check(self) -> dict:
        """Allocator invariant: every physical block is in exactly one of
        {free, quarantined, some slot's table}, reservations never exceed
        the free pool.  Raises RuntimeError on violation (the leak-class
        tripwire the engine can run after every step); returns the
        accounting."""
        allocated = []
        for slot in range(self.b):
            allocated.extend(int(x) for x in
                             self.block_tables[slot, :int(self.n_blocks[slot])])
        every = allocated + [int(x) for x in self._free] + \
            [int(x) for x in self._quarantined]
        acct = {"allocated": len(allocated), "free": len(self._free),
                "quarantined": len(self._quarantined),
                "reserved": int(self._resv.sum()),
                "num_blocks": self.num_blocks}
        if len(every) != self.num_blocks or len(set(every)) != len(every) \
                or any(x < 0 or x >= self.num_blocks for x in every):
            raise RuntimeError(
                f"paged arena accounting violated (leaked or double-owned "
                f"blocks): {acct}")
        if acct["reserved"] > acct["free"]:
            raise RuntimeError(
                f"outstanding reservations exceed the free pool: {acct}")
        return acct

    def scrub_row(self, slot: int, pos: int) -> None:
        """Zero one committed KV row (every layer/leaf) of a slot — the
        quarantine path's cleanup for a row written by a poisoned decode.
        Attention masks scores beyond ``len``, but a NaN row still poisons
        ``sum(p * v)`` through ``0 * NaN``, so the row must be physically
        zeroed, not just masked."""
        if not self.pages or pos >= self.view_len:
            return
        logical = min(pos // self.block_size, self.blocks_per_slot - 1)
        if logical >= int(self.n_blocks[slot]):
            return
        phys = int(self.block_tables[slot, logical])
        off = pos % self.block_size
        self.pages = self._scrub(self.pages,
                                 jnp.asarray([phys, off], jnp.int32))
        self._view_dirty = True

    def invalidate_view(self) -> None:
        """Force the next ``gather_view`` to rebuild from the pages —
        needed when a tick ran more than one decode closure (healthy +
        degraded), because ``apply_decode`` caches the *last* closure's
        view which holds the other population's uncommitted rows."""
        self._view_dirty = True

    # --------------------------------------------------------------- views
    def gather_view(self, lens) -> dict:
        """Contiguous (Lx, B, view_len, ...) cache view for the jitted
        decode step.  Rebuilt from the pages only after a write that
        bypassed the view (``scatter_chunk``, ``scrub_row``,
        ``invalidate_view``); otherwise the last decode tick's output,
        which holds every committed row at its logical position.  A new
        block changes where later rows land in the pages, not what the
        view holds, so block growth keeps it.  Rows past a slot's ``len``
        may hold stale data — masked by attention."""
        if self._view_dirty or self._view is None:
            self._view = self._gather(self.pages,
                                      jnp.asarray(self.block_tables))
            self._view_dirty = False
        cache = dict(self._view)
        cache.update(self.state)
        cache["len"] = jnp.asarray(lens, jnp.int32)
        return cache

    def apply_decode(self, new_cache: dict, lens, active) -> None:
        """Commit one decode tick: for each active slot, scatter the row
        written at ``lens[i]`` into its page; inactive slots' writes are
        dropped (OOB physical block) and their states restored."""
        lens = np.asarray(lens)
        active = np.asarray(active)
        logical = np.minimum(lens // self.block_size,
                             self.blocks_per_slot - 1)
        phys = np.where(active,
                        self.block_tables[np.arange(self.b), logical],
                        self.num_blocks)                 # OOB -> dropped
        off = lens % self.block_size
        if self.pages:
            idx = jnp.asarray(np.stack([lens, phys, off]).astype(np.int32))
            self.pages = self._scatter_decode(
                self.pages, {n: new_cache[n] for n in self.seq_names}, idx)
            # the view already contains this tick's writes for every slot;
            # inactive slots' garbage rows sit at their len (masked)
            self._view = {n: new_cache[n] for n in self.seq_names}
        if self.state_names:
            self.state = self._mask_state(
                self.state, {n: new_cache[n] for n in self.state_names},
                jnp.asarray(active.reshape(-1)))

    def scatter_chunk(self, slot: int, rows: dict, start: int,
                      count: int) -> None:
        """Splice a prefill chunk's rows (Lx, C, ...) into the slot's pages
        at positions start..start+count-1 (the C-count pad rows drop)."""
        if not self.pages:
            return
        c = next(iter(rows.values())).shape[1]
        bs = self.block_size
        first = start % bs
        # every block the chunk can touch (a static count per C); blocks
        # past ``count`` rows or past the slot's table drop
        logical = start // bs + np.arange(-(-(c + bs - 1) // bs))
        used = ((logical * bs < start + count)
                & (logical < self.n_blocks[slot]))
        phys = np.where(
            used,
            self.block_tables[slot, np.minimum(logical,
                                               self.blocks_per_slot - 1)],
            self.num_blocks).astype(np.int32)
        self.pages = self._scatter_chunk(
            self.pages, {n: rows[n] for n in self.seq_names},
            jnp.asarray(phys), jnp.asarray([first, count], jnp.int32))
        self._view_dirty = True


class ContiguousKVCache(_KVCacheBase):
    """The classic one-arena-per-slot cache behind the paged interface."""

    def __init__(self, cfg: ModelConfig, batch_slots: int, max_len: int,
                 **_):
        super().__init__(cfg, batch_slots, max_len)
        self.view_len = max_len
        self.store = {n: jnp.zeros(s.shape, s.dtype)
                      for n, s in self.seq_shapes.items()}
        b = batch_slots

        @jax.jit
        def apply_decode(store, state, new_cache, lens, active):
            s_out = {}
            for n, old in store.items():
                s = old.shape[2]
                at_pos = ((jnp.arange(s)[None, :] == lens[:, None])
                          & active[:, None])             # (B, S)
                m = at_pos.reshape((1, b, s) + (1,) * (old.ndim - 3))
                s_out[n] = jnp.where(m, new_cache[n].astype(old.dtype), old)
            st_out = {}
            for n, old in state.items():
                m = active.reshape((1, b) + (1,) * (old.ndim - 2))
                st_out[n] = jnp.where(m, new_cache[n].astype(old.dtype),
                                      old)
            return s_out, st_out

        self._apply = apply_decode

    def blocks_needed(self, n_tokens: int) -> int:
        return 0

    def reserve(self, slot: int, n_tokens: int) -> bool:
        return True

    def ensure(self, slot: int, n_tokens: int) -> None:
        pass

    def free_slot(self, slot: int) -> None:
        # stale K/V rows beyond len are masked out; states must be zeroed
        self.zero_slot_state(slot)

    def quarantine_blocks(self, n: int) -> int:
        return 0                      # no arena to pressure

    def release_quarantined(self) -> int:
        return 0

    def arena_check(self) -> dict:
        return {"allocated": 0, "free": 0, "quarantined": 0,
                "reserved": 0, "num_blocks": 0}

    def scrub_row(self, slot: int, pos: int) -> None:
        for n in self.seq_names:
            self.store[n] = self.store[n].at[:, slot, pos].set(0)

    def invalidate_view(self) -> None:
        pass                          # gather_view reads the store directly

    def gather_view(self, lens) -> dict:
        cache = dict(self.store)
        cache.update(self.state)
        cache["len"] = jnp.asarray(lens, jnp.int32)
        return cache

    def apply_decode(self, new_cache: dict, lens, active) -> None:
        self.store, self.state = self._apply(
            self.store, self.state, new_cache,
            jnp.asarray(np.asarray(lens)),
            jnp.asarray(np.asarray(active).reshape(-1)))

    def scatter_chunk(self, slot: int, rows: dict, start: int,
                      count: int) -> None:
        for n in self.seq_names:
            self.store[n] = self.store[n].at[
                :, slot, start : start + count].set(rows[n][:, :count])


def make_kv_cache(cfg: ModelConfig, batch_slots: int, max_len: int,
                  paged: bool = True, block_size: int = 16,
                  num_blocks: int | None = None):
    if paged:
        return PagedKVCache(cfg, batch_slots, max_len,
                            block_size=block_size, num_blocks=num_blocks)
    return ContiguousKVCache(cfg, batch_slots, max_len)
