"""Paged KV cache: block-pool allocator, gather/scatter views, pspecs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.configs.registry import get_config
from repro.models import factory
from repro.serve.paged_cache import (ContiguousKVCache, PagedKVCache,
                                     classify_cache, kv_gather_view)
from repro.sharding import partition

KEY = jax.random.PRNGKey(0)


def _cfg():
    return get_config("granite-3-2b", reduced=True)


def test_classify_cache_families():
    cfg = _cfg()
    seq, state = classify_cache(factory.init_cache(cfg, 2, 32), 32)
    assert sorted(seq) == ["k", "v"] and state == []
    rcfg = get_config("rwkv6-1.6b", reduced=True)
    seq, state = classify_cache(factory.init_cache(rcfg, 2, 32), 32)
    assert seq == [] and sorted(state) == ["cm_x", "tm_x", "wkv"]
    zcfg = get_config("zamba2-2.7b", reduced=True)
    seq, state = classify_cache(factory.init_cache(zcfg, 2, 32), 32)
    assert sorted(seq) == ["k", "v"] and sorted(state) == ["conv", "ssm"]
    icfg = _cfg().replace(kv_cache_dtype="int8")
    seq, _ = classify_cache(factory.init_cache(icfg, 2, 32), 32)
    assert sorted(seq) == ["k", "k_scale", "v", "v_scale"]


def test_allocator_alloc_free_reuse():
    pc = PagedKVCache(_cfg(), batch_slots=2, max_len=32, block_size=8,
                      num_blocks=6)
    assert pc.blocks_per_slot == 4
    assert pc.reserve(0, 20)        # 3 blocks
    assert pc.reserve(1, 24)        # 3 blocks
    pc.ensure(0, 9)                 # 2 blocks materialize
    assert pc.blocks_in_use == 2 and pc.free_blocks == 4
    # pool fully spoken for: a third reservation must fail
    assert not pc.reserve(1, 32)    # slot 1 would now need 4 > avail
    pc.ensure(1, 24)
    assert pc.blocks_in_use == 5
    used = set(pc.block_tables[0, :2]) | set(pc.block_tables[1, :3])
    assert len(used) == 5           # distinct physical blocks
    pc.free_slot(0)
    assert pc.free_blocks == 3 and pc.n_blocks[0] == 0
    assert pc.reserve(0, 24)        # freed blocks admit the next request
    pc.ensure(0, 24)
    assert pc.blocks_in_use == 6


def test_ensure_is_covered_by_reservation():
    pc = PagedKVCache(_cfg(), batch_slots=1, max_len=32, block_size=8,
                      num_blocks=4)
    assert pc.reserve(0, 32)
    for n in range(1, 33):
        pc.ensure(0, n)             # lazy growth never fails
    assert pc.blocks_in_use == 4


def test_paged_gather_scatter_roundtrip():
    """Rows written through pages must read back exactly at their
    positions in the gathered contiguous view."""
    cfg = _cfg()
    pc = PagedKVCache(cfg, batch_slots=2, max_len=24, block_size=8)
    rng = np.random.default_rng(0)
    chunk = 6
    rows = {n: jnp.asarray(rng.standard_normal(
        (cfg.n_layers, chunk) + pc.pages[n].shape[3:]).astype(np.float32))
        for n in pc.seq_names}
    pc.reserve(1, 14)
    pc.ensure(1, 10)
    pc.scatter_chunk(1, rows, start=4, count=5)   # 6th row dropped
    view = pc.gather_view(np.array([0, 9]))
    for n in pc.seq_names:
        got = np.asarray(view[n][:, 1, 4:9])
        np.testing.assert_array_equal(got, np.asarray(rows[n][:, :5]))
        assert np.all(np.asarray(view[n][:, 1, 9:10]) == 0)  # dropped row


def test_paged_decode_write_masks_inactive_slots():
    cfg = _cfg()
    pc = PagedKVCache(cfg, batch_slots=2, max_len=16, block_size=8)
    for i in range(2):
        pc.reserve(i, 8)
        pc.ensure(i, 4)
    lens = np.array([2, 3])
    view = pc.gather_view(lens)
    fake = {n: jnp.ones_like(view[n]) for n in pc.seq_names}
    pc.apply_decode(fake, lens, active=np.array([True, False]))
    # regather from the arena: the active slot's row landed in its page,
    # the inactive slot's write was dropped (OOB physical block)
    pc._view_dirty = True
    view2 = pc.gather_view(lens)
    assert np.all(np.asarray(view2["k"][:, 0, 2]) == 1)   # active write
    assert np.all(np.asarray(view2["k"][:, 1, 3]) == 0)   # dropped write


def test_contiguous_wrapper_matches_interface():
    cfg = _cfg()
    cc = ContiguousKVCache(cfg, batch_slots=2, max_len=16)
    assert cc.reserve(0, 999) and cc.blocks_needed(999) == 0
    view = cc.gather_view(np.array([0, 0]))
    assert view["k"].shape[2] == 16


def test_paged_cache_pspecs():
    cfg = _cfg()
    pc = PagedKVCache(cfg, batch_slots=2, max_len=32, block_size=8)
    n = jax.device_count()
    mesh = compat.make_mesh((n, 1), ("data", "model"))
    specs = partition.paged_cache_pspecs(pc.pages, mesh)
    for name, spec in specs.items():
        assert spec[0] is None          # layer-stack never sharded
        assert spec[2] is None          # intra-block rows never split
    # a sharded device_put must succeed (blocks divide the data axis or
    # fall back to replication)
    arr = jax.device_put(pc.pages["k"],
                         jax.sharding.NamedSharding(mesh, specs["k"]))
    assert arr.shape == pc.pages["k"].shape


def _old_scatter(ref, rows, phys, off):
    """The out-of-place scatter the cache used to run, on host copies:
    ``arena.at[:, phys, off].set(rows, mode="drop")`` row by row."""
    for j in range(len(phys)):
        if phys[j] < ref.shape[1]:
            ref[:, phys[j], off[j]] = rows[:, j]


def _random_like(rng, shape, dtype):
    x = rng.standard_normal(shape) * 4
    return jnp.asarray(x.astype(np.float32)).astype(dtype)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_paged_view_and_pages_stay_in_step_across_block_growth(kv_dtype):
    """Decode ticks that cross several block boundaries, with an inactive
    slot, a mid-run prefill splice, a scrub and a forced invalidation.
    At every tick the cached view equals a view rebuilt from the pages
    below each slot's ``len``, and the in-place writes leave the pages bit
    for bit where the old out-of-place scatter left them.  The gather runs
    on the first tick and after a write that bypasses the view, never
    because a slot took a new block."""
    cfg = _cfg() if kv_dtype is None else _cfg().replace(
        kv_cache_dtype=kv_dtype)
    b, bs, chunk = 3, 4, 8
    pc = PagedKVCache(cfg, batch_slots=b, max_len=40, block_size=bs)
    gathers = []
    real_gather = pc._gather
    pc._gather = lambda *a: gathers.append(1) or real_gather(*a)
    rng = np.random.default_rng(0)
    ref = {n: np.array(a) for n, a in pc.pages.items()}

    def splice(slot, start, count):
        pc.ensure(slot, start + count)
        rows = {n: _random_like(rng, (a.shape[0], chunk) + a.shape[3:],
                                a.dtype) for n, a in pc.pages.items()}
        pos = start + np.arange(chunk)
        phys = np.where(np.arange(chunk) < count,
                        pc.block_tables[slot, pos // bs], pc.num_blocks)
        for n in ref:
            _old_scatter(ref[n], np.asarray(rows[n]), phys, pos % bs)
        pc.scatter_chunk(slot, rows, start, count)

    lens = np.zeros(b, np.int32)
    active = np.zeros(b, bool)
    for slot, plen in ((0, 3), (1, 6)):
        assert pc.reserve(slot, 40)
        splice(slot, 0, plen)
        lens[slot], active[slot] = plen, True
    bypassed, grew = True, 0
    for tick in range(22):
        if tick == 5:                      # slot 2 admitted mid-run
            assert pc.reserve(2, 40)
            splice(2, 0, 5)
            lens[2], active[2] = 5, True
            bypassed = True
        if tick == 9:
            pc.scrub_row(0, 2)
            for n in ref:
                ref[n][:, pc.block_tables[0, 0], 2] = 0
            bypassed = True
        if tick == 12:
            pc.invalidate_view()
            bypassed = True
        if tick == 15:                     # slot 1 finishes: inactive
            pc.free_slot(1)
            lens[1], active[1] = 0, False
        before = pc.n_blocks.copy()
        for i in np.flatnonzero(active):
            pc.ensure(i, int(lens[i]) + 1)
        grew += int((pc.n_blocks > before).any())
        n_gathers = len(gathers)
        view = pc.gather_view(lens)
        assert len(gathers) - n_gathers == int(bypassed), tick
        bypassed = False
        rebuilt = kv_gather_view(pc.pages, jnp.asarray(pc.block_tables))
        for n in pc.seq_names:
            for i in range(b):
                np.testing.assert_array_equal(
                    np.asarray(view[n][:, i, :lens[i]]),
                    np.asarray(rebuilt[n][:, i, :lens[i]]))
        # the step writes a row at every slot's len, inactive ones too
        new = dict(view)
        for n in pc.seq_names:
            rows = _random_like(rng, (view[n].shape[0], b)
                                + view[n].shape[3:], view[n].dtype)
            new[n] = view[n].at[:, np.arange(b), lens].set(rows)
            phys = np.where(active, pc.block_tables[np.arange(b),
                                                    lens // bs],
                            pc.num_blocks)
            _old_scatter(ref[n], np.asarray(rows), phys, lens % bs)
        pc.apply_decode(new, lens, active)
        for n in pc.seq_names:
            np.testing.assert_array_equal(np.asarray(pc.pages[n]), ref[n])
        lens[active] += 1
    assert grew >= 8                       # ticks on which a slot grew
    assert len(gathers) == 4               # first tick, splice, scrub, flush
