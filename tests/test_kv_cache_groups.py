"""The cache manager's page GROUPS (``serving/kv_cache.py``): one manager,
groups as data. A ``full`` group keeps a context's every page; a ``window``
group frees a page once its last token lies behind every later query's
window. Host-side bookkeeping on tiny pools: admit, grow, shrink, release,
swap, exhaustion of one group, ``fits_ever``, the per-group invariants and
the bound on a slot's window pages."""
import numpy as np
import pytest

from paddle_tpu.serving.kv_cache import (NULL_PAGE, CacheLeaf,
                                         PagedCacheConfig, PagedKVCache,
                                         PageGroup)

PAGE, PPS, W = 4, 16, 8
LEAVES = (CacheLeaf("k_pool", (8,), np.float32),
          CacheLeaf("v_pool", (8,), np.float32))
GROUPS = (PageGroup("full", (3,)), PageGroup("window", (0, 1, 2), window=W))
#: what a window of 8 takes at pages of 4, a page's edge anywhere: 3
MOST = -(-W // PAGE) + 1


def cache(full=40, window=12, **over):
    cfg = dict(num_layers=4, leaves=LEAVES, groups=GROUPS,
               group_pages=(window,), num_pages=full, page_size=PAGE,
               max_batch=3, pages_per_seq=PPS, enable_prefix_caching=False)
    cfg.update(over)
    return PagedKVCache(PagedCacheConfig(**cfg))


def test_a_model_of_one_group_is_what_it_was():
    """No groups stated: one ``full`` group of every layer; the cache's
    allocator, table and slot pages are that group's own objects; a launch
    uploads the one 2-D table."""
    c = PagedKVCache(PagedCacheConfig(num_layers=2, leaves=LEAVES,
                                      num_pages=9, page_size=PAGE,
                                      max_batch=2, pages_per_seq=4))
    g, = c.groups
    assert (g.name, g.layers, g.window) == ("full", (0, 1), None)
    assert c.allocator is g.allocator and c.page_table is g.table
    assert c.tables is c.page_table and c.tables.shape == (2, 4)
    assert not c.has_windows and c.release_behind(0, 100) == 0
    assert c.admit(0, 6) and c.page_table[0].tolist() == [1, 2, 0, 0]
    assert "groups" not in c.stats()
    assert c.residency() == (4, 4)
    c.check_invariants()


def test_each_group_has_its_pages_table_and_pools():
    c = cache()
    assert [g.allocator.num_pages for g in c.groups] == [40, 12]
    assert [pl["k_pool"].shape[0] for pl in c.pools] == [12, 12, 12, 40]
    assert c.cfg.group_of_layer == (1, 1, 1, 0)
    assert c.tables.shape == (2, 3, PPS)
    assert c.tables[0] is not c.page_table and np.shares_memory(
        c.tables, c.page_table)
    assert c.admit(1, 10)                       # 3 pages in each group
    assert c.tables[:, 1, :4].tolist() == [[1, 2, 3, 0], [1, 2, 3, 0]]
    st = c.stats()
    assert st["pages_in_use"] == 3 and st["groups"]["window"] == {
        "pages_in_use": 3, "free_pages": 8, "usable_pages": 11,
        "layers": 3, "window": W, "window_pages_released": 0}
    # 3 pages x 1 layer + 3 pages x 3 layers; one lifetime: 3 x 4
    assert c.residency() == (12, 12)
    c.check_invariants()


def test_a_window_page_goes_back_once_no_later_query_sees_it():
    """The query at position q sees q - 7 .. q: page i (positions 4i ..
    4i + 3) is dead once 4i + 3 <= q - 8. The full group keeps all."""
    c = cache()
    assert c.admit(0, 22)                       # 6 pages, positions 0..21
    win = c.groups[1]
    assert c.release_behind(0, 10) == 0         # sees 3..10: page 0 lives
    assert c.release_behind(0, 11) == 1         # sees 4..11: page 0 is dead
    assert c.release_behind(0, 11) == 0
    assert win.first[0] == 1 and win.table[0, :7].tolist() == [
        NULL_PAGE, 2, 3, 4, 5, 6, 0]
    assert c.release_behind(0, 22) == 2         # sees 15..22: pages 1, 2
    assert win.pages[0] == [4, 5, 6] and c.window_pages(0) == {"window": 3}
    assert c.page_table[0, :6].tolist() == [1, 2, 3, 4, 5, 6]
    assert win.released == 3 and win.allocator.num_free == 8
    # the freed pages are handed out again, last freed first
    assert c.admit(1, 5) and win.pages[1] == [3, 2]
    c.check_invariants()
    # 6 + 2 full pages x 1 layer, 3 + 2 window pages x 3 layers; one
    # lifetime: 8 pages x 4 layers
    assert c.residency() == (8 + 15, 32)


def test_a_decoding_slot_holds_a_window_of_pages_and_no_more():
    """Token by token from an empty context to 60: after each step's
    release and growth the slot holds at most ``ceil(8 / 4) + 2`` window
    pages, the table names them at their positions' columns, and the
    invariants hold."""
    c = cache(window=6)                          # 5 usable pages
    assert c.admit(0, 1)
    seen = set()
    for ctx in range(1, 60):
        c.release_behind(0, ctx)
        assert c.grow(0, ctx + 1)
        held = c.window_pages(0)["window"]
        seen.add(held)
        assert held <= MOST + 1
        g = c.groups[1]
        assert g.first[0] + held == ctx // PAGE + 1
        c.check_invariants()
    assert max(seen) == MOST and len(c._slot_pages[0]) == 15
    c.release(0)
    assert c.stats()["groups"]["window"]["pages_in_use"] == 0
    c.check_invariants()


def test_exhaustion_of_either_group_is_exhaustion():
    """Admission takes a prompt's pages in every group or none; growth
    fails where any group has no page left, and the scheduler preempts."""
    c = cache(full=40, window=6)                # 5 usable window pages
    assert c.admit(0, 12)                       # 3 + 3
    assert not c.admit(1, 12)                   # the window group has 2
    assert 1 not in c._slot_pages and c.allocator.pages_in_use == 3
    assert c.admit(1, 8)                        # 2 + 2: the group is full
    assert not c.grow(0, 13)
    c.check_invariants()
    assert c.release_behind(0, 12) == 1 and c.grow(0, 13)
    # and the other way round: the full group runs out first
    c = cache(full=5, window=12)                # 4 usable full pages
    assert c.admit(0, 12) and not c.admit(1, 8)
    assert c.stats()["groups"]["window"]["pages_in_use"] == 3
    c.check_invariants()


def test_fits_ever_asks_every_group():
    c = cache(full=40, window=6)                # 39 and 5 usable
    assert c.fits_ever(20)                      # 5 pages everywhere
    assert not c.fits_ever(24)                  # 6 window pages, whole
    # told the prompt: a window group holds the prompt, then a window
    assert c.fits_ever(60, prompt_tokens=18)
    assert not c.fits_ever(60, prompt_tokens=24)
    assert not c.fits_ever(65)                  # past the table
    assert not cache(full=5, window=12).fits_ever(20)


def test_shrink_gives_back_the_tail_in_every_group():
    c = cache()
    assert c.admit(0, 10) and c.grow(0, 19)     # 5 pages each
    c.release_behind(0, 15)                     # window: pages 0, 1 dead
    assert c.shrink(0, 13) == 2                 # page 4 of each group
    assert len(c._slot_pages[0]) == 4 and c.groups[1].pages[0] == [3, 4]
    assert c.groups[1].table[0, :6].tolist() == [0, 0, 3, 4, 0, 0]
    c.check_invariants()


def test_swap_carries_both_groups_pages_from_their_columns():
    """Out and back in bit for bit, into another slot and other pages:
    the full group's four pages from column 0, the window group's three
    from column 1, each layer's bytes its own group's."""
    import jax.numpy as jnp

    c = cache()
    assert c.admit(0, 14)
    c.release_behind(0, 13)                     # window page 0 is gone
    assert c.grow(0, 15)
    rng = np.random.default_rng(0)
    c.pools = [{k: jnp.asarray(rng.normal(size=a.shape), a.dtype)
                for k, a in pl.items()} for pl in c.pools]
    full_rows = np.asarray(c.pools[3]["k_pool"])[[1, 2, 3, 4]]
    win_rows = np.asarray(c.pools[0]["v_pool"])[[2, 3, 4]]
    assert c.admit(2, 5)                        # takes pages the first had
    handle = c.swap_out(0)
    assert handle.n_pages == 4 and handle.rest == ((3, 1),)
    assert handle.k.shape == (4, 4, PAGE, 8)    # 4 layers, widest group
    c.check_invariants()
    assert c.admit(0, 9)                        # shuffle the free lists
    assert c.swap_in(1, handle)
    g = c.groups[1]
    assert g.first[1] == 1 and len(g.pages[1]) == 3
    assert g.table[1, :5].tolist() == [0] + g.pages[1] + [0]
    assert np.array_equal(
        np.asarray(c.pools[3]["k_pool"])[c._slot_pages[1]], full_rows)
    assert np.array_equal(np.asarray(c.pools[0]["v_pool"])[g.pages[1]],
                          win_rows)
    assert c.compile_counts["swap_gather"] == 1 \
        == c.compile_counts["swap_scatter"]
    c.check_invariants()
    # no room in the window group: nothing changes
    tight = cache(window=3)
    assert not tight.swap_in(0, handle) and 0 not in tight._slot_pages
    tight.check_invariants()


@pytest.mark.parametrize("break_it, message", [
    (lambda c: c.groups[1].pages[1].append(c.groups[1].pages[0][0]),
     "a page in two live slots"),
    (lambda c: c.groups[1].allocator._free.append(c.groups[1].pages[0][0]),
     "a page free and live"),
    (lambda c: c.groups[1].table.__setitem__((0, 9), 5),
     "table row is not its pages"),
    (lambda c: c._slot_pos.__setitem__(0, 40),
     "holds a page behind the window"),
    (lambda c: c.groups[1].pages.pop(1), "the live pages are the slots'"),
])
def test_the_invariants_catch_what_they_name(break_it, message):
    c = cache()
    assert c.admit(0, 12) and c.admit(1, 6)
    c.release_behind(0, 11)
    c.check_invariants()
    break_it(c)
    with pytest.raises(AssertionError, match=message):
        c.check_invariants()


@pytest.mark.parametrize("config, message", [
    (dict(enable_prefix_caching=True), "cannot share pages by prefix"),
    (dict(groups=(PageGroup("w", (0, 1, 2, 3), window=W),),
          group_pages=()), "the first page group keeps"),
    (dict(groups=(PageGroup("a", (0, 1)), PageGroup("b", (1, 2)))),
     "each layer at most once"),
    (dict(group_pages=()), "need 1 entries of group_pages"),
    (dict(groups=(PageGroup("full", (3,)),
                  PageGroup("window", (0, 1, 2), window=0))),
     "window 0 < 1"),
])
def test_what_groups_cannot_be_refuses(config, message):
    with pytest.raises(ValueError, match=message):
        cache(**config)
