"""Paged KV cache invariants (serving/paged_cache.py): block
alloc/free/reuse, free-list conservation, no page shared by two live
requests, scratch page 0 never handed out."""
import numpy as np
import pytest

from paddle_tpu.serving.paged_cache import PagedKVCache


def make_cache(n_blocks=16, block_size=4):
    return PagedKVCache(n_layers=2, n_blocks=n_blocks,
                        block_size=block_size, n_heads=2, head_dim=4)


class TestAllocFree:
    def test_alloc_sizes_and_uniqueness(self):
        c = make_cache()
        a = c.alloc("a", 9)     # ceil(9/4) = 3 pages
        b = c.alloc("b", 4)     # 1 page
        assert len(a) == 3 and len(b) == 1
        assert 0 not in a + b                  # scratch never allocated
        assert len(set(a + b)) == 4            # no sharing
        assert c.n_free == 15 - 4
        c.check_invariants()

    def test_free_returns_pages_without_touching_neighbors(self):
        c = make_cache()
        a = c.alloc("a", 8)
        b = c.alloc("b", 8)
        before_b = c.table("b")
        c.free("a")
        assert c.table("b") == before_b        # neighbor untouched
        assert c.n_free == 15 - 2
        c.check_invariants()

    def test_lifo_reuse(self):
        c = make_cache()
        a = c.alloc("a", 4)
        c.free("a")
        b = c.alloc("b", 4)
        assert b == a                          # hottest page reused

    def test_double_alloc_and_bad_free_raise(self):
        c = make_cache()
        c.alloc("a", 4)
        with pytest.raises(ValueError, match="already holds"):
            c.alloc("a", 4)
        with pytest.raises(KeyError):
            c.free("zzz")

    def test_exhaustion_raises_and_can_alloc_predicts(self):
        c = make_cache(n_blocks=4)             # 3 allocatable
        assert c.can_alloc(12) and not c.can_alloc(13)
        c.alloc("a", 12)
        assert not c.can_alloc(1)
        with pytest.raises(MemoryError, match="exhausted"):
            c.alloc("b", 1)
        c.check_invariants()

    def test_conservation_under_churn(self):
        rng = np.random.RandomState(0)
        c = make_cache(n_blocks=32, block_size=4)
        live = {}
        for i in range(200):
            if live and (rng.rand() < 0.4 or not c.can_alloc(16)):
                rid = rng.choice(sorted(live))
                c.free(rid)
                del live[rid]
            else:
                n = int(rng.randint(1, 17))
                if c.can_alloc(n):
                    live[f"r{i}"] = c.alloc(f"r{i}", n)
            c.check_invariants()
        assert c.n_free + c.n_live + 1 == 32


class TestTableArray:
    def test_padding_and_dummy_lanes(self):
        c = make_cache()
        a = c.alloc("a", 9)
        t = c.table_array(["a", None], width=5)
        assert t.shape == (2, 5) and t.dtype == np.int32
        assert list(t[0, :3]) == a
        assert (t[0, 3:] == 0).all()           # pad -> scratch
        assert (t[1] == 0).all()               # dummy lane -> scratch

    def test_width_guard(self):
        c = make_cache()
        c.alloc("a", 16)                       # 4 pages
        with pytest.raises(ValueError, match="table width"):
            c.table_array(["a"], width=3)


class TestConstruction:
    def test_pool_shapes_and_dtype(self):
        c = PagedKVCache(n_layers=3, n_blocks=8, block_size=4,
                         n_heads=2, head_dim=5, dtype="bfloat16")
        assert len(c.pools) == 3
        k, v = c.pools[0]
        # one row a token, heads side by side: [n_blocks, bs, nh * hd]
        assert k.shape == (8, 4, 2 * 5) == v.shape
        assert str(k.dtype) == "bfloat16"
        assert (c.n_heads, c.head_dim) == (2, 5)

    @pytest.mark.parametrize("n_heads,head_dim", [(20, 64), (4, 16),
                                                  (8, 128)])
    def test_one_page_shape_for_every_model(self, n_heads, head_dim):
        """No option keeps a 4-D page: the merged width is read from
        the model, and the sizing math still counts heads x head."""
        c = PagedKVCache(n_layers=1, n_blocks=4, block_size=16,
                         n_heads=n_heads, head_dim=head_dim,
                         dtype="bfloat16")
        k, v = c.pools[0]
        assert k.shape == v.shape == (4, 16, n_heads * head_dim)
        assert c.stats()["pool_bytes"] == 2 * k.nbytes

    def test_bad_shapes_raise(self):
        with pytest.raises(ValueError, match="n_blocks"):
            make_cache(n_blocks=1)
        with pytest.raises(ValueError, match="block_size"):
            make_cache(block_size=0)
