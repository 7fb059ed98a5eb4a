"""Copy-on-write prefix caching + ServingConfig + sharded decode.

The load-bearing property: with ``prefix_cache=True`` a request's greedy
tokens are **bit-identical** to cold solo serving — on fp and int8 caches,
under preemption and under speculative decode — because shared pages hold
exactly the KV the slot would have recomputed (chain-keyed, so position is
part of a page's identity) and no slot can ever write a page another slot
maps (boundary pages are copied at attach; ``prepare_write`` forks any
other shared page before a write could land).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import get_model
from repro.serve import (KVCacheSpec, LayerKVSpec, PagedKVCache,
                         PrefixIndex, Request, ServingConfig, ServingEngine,
                         derive_kv_spec)
from repro.serve.kv_cache import copy_page


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-1.5b", reduced=True)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def int8_spec(setup):
    cfg, model, params = setup
    return derive_kv_spec(model, params)


def _prefix_requests(cfg, n, sys_len=18, suffix_len=2, max_new=4, seed=0):
    """Shared system prompt + unique per-request suffix, request_id
    pinned so the same sampled streams reproduce under solo serving."""
    rng = np.random.default_rng(seed)
    system = rng.integers(0, cfg.vocab, size=(sys_len,))
    return [Request(prompt=np.concatenate(
                        [system, rng.integers(0, cfg.vocab,
                                              size=(suffix_len,))]),
                    max_new_tokens=max_new, request_id=i)
            for i in range(n)]


class _TinyCfg:
    """Minimal model-config stand-in for cache-level tests."""
    n_layers = 2
    n_kv_heads = 2
    hd = 4
    dtype = jnp.float32


def _tiny_cache(**kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("max_seq", 16)
    kw.setdefault("batch_slots", 2)
    kw.setdefault("prefix_cache", True)
    spec = KVCacheSpec.all_fp(_TinyCfg.n_layers)
    return PagedKVCache(_TinyCfg, spec, kw.pop("batch_slots"),
                        kw.pop("max_seq"), **kw)


def _page_content(cache, pg):
    return np.asarray(cache.pages[0]["k"][pg])


def _stamp_page(cache, pg, value):
    for pool in cache.pages:
        pool["k"] = pool["k"].at[pg].set(value)
        pool["v"] = pool["v"].at[pg].set(value)


# ---------------------------------------------------------------------------
# PrefixIndex
# ---------------------------------------------------------------------------

def test_prefix_index_chain_lookup_and_position_identity():
    idx = PrefixIndex()
    a, b = (1, 2, 3, 4), (5, 6, 7, 8)
    assert idx.register([a, b], [10, 11]) == [10, 11]
    assert idx.lookup([a, b]) == [10, 11]
    assert idx.lookup([a]) == [10]
    # position is part of the key: the same tokens under a different
    # parent chain do NOT match (RoPE'd KV would differ)
    assert idx.lookup([b]) == []
    assert idx.lookup([b, a]) == []


def test_prefix_index_first_writer_wins():
    idx = PrefixIndex()
    a = (1, 2, 3, 4)
    idx.register([a], [10])
    # a second walker with the same chain keeps the existing page; its
    # duplicate page is NOT indexed
    assert idx.register([a, (9, 9, 9, 9)], [77, 12]) == [12]
    assert idx.lookup([a]) == [10]
    assert not idx.is_registered(77)


def test_prefix_index_partial_lookup_longest_overlap():
    idx = PrefixIndex()
    a, b = (1, 2, 3, 4), (5, 6, 7, 8)
    idx.register([a, b], [10, 11])
    # mid-page overlap under the matched chain: 2 leading tokens shared
    m, pg = idx.partial_lookup(1, [a], (5, 6, 99, 99))
    assert (m, pg) == (2, 11)
    # no child shares a leading token → no overlap
    m, pg = idx.partial_lookup(1, [a], (42,))
    assert (m, pg) == (0, None)


def test_prefix_index_evict_cascades_subtree():
    idx = PrefixIndex()
    a, b, c = (1,) * 4, (2,) * 4, (3,) * 4
    idx.register([a, b], [10, 11])
    idx.register([a, c], [10, 12])      # second child under the root
    dropped = idx.evict(10)
    # the whole subtree goes: children are unreachable without the root
    assert set(dropped) == {10, 11, 12} and dropped[0] == 10
    assert len(idx) == 0
    assert idx.lookup([a]) == []


# ---------------------------------------------------------------------------
# cache level: refcounts, LRU reuse, fork-on-write isolation
# ---------------------------------------------------------------------------

def test_release_parks_registered_pages_in_lru_and_reattach_reclaims():
    cache = _tiny_cache()
    toks = list(range(8))                       # 2 full pages
    assert cache.grow(0, 8)
    owned = list(cache.owned[0])
    assert cache.register_prefix(0, toks) == 2
    cache.release(0)
    # registered pages park in the LRU (reusable), not the free list
    assert cache.cached_pages == 2 and cache.used_pages == 0
    assert all(pg not in cache.free for pg in owned)
    # a repeat prompt re-attaches them: LRU drains, refcounts bump,
    # the private pages admission allocated go back to the free list
    assert cache.grow(0, 9)
    cached = cache.attach_prefix(0, toks + [99])
    assert cached == 8
    assert cache.cached_pages == 0
    assert cache.owned[0][:2] == owned
    assert all(cache.ref[pg] == 1 for pg in owned)
    # releasing again re-parks them
    cache.release(0)
    assert cache.cached_pages == 2


def test_unregistered_pages_go_straight_to_free_list():
    cache = _tiny_cache()
    assert cache.grow(0, 8)
    cache.release(0)                            # nothing registered
    assert cache.cached_pages == 0
    assert len(cache.free) == cache.num_pages - 1


def test_grow_counts_lru_as_available_and_evicts_oldest():
    cache = _tiny_cache(max_seq=8, num_pages=3)  # pages 1..2 usable
    toks = list(range(8))
    assert cache.grow(0, 8)
    cache.register_prefix(0, toks)
    cache.release(0)
    assert not cache.free and cache.cached_pages == 2
    # the pool looks full but cached-free pages are reclaimable
    assert cache.grow(1, 8)
    assert cache.cached_pages == 0 and len(cache.index) == 0


def test_lru_eviction_cascade_frees_orphaned_descendants():
    cache = _tiny_cache(max_seq=8, num_pages=3)  # free list exhausted
    toks = list(range(8))
    assert cache.grow(0, 8)
    root, leaf = cache.owned[0]
    cache.register_prefix(0, toks)
    cache.release(0)
    # force the *root* to be reclaimed first (release order naturally
    # parks leaves older; this white-box reorder exercises the cascade)
    cache.lru.move_to_end(root, last=False)
    pg = cache._take_page()
    assert pg == root
    # the leaf's registration died with its parent: it fell from the
    # LRU to the free list instead of leaking as an unreachable entry
    assert leaf in cache.free and leaf not in cache.lru
    assert len(cache.index) == 0


def test_fork_on_write_isolates_sharers():
    cache = _tiny_cache()
    toks = list(range(8))
    assert cache.grow(0, 8)
    shared = list(cache.owned[0])
    for mark, pg in enumerate(shared, start=1):
        _stamp_page(cache, pg, float(mark))
    cache.register_prefix(0, toks)
    # slot 1 attaches the same prompt (plus a divergent tail page)
    assert cache.grow(1, 9)
    assert cache.attach_prefix(1, toks + [99]) == 8
    assert cache.owned[1][:2] == shared
    assert all(cache.ref[pg] == 2 for pg in shared)
    table_before = cache.table[0].copy()

    # a write at position 0 would land on shared pages: both must fork
    forks_before = cache.forks
    assert cache.prepare_write(1, 0)
    assert cache.forks == forks_before + 2
    assert all(a != b for a, b in zip(cache.owned[1][:2], shared))
    # fork copies content...
    for mark, pg in enumerate(cache.owned[1][:2], start=1):
        np.testing.assert_array_equal(_page_content(cache, pg),
                                      np.full((4, 2, 4), float(mark)))
    # ...and slot 0 keeps its mapping, refcounts back to 1
    np.testing.assert_array_equal(cache.table[0], table_before)
    assert all(cache.ref[pg] == 1 for pg in shared)
    # slot 1 scribbling on its forked page never reaches slot 0
    _stamp_page(cache, cache.owned[1][0], -1.0)
    np.testing.assert_array_equal(_page_content(cache, shared[0]),
                                  np.full((4, 2, 4), 1.0))


def test_attach_copies_boundary_page_instead_of_sharing():
    cache = _tiny_cache()
    toks = list(range(8))
    assert cache.grow(0, 8)
    shared = list(cache.owned[0])
    _stamp_page(cache, shared[1], 7.0)
    cache.register_prefix(0, toks)
    # new prompt diverges mid-page-2: tokens 0..5 match, 6 differs
    assert cache.grow(1, 8)
    priv = cache.owned[1][1]
    cached = cache.attach_prefix(1, toks[:6] + [42, 43])
    assert cached == 6
    # page 1 shared, page 2 copied into the slot's own page (ref stays 1)
    assert cache.owned[1][0] == shared[0] and cache.owned[1][1] == priv
    assert cache.ref[shared[1]] == 1 and cache.ref[priv] == 1
    np.testing.assert_array_equal(_page_content(cache, priv),
                                  np.full((4, 2, 4), 7.0))
    # the boundary copy counts as a fork
    assert cache.forks == 1


def test_prepare_write_above_frontier_is_noop():
    cache = _tiny_cache()
    toks = list(range(8))
    assert cache.grow(0, 8)
    cache.register_prefix(0, toks)
    assert cache.grow(1, 9)
    cached = cache.attach_prefix(1, toks + [99])
    forks = cache.forks
    # the normal serving flow only writes at/above the attach frontier,
    # which lands in the slot's private tail page: nothing to fork
    assert cache.prepare_write(1, cached)
    assert cache.forks == forks


# ---------------------------------------------------------------------------
# the page copy: one donated executable over every pool
# ---------------------------------------------------------------------------

def _mixed_cache(**kw):
    """Tiny cache whose first layer is fp32 and second int8, its pools
    filled with seeded values; returns the cache and a numpy copy of
    every pool.  The pools are built from the numpy arrays, never read
    back: on the CPU a numpy view of a jax array pins its buffer, and a
    pinned buffer is copied instead of donated."""
    kw.setdefault("page_size", 4)
    spec = KVCacheSpec((LayerKVSpec(int8=False),
                        LayerKVSpec(int8=True, k_scale=np.ones(2),
                                    v_scale=np.ones(2))))
    cache = PagedKVCache(_TinyCfg, spec, 2, 16, prefix_cache=True, **kw)
    rng = np.random.default_rng(3)
    want = [{n: (rng.integers(-128, 128, p[n].shape).astype(np.int8)
                 if p[n].dtype == jnp.int8 else
                 rng.standard_normal(p[n].shape).astype(np.float32))
             for n in ("k", "v")} for p in cache.pages]
    cache.pages = [{n: jnp.asarray(w[n]) for n in w} for w in want]
    return cache, want


@pytest.mark.parametrize("src,dst", [(1, 8), (8, 1), (4, 5)])
def test_copy_page_bit_exact_on_every_pool(src, dst):
    cache, want = _mixed_cache()
    assert [p["k"].dtype for p in cache.pages] == [jnp.float32, jnp.int8]
    cache._copy_page(src, dst)
    for got, w in zip(cache.pages, want):
        for n in ("k", "v"):
            w[n][dst] = w[n][src]            # only page dst changes
            assert got[n].dtype == w[n].dtype
            np.testing.assert_array_equal(np.asarray(got[n]), w[n])


def test_copy_page_compiles_once_for_every_page_pair():
    """The pages are traced: the first copy on a pool geometry compiles
    the one executable (fp and int8 pools alike), and no later copy,
    boundary copy at attach or fork, compiles again."""
    cache, _ = _mixed_cache(page_size=2)       # a geometry of its own
    n0 = copy_page._cache_size()
    cache._copy_page(1, 2)
    assert copy_page._cache_size() == n0 + 1
    for src, dst in [(3, 4), (8, 1), (16, 5)]:
        cache._copy_page(src, dst)
    toks = list(range(4))
    assert cache.grow(0, 4)
    cache.register_prefix(0, toks)
    assert cache.grow(1, 4)
    assert cache.attach_prefix(1, toks[:3] + [42]) == 3   # boundary copy
    assert cache.prepare_write(1, 0)                      # a fork
    assert cache.forks == 2
    assert copy_page._cache_size() == n0 + 1


def test_copy_page_donates_the_pools():
    cache, _ = _mixed_cache()
    before = [p[n] for p in cache.pages for n in ("k", "v")]
    cache._copy_page(2, 3)
    assert all(a.is_deleted() for a in before)
    assert not any(p[n].is_deleted() for p in cache.pages
                   for n in ("k", "v"))


def test_copy_page_keeps_the_pools_sharding():
    """On a 2-device mesh (subprocess: the device count is fixed when
    jax is imported) the copied pools keep their KV-head sharding."""
    script = """
import jax, jax.numpy as jnp, numpy as np
assert len(jax.devices()) == 2, jax.devices()
from repro.launch.mesh import make_debug_mesh
from repro.launch.shardings import kv_pool_sharding
from repro.serve import KVCacheSpec, PagedKVCache

class Cfg:
    n_layers, n_kv_heads, hd, dtype = 2, 2, 4, jnp.float32

sharding = kv_pool_sharding(make_debug_mesh(2), Cfg.n_kv_heads)
assert not sharding.is_fully_replicated
cache = PagedKVCache(Cfg, KVCacheSpec.all_fp(2), 2, 16, page_size=4,
                     prefix_cache=True, sharding=sharding)
want = np.arange(np.prod(cache.pages[0]["k"].shape), dtype=np.float32)
want = want.reshape(cache.pages[0]["k"].shape)
cache.pages = [{n: jax.device_put(want, sharding) for n in ("k", "v")}
               for _ in range(2)]
cache._copy_page(3, 6)
want[6] = want[3]
for p in cache.pages:
    for a in p.values():
        assert a.sharding == sharding, a.sharding
        np.testing.assert_array_equal(np.asarray(a), want)
print("SHARDED_COPY_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=2"),
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_COPY_OK" in out.stdout


# ---------------------------------------------------------------------------
# engine level: bit-identical outputs under sharing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv", ["fp", "int8"])
def test_shared_prefix_bit_identical_to_solo(setup, int8_spec, kv):
    """Shared-prefix batch == unshared solo serving, fp AND int8 caches.

    The int8 case is the sharp one: a shared page holds *quantized* KV,
    so sharing is only sound because the chain key guarantees the
    attaching request would have quantized the exact same values."""
    cfg, model, params = setup
    spec = int8_spec if kv == "int8" else "fp"
    reqs = _prefix_requests(cfg, 4)
    eng = ServingEngine(model, params,
                        ServingConfig(batch_slots=2, max_seq=32,
                                      kv_cache=spec, prefix_cache=True))
    solo = ServingEngine(model, params,
                         ServingConfig(batch_slots=1, max_seq=32,
                                       kv_cache=spec))
    outs = eng.generate(reqs)
    for i, r in enumerate(reqs):
        ref = solo.generate([r])[0]
        assert outs[i] == ref, f"request {i} diverged under sharing ({kv})"
    # sharing actually happened: hits recorded, pages went through the LRU
    assert eng.metrics.prefix_hit_rate > 0
    assert eng.cache.cached_pages > 0
    assert eng.cache.used_pages == 0


def test_repeat_prompt_skips_prefill_chunks(setup):
    """A repeated prompt attaches its cached pages: the warm serve runs
    strictly fewer prefill chunks and reports a high hit rate."""
    cfg, model, params = setup
    req = _prefix_requests(cfg, 1, sys_len=22)[0]
    eng = ServingEngine(model, params,
                        ServingConfig(batch_slots=2, max_seq=32,
                                      prefix_cache=True))
    eng.generate([req])
    cold_chunks = eng.metrics.summary()["prefill_chunks"]
    eng.reset_metrics()
    out_warm = eng.generate([req])[0]
    m = eng.metrics
    assert m.summary()["prefill_chunks"] < cold_chunks
    # 24-token prompt, 23 cached (last token always recomputed)
    assert m.prefix_hit_rate == pytest.approx(23 / 24)
    solo = ServingEngine(model, params,
                         ServingConfig(batch_slots=1, max_seq=32))
    assert out_warm == solo.generate([req])[0]


def test_preemption_under_sharing_still_bit_identical(setup):
    """A pool tight enough to preempt with prefix caching on: preempted
    requests replay (re-attaching their own just-released pages when
    cached) and every output still matches solo serving."""
    cfg, model, params = setup
    # 12-token prompts fit admission (4 pages each on an 8-page pool)
    # but 24-token completions need 6 pages each: the pool dries up
    # mid-decode and the newest request is preempted and replayed
    reqs = _prefix_requests(cfg, 3, sys_len=10, suffix_len=2, max_new=12)
    eng = ServingEngine(model, params,
                        ServingConfig(batch_slots=2, max_seq=24,
                                      page_size=4, num_pages=9,
                                      prefix_cache=True))
    outs = eng.generate(reqs)
    assert eng.metrics.preemptions >= 1
    solo = ServingEngine(model, params,
                         ServingConfig(batch_slots=1, max_seq=24,
                                       page_size=4))
    for i, r in enumerate(reqs):
        assert outs[i] == solo.generate([r])[0], \
            f"request {i} diverged under preemption + sharing"


@pytest.mark.parametrize("kv", ["fp", "int8"])
def test_spec_decode_under_sharing_bit_identical(setup, int8_spec, kv):
    """Speculative decode + prefix sharing: rollback garbage lands only
    in pages no other request maps, so the emitted streams still equal
    the per-token, unshared ones — fp and int8."""
    cfg, model, params = setup
    spec = int8_spec if kv == "int8" else "fp"
    rng = np.random.default_rng(3)
    pat = rng.integers(0, cfg.vocab, size=(4,)).astype(np.int32)
    system = np.tile(pat, 3)                    # repetitive → drafts accept
    reqs = [Request(prompt=np.concatenate(
                        [system, rng.integers(0, cfg.vocab, size=(2,))]),
                    max_new_tokens=8, request_id=i) for i in range(3)]
    eng = ServingEngine(model, params,
                        ServingConfig(batch_slots=2, max_seq=32,
                                      kv_cache=spec, prefix_cache=True,
                                      spec_decode="ngram", spec_k=4))
    base = ServingEngine(model, params,
                         ServingConfig(batch_slots=1, max_seq=32,
                                       kv_cache=spec))
    outs = eng.generate(reqs)
    for i, r in enumerate(reqs):
        assert outs[i] == base.generate([r])[0], \
            f"request {i} diverged under speculation + sharing ({kv})"
    assert eng.metrics.summary()["acceptance_rate"] > 0
    assert eng.metrics.prefix_hit_rate > 0


# ---------------------------------------------------------------------------
# ServingConfig
# ---------------------------------------------------------------------------

def test_legacy_kwargs_equal_config_and_warn_once(setup):
    cfg, model, params = setup
    rng = np.random.default_rng(5)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, size=(6,)),
                    max_new_tokens=4, request_id=i) for i in range(2)]
    import warnings as _w
    with _w.catch_warnings(record=True) as caught:
        _w.simplefilter("always")
        legacy = ServingEngine(model, params, batch_slots=2, max_seq=32,
                               page_size=4, seed=7)
    assert sum(issubclass(w.category, DeprecationWarning)
               for w in caught) == 1
    assert "ServingConfig" in str(caught[0].message)
    new = ServingEngine(model, params,
                        ServingConfig(batch_slots=2, max_seq=32,
                                      page_size=4, seed=7))
    assert legacy.generate(reqs) == new.generate(reqs)
    assert legacy.config == new.config
    # legacy positional batch_slots still works
    with _w.catch_warnings(record=True):
        _w.simplefilter("ignore")
        pos = ServingEngine(model, params, 2, 32, page_size=4, seed=7)
    assert pos.config == new.config


def test_config_plus_loose_kwargs_rejected(setup):
    cfg, model, params = setup
    sc = ServingConfig(batch_slots=1, max_seq=16)
    with pytest.raises(TypeError, match="ambiguous"):
        ServingEngine(model, params, sc, page_size=4)
    with pytest.raises(TypeError, match="ambiguous"):
        ServingEngine(model, params, sc, max_seq=16)
    with pytest.raises(TypeError, match="ServingConfig"):
        ServingEngine(model, params, "paged")
    with pytest.raises(TypeError, match="batch_slots"):
        ServingEngine(model, params)


def test_config_validation():
    with pytest.raises(ValueError, match="batch_slots"):
        ServingConfig(batch_slots=0, max_seq=16)
    with pytest.raises(ValueError, match="max_seq"):
        ServingConfig(batch_slots=1, max_seq=0)
    with pytest.raises(ValueError, match="kv_cache"):
        ServingConfig(batch_slots=1, max_seq=16, kv_cache="int4")
    with pytest.raises(ValueError, match="mode"):
        ServingConfig(batch_slots=1, max_seq=16, mode="pageless")
    with pytest.raises(ValueError, match="num_pages"):
        ServingConfig(batch_slots=1, max_seq=16, num_pages=1)
    with pytest.raises(ValueError, match="full-precision"):
        ServingConfig(batch_slots=1, max_seq=16, mode="static",
                      kv_cache="sira-int8")
    with pytest.raises(ValueError, match="prefix_cache"):
        ServingConfig(batch_slots=1, max_seq=16, mode="static",
                      prefix_cache=True)
    with pytest.raises(ValueError, match="mesh"):
        ServingConfig(batch_slots=1, max_seq=16, mesh="tpu")
    # replace() round-trips through validation
    sc = ServingConfig(batch_slots=2, max_seq=32)
    assert sc.replace(prefix_cache=True).prefix_cache
    with pytest.raises(ValueError, match="page_size"):
        sc.replace(page_size=0)


# ---------------------------------------------------------------------------
# sharded decode
# ---------------------------------------------------------------------------

def test_sharded_decode_matches_unsharded(setup):
    """decode_paged under a mesh (params + KV pools placed, jitted calls
    in the mesh context) emits exactly the unsharded tokens."""
    from repro.launch.mesh import make_debug_mesh
    cfg, model, params = setup
    mesh = make_debug_mesh(len(jax.devices()))
    rng = np.random.default_rng(9)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, size=(n,)),
                    max_new_tokens=4, request_id=i)
            for i, n in enumerate((7, 5))]
    plain = ServingEngine(model, params,
                          ServingConfig(batch_slots=2, max_seq=32))
    sharded = ServingEngine(model, params,
                            ServingConfig(batch_slots=2, max_seq=32,
                                          mesh=mesh, prefix_cache=True))
    assert plain.generate(reqs) == sharded.generate(reqs)


def test_sharded_decode_two_forced_devices(setup):
    """Same tokens on a 2-device forced-host-platform mesh (subprocess:
    device count is fixed at jax import)."""
    cfg, model, params = setup
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab, size=(7,))
    ref = ServingEngine(model, params,
                        ServingConfig(batch_slots=1, max_seq=32)
                        ).generate([Request(prompt=prompt,
                                            max_new_tokens=4)])[0]
    script = """
import jax, numpy as np
assert len(jax.devices()) == 2, jax.devices()
from repro.configs import get_config
from repro.models import get_model
from repro.launch.mesh import make_debug_mesh
from repro.serve import Request, ServingConfig, ServingEngine
cfg = get_config("qwen2-1.5b", reduced=True)
model = get_model(cfg)
params = model.init(jax.random.PRNGKey(0))
mesh = make_debug_mesh(2)
assert mesh.devices.size == 2
eng = ServingEngine(model, params,
                    ServingConfig(batch_slots=1, max_seq=32, mesh=mesh))
prompt = np.asarray(%r)
print("TOKENS", eng.generate([Request(prompt=prompt,
                                      max_new_tokens=4)])[0])
""" % (prompt.tolist(),)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=2"),
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("TOKENS")]
    assert line and line[0] == f"TOKENS {ref}"
