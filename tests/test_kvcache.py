"""Paged KV cache: block pool, prefix sharing, cold tier, engine parity.

The load-bearing property mirrors the slot pool's batch invariance
(docs/KV_CACHE.md): with DENSE blocks the paged engine must be
bit-identical to the PR 2 slot pool — the block table is pure routing —
and with QUANTIZED blocks the drift against the dense reference must stay
bounded and deterministic.  The host-side ``BlockKVManager`` bookkeeping
(prefix chain, refcounts, LRU + cold tier) is exercised directly, including
the compaction edge cases the slot pool shares: release-all-then-reinsert,
ragged ``kv_len`` after a neighbor's release, and the double-release guard.

Bit-identity needs ``max_len % block_size == 0`` (identical attention
reduction shapes) and sharing needs ``prefill_chunk % block_size == 0`` —
both hold here by construction (BS=8 divides MAX_LEN=48 and CHUNK=8).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core.spec import KVCompressionSpec
from repro.models import api
from repro.serving import engine as serving_engine
from repro.serving.batching import (ContinuousEngine, Request,
                                    SlotBatchManager)
from repro.serving.kvcache import (BlockKVManager, ColdBlockStore,
                                   kv_cache_bytes, kv_pool_bytes)

MAX_LEN = 48
BS = 8          # block size; divides MAX_LEN and CHUNK
CHUNK = 8


def _cfg():
    return registry.reduced(registry.get("qwen3-1.7b"))


@pytest.fixture(scope="module")
def harness():
    cfg = _cfg()
    params = api.build(cfg).init(cfg, jax.random.PRNGKey(0))
    sc = serving_engine.ServeConfig(max_len=MAX_LEN)
    eng = serving_engine.Engine(cfg, params, sc)
    return cfg, params, sc, eng


def _tok(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab, (n,)).astype(np.int32)


def _req(prompt, gen=4):
    return Request(prompt=np.asarray(prompt, np.int32), max_new_tokens=gen)


def _block_leaves(pool, blk):
    """Host snapshot of one pool block across every leaf."""
    return {k: np.asarray(v[:, blk]) for k, v in pool.items()}


# -------------------------------------------------------------------- policy

def test_kv_spec_parse_roundtrip():
    spec = KVCompressionSpec.parse("bits=4,block=16,codec=rans,sharing")
    assert (spec.bits, spec.block_size, spec.codec, spec.sharing) == \
        (4, 16, "rans", True)
    assert KVCompressionSpec.parse(spec.describe()) == spec
    with pytest.raises(ValueError, match="bits"):
        KVCompressionSpec(bits=5).validate()
    with pytest.raises(ValueError, match="codec"):
        # entropy-coding bf16 blocks needs a sub-16-bit symbol alphabet
        KVCompressionSpec(bits=16, codec="rans").validate()


def test_supports_paged_kv_gates_families():
    # the paged pool rides the cached step, so having it is the gate
    assert api.has_cached_step(_cfg())
    assert api.has_cached_step(
        registry.reduced(registry.get("qwen2-moe-a2.7b")))
    assert not api.has_cached_step(
        registry.reduced(registry.get("mamba2-370m")))


def test_pool_sizing_helpers():
    cfg = _cfg()
    dense = kv_pool_bytes(cfg, 8, BS, 16)
    q4 = kv_pool_bytes(cfg, 8, BS, 4)
    assert dense > 0 and q4 > 0
    # int4 + bf16 scale/zero per (token, head) must beat bf16 blocks
    assert q4 < dense / 2
    # default-capacity dense pool == slot cache bytes + one trash block
    m = BlockKVManager(cfg, n_slots=2, max_len=MAX_LEN,
                       spec=KVCompressionSpec(block_size=BS))
    assert m.pool_bytes == (kv_cache_bytes(cfg, 2, MAX_LEN)
                            + kv_pool_bytes(cfg, 1, BS, 16))


def test_manager_rejects_bad_geometry():
    cfg = _cfg()
    with pytest.raises(ValueError, match="chunk"):
        BlockKVManager(cfg, 1, MAX_LEN, prefill_chunk=6,
                       spec=KVCompressionSpec(block_size=BS, sharing=True))
    with pytest.raises(ValueError, match="n_blocks"):
        BlockKVManager(cfg, 1, MAX_LEN, n_blocks=3,
                       spec=KVCompressionSpec(block_size=BS))


# ------------------------------------------------------------- block manager

def test_block_manager_lifecycle_and_trash_block():
    cfg = _cfg()
    m = BlockKVManager(cfg, n_slots=2, max_len=MAX_LEN,
                       spec=KVCompressionSpec(block_size=BS),
                       prefill_chunk=CHUNK)
    got = m.alloc(_req(_tok(cfg, 12, 0), gen=4))
    assert got is not None
    slot, skip = got
    assert slot == 0 and skip == 0           # sharing off: never skips
    row = m.table_rows([slot])[0]
    nb = -(-16 // BS)                        # ceil((12 + 4) / BS)
    assert all(b != 0 for b in row[:nb])     # block 0 is never allocated
    assert all(b == 0 for b in row[nb:])     # tail stays trash
    m.insert(slot, 12)
    assert m.kv_len[slot] == 12 and m.active == [slot]
    req = m.release(slot)
    assert req is not None and m.active == [] and m.n_free == 2
    assert m.n_free_blocks == m.n_blocks - 1      # everything but trash
    assert not m.table_rows([slot]).any()


def test_decode_tables_masks_nonlive_lanes():
    cfg = _cfg()
    m = BlockKVManager(cfg, n_slots=2, max_len=MAX_LEN,
                       spec=KVCompressionSpec(block_size=BS),
                       prefill_chunk=CHUNK)
    s0, _ = m.alloc(_req(_tok(cfg, 9, 1)))
    m.insert(s0, 9)
    s1, _ = m.alloc(_req(_tok(cfg, 9, 2)))   # allocated but NOT live yet
    dt = m.decode_tables()
    assert dt[s0].any()                      # live lane routes to its blocks
    assert not dt[s1].any()                  # prefilling lane is all-trash
    assert m.table_rows([s1]).any()          # ...but the prefill view isn't
    m.insert(s1, 9)
    assert m.decode_tables()[s1].any()


def test_prefix_sharing_hits_and_refcounts():
    cfg = _cfg()
    m = BlockKVManager(cfg, n_slots=3, max_len=MAX_LEN,
                       spec=KVCompressionSpec(block_size=BS, sharing=True),
                       prefill_chunk=CHUNK)
    prefix = _tok(cfg, 2 * BS, 3)
    a = _req(np.concatenate([prefix, _tok(cfg, 4, 4)]), gen=4)
    b = _req(np.concatenate([prefix, _tok(cfg, 6, 5)]), gen=4)
    s0, skip0 = m.alloc(a)
    assert skip0 == 0 and m.shared_hits == 0
    m.insert(s0, a.prompt_len)               # publishes the 2 full blocks
    s1, skip1 = m.alloc(b)
    # both full prefix blocks hit; skip = 2 blocks' worth of whole chunks
    assert m.shared_hits == 2 and skip1 == 2 * BS
    assert (m.table_rows([s0])[0][:2] == m.table_rows([s1])[0][:2]).all()
    m.insert(s1, b.prompt_len)
    # shared blocks survive the publisher's release while b still holds them
    m.release(s0)
    assert m.stats()["prefix_hit_rate"] > 0
    s2, skip2 = m.alloc(_req(np.concatenate([prefix, _tok(cfg, 4, 6)])))
    assert skip2 == 2 * BS                   # chain outlives the publisher
    m.release(s1)
    m.release(s2)


def test_eviction_never_reclaims_planned_hit():
    """A planned resident hit at refcount 0 sits on the LRU; the admission
    eviction loop must pin it first, not reclaim it (regression: evicting
    the hit crashed the refcount bump and corrupted the chain)."""
    cfg = _cfg()
    m = BlockKVManager(cfg, n_slots=2, max_len=MAX_LEN, n_blocks=7,
                       spec=KVCompressionSpec(block_size=BS, sharing=True),
                       prefill_chunk=CHUNK)
    pa = _tok(cfg, BS, 7)
    for prompt in [pa, _tok(cfg, BS, 8)]:
        s, _ = m.alloc(_req(prompt, gen=BS))
        m.insert(s, BS)
        m.release(s)
    # LRU is now [A0, B0] with A0 oldest; a 40-token request hitting A0
    # needs 5 fresh blocks with only 4 free -> one eviction must pick B0
    a_blk = int(m._chain[m._chain_keys(pa)[0]])
    before = _block_leaves(m.pool, a_blk)
    big = _req(np.concatenate([pa, _tok(cfg, 32, 9)]), gen=8)
    assert m.can_admit(big)
    s, skip = m.alloc(big)
    assert skip == BS and m.dropped_evictions == 1
    assert int(m.table_rows([s])[0][0]) == a_blk
    after = _block_leaves(m.pool, a_blk)
    for k in before:
        np.testing.assert_array_equal(before[k], after[k])
    m.insert(s, big.prompt_len)
    m.release(s)


def test_cold_tier_evict_restore_roundtrip():
    """Evicted shared blocks entropy-code to host bytes and restore
    bit-exactly on the next prefix hit (quantized leaves are uint8, so the
    codec roundtrip is lossless)."""
    cfg = _cfg()
    spec = KVCompressionSpec(bits=8, block_size=BS, codec="rans",
                             sharing=True)
    m = BlockKVManager(cfg, n_slots=2, max_len=MAX_LEN, n_blocks=7,
                       spec=spec, prefill_chunk=CHUNK)
    pa = _tok(cfg, 2 * BS, 10)
    sa, _ = m.alloc(_req(pa, gen=8))
    # fake a prefill: stamp recognizable data into A's two prompt blocks
    row = m.table_rows([sa])[0]
    pool = {k: np.array(v) for k, v in m.pool.items()}
    rng = np.random.default_rng(0)
    for j in range(2):
        for k in pool:
            leaf = pool[k]
            stamp = rng.integers(0, 255, leaf[:, row[j]].shape)
            leaf[:, row[j]] = stamp.astype(leaf.dtype)
    m.pool = {k: jnp.asarray(v) for k, v in pool.items()}
    originals = [_block_leaves(m.pool, int(row[j])) for j in range(2)]
    m.insert(sa, len(pa))
    m.release(sa)
    # a 40-token stranger needs 6 blocks with 4 free -> evicts A0+A1 to cold
    sb, _ = m.alloc(_req(_tok(cfg, 5 * BS, 11), gen=8))
    assert m.cold_evictions == 2 and len(m.cold) == 2 and m.cold_bytes > 0
    m.insert(sb, 5 * BS)
    m.release(sb)
    # readmitting A walks the chain into the cold tier and decodes back
    sa2, skip = m.alloc(_req(pa, gen=8))
    assert m.cold_restores == 2
    assert skip == BS                        # final chunk always re-runs
    row2 = m.table_rows([sa2])[0]
    for j in range(2):
        restored = _block_leaves(m.pool, int(row2[j]))
        for k in restored:
            np.testing.assert_array_equal(restored[k], originals[j][k])


def test_cold_store_entropy_codes_uint8_leaves():
    store = ColdBlockStore("rans")
    rng = np.random.default_rng(0)
    # skewed symbols compress; bf16-viewed scale leaves ride along raw
    leaves = {
        "k": rng.choice(8, size=(2, 16, 2, 4)).astype(np.uint8),
        "k_scale": rng.normal(size=(2, 16, 2, 1)).astype(np.float32),
    }
    store.put("key", leaves)
    assert "key" in store and store.effective_bits < 8.0
    got = store.pop("key")
    assert "key" not in store and len(store) == 0
    np.testing.assert_array_equal(got["k"], leaves["k"])
    np.testing.assert_array_equal(got["k_scale"], leaves["k_scale"])


# ------------------------------------------- compaction edge cases (both
# managers: the slot pool and its paged successor share the lifecycle)

def test_slot_manager_release_all_then_reinsert():
    cfg = _cfg()
    mod = api.build(cfg)
    m = SlotBatchManager(cfg, n_slots=2, max_len=16)
    slots = [m.alloc(_req(np.ones(4, np.int32))) for _ in range(2)]
    rc = jax.tree.map(lambda c: jnp.ones_like(c[:, :1]),
                      mod.init_cache(cfg, 2, 16))
    for s in slots:
        m.insert(s, rc, kv_len=4)
    for s in slots:
        m.release(s)
    assert m.n_free == 2 and not m.kv_len.any()
    # the pool is fully compacted and immediately reusable
    assert all(float(jnp.abs(leaf).sum()) == 0.0
               for leaf in jax.tree.leaves(m.cache))
    s = m.alloc(_req(np.ones(4, np.int32)))
    m.insert(s, rc, kv_len=7)
    assert m.kv_len[s] == 7 and m.active == [s]


def test_block_manager_release_all_then_reinsert():
    cfg = _cfg()
    m = BlockKVManager(cfg, n_slots=2, max_len=MAX_LEN,
                       spec=KVCompressionSpec(block_size=BS, sharing=True),
                       prefill_chunk=CHUNK)
    prompts = [_tok(cfg, 12, s) for s in (20, 21)]
    slots = [m.alloc(_req(p))[0] for p in prompts]
    for s, p in zip(slots, prompts):
        m.insert(s, len(p))
    for s in slots:
        m.release(s)
    assert m.n_free == 2 and not m.kv_len.any() and not m.tables.any()
    # published blocks linger on the LRU (refcount 0 != free) ...
    assert m.n_free_blocks < m.n_blocks - 1 and len(m._lru) == 2
    # ... and a full reinsert cycle still works on the drained pool
    s, skip = m.alloc(_req(prompts[0]))
    assert skip == BS                        # the chain survived release-all
    m.insert(s, 12)
    assert m.kv_len[s] == 12 and m.active == [s]


def test_slot_manager_ragged_kv_len_survives_neighbor_compaction():
    cfg = _cfg()
    mod = api.build(cfg)
    m = SlotBatchManager(cfg, n_slots=3, max_len=16)
    rc = jax.tree.map(lambda c: jnp.ones_like(c[:, :1]),
                      mod.init_cache(cfg, 3, 16))
    lens = [4, 9, 13]
    slots = [m.alloc(_req(np.ones(4, np.int32))) for _ in lens]
    for s, L in zip(slots, lens):
        m.insert(s, rc, kv_len=L)
    m.release(slots[1])                      # compact the middle lane
    assert m.kv_len.tolist() == [4, 0, 13]   # neighbors' lens untouched
    assert float(jnp.abs(m.cache["k"][:, slots[1]]).sum()) == 0.0
    assert float(jnp.abs(m.cache["k"][:, slots[0]]).sum()) > 0.0
    s = m.alloc(_req(np.ones(4, np.int32)))  # freed slot comes back ...
    assert s == slots[1] and m.kv_len[s] == 0    # ... with kv_len reset


def test_block_manager_ragged_kv_len_survives_neighbor_compaction():
    cfg = _cfg()
    m = BlockKVManager(cfg, n_slots=3, max_len=MAX_LEN,
                       spec=KVCompressionSpec(block_size=BS),
                       prefill_chunk=CHUNK)
    lens = [4, 9, 13]
    slots = [m.alloc(_req(_tok(cfg, L, 30 + L)))[0] for L in lens]
    for s, L in zip(slots, lens):
        m.insert(s, L)
    freed = set(m.table_rows([slots[1]])[0]) - {0}
    m.release(slots[1])
    assert m.kv_len.tolist() == [4, 0, 13]
    assert freed <= set(m._free_blocks)      # blocks compacted + reclaimed
    assert m.table_rows([slots[0]]).any() and m.table_rows([slots[2]]).any()
    s, _ = m.alloc(_req(_tok(cfg, 5, 40)))
    assert s == slots[1] and m.kv_len[s] == 0


def test_double_release_guard_both_managers():
    cfg = _cfg()
    sm = SlotBatchManager(cfg, n_slots=1, max_len=16)
    s = sm.alloc(_req(np.ones(2, np.int32)))
    sm.release(s)
    with pytest.raises(AssertionError, match="free slot"):
        sm.release(s)
    bm = BlockKVManager(cfg, n_slots=1, max_len=MAX_LEN,
                        spec=KVCompressionSpec(block_size=BS),
                        prefill_chunk=CHUNK)
    s, _ = bm.alloc(_req(_tok(cfg, 4, 50)))
    bm.insert(s, 4)
    with pytest.raises(AssertionError, match="double insert"):
        bm.insert(s, 4)
    bm.release(s)
    with pytest.raises(AssertionError, match="free slot"):
        bm.release(s)


# ------------------------------------------------------------- engine parity

def _jobs(cfg, seed=0):
    """Six requests over two shared 2-block system prompts + ragged tails."""
    rng = np.random.default_rng(seed)
    prefixes = [_tok(cfg, 2 * BS, 100 + i) for i in range(2)]
    jobs = []
    for i, tail in enumerate([5, 9, 2, 7, 11, 3]):
        p = np.concatenate([prefixes[i % 2], _tok(cfg, tail, 200 + i)])
        jobs.append((p, int(rng.integers(3, 7))))
    return jobs


def test_paged_dense_engine_bit_identical_to_slot_pool(harness):
    """Dense blocks + prefix sharing through the FULL scheduler must equal
    the slot-pool engine token for token — the block table is pure routing
    and a shared prefix's K/V rows are bit-identical to recomputing them."""
    cfg, params, sc, eng = harness
    jobs = _jobs(cfg)
    ref = ContinuousEngine(cfg, params, sc, n_slots=3, prefill_chunk=CHUNK,
                           steps=eng.steps)
    rids = [ref.submit(p, g).rid for p, g in jobs]
    want = {r.rid: r.output for r in ref.run()}
    spec = KVCompressionSpec(bits=16, block_size=BS, sharing=True)
    ce = ContinuousEngine(cfg, params, sc, n_slots=3, prefill_chunk=CHUNK,
                          steps=eng.steps, kv_spec=spec)
    prids = [ce.submit(p, g).rid for p, g in jobs]
    got = {r.rid: r.output for r in ce.run()}
    assert [got[r] for r in prids] == [want[r] for r in rids]
    st = ce.slots.stats()
    assert st["shared_hits"] > 0             # the sharing actually engaged
    assert st["blocks_free"] >= 0 and st["pool_bytes"] > 0


def test_paged_quantized_engine_bounded_deterministic_drift(harness):
    """Quantized blocks trade exactness for capacity: outputs keep their
    lengths, drift vs the dense reference stays bounded, and two identical
    runs are bit-identical (the drift is deterministic, not noise)."""
    cfg, params, sc, eng = harness
    jobs = _jobs(cfg)

    def run(spec):
        ce = ContinuousEngine(cfg, params, sc, n_slots=3,
                              prefill_chunk=CHUNK, steps=eng.steps,
                              kv_spec=spec)
        rids = [ce.submit(p, g).rid for p, g in jobs]
        fin = {r.rid: r for r in ce.run()}
        return [fin[r].output for r in rids]

    ref = run(KVCompressionSpec(bits=16, block_size=BS, sharing=True))
    spec = KVCompressionSpec(bits=4, block_size=BS, codec="rans",
                             sharing=True)
    q1, q2 = run(spec), run(spec)
    assert q1 == q2                          # deterministic
    assert [len(o) for o in q1] == [len(o) for o in ref]
    toks = sum(len(o) for o in ref)
    diverged = sum(t != r for o, ro in zip(q1, ref)
                   for t, r in zip(o, ro))
    assert diverged / toks <= 0.6, f"int4 KV drift {diverged}/{toks}"


def test_paged_moe_engine_matches_slot_pool():
    """The MoE family rides the same paged step plumbing (one small run)."""
    cfg = registry.reduced(registry.get("qwen2-moe-a2.7b"))
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = api.build(cfg).init(cfg, jax.random.PRNGKey(0))
    sc = serving_engine.ServeConfig(max_len=MAX_LEN)
    jobs = [(_tok(cfg, 11, 60), 4), (_tok(cfg, 7, 61), 3)]
    ref = ContinuousEngine(cfg, params, sc, n_slots=2, prefill_chunk=CHUNK)
    reqs = [ref.submit(p, g) for p, g in jobs]
    ref.run()
    want = [r.output for r in reqs]
    ce = ContinuousEngine(cfg, params, sc, n_slots=2, prefill_chunk=CHUNK,
                          kv_spec=KVCompressionSpec(block_size=BS))
    reqs = [ce.submit(p, g) for p, g in jobs]
    ce.run()
    assert [r.output for r in reqs] == want
