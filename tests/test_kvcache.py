"""Paged KV-cache subsystem (repro.kvcache): allocator semantics, int4
packing, the Pallas paged-attention kernel vs its jnp oracle, paged
engine parity against the dense-cache engine, and FIT-driven per-layer
KV bit allocation.

The load-bearing guarantee: with fp pages, the paged engine's outputs
are BIT-IDENTICAL to the dense-cache engine's (which test_serve.py pins
to isolated decode) — under sampling, staggered arrivals, eviction +
backfill, and prefix-shared prompts.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import smoke_config
from repro.core import build_report
from repro.core.rankcorr import spearman
from repro.data.synthetic import LMStreamConfig, lm_batches
from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kvcache import (
    BlockAllocator, allocate_kv_bits, kv_bit_config, kv_bits_from_config,
    kv_report_fns, kv_sites)
from repro.kvcache.paged import quantize_kv
from repro.models import init_params, loss_fn
from repro.models.context import Context, QATContext
from repro.models.transformer import forward
from repro.quant.policy import QuantPolicy
from repro.serve import Engine, EngineConfig, SamplingParams, trace_requests

# staggered arrivals + more requests than slots: queueing, mid-flight
# admission, eviction on completion, immediate backfill — plus a shared
# 24-token prompt prefix so the page-sharing path is live
TRACE = [(0, 8, 5), (0, 12, 7), (3, 6, 4), (10, 10, 6), (11, 35, 8)]
ECFG = dict(max_slots=2, max_len=64, max_new_tokens=16,
            prefill_chunk=4, decode_burst=4)


# ---------------------------------------------------------------------------
# int4 packing
# ---------------------------------------------------------------------------

def test_pack_unpack_int4_roundtrip(rng):
    q = rng.integers(-8, 8, (5, 3, 16)).astype(np.int8)
    packed = ref.pack_int4(jnp.asarray(q))
    assert packed.shape == (5, 3, 8) and packed.dtype == jnp.uint8
    out = ref.unpack_int4(packed)
    np.testing.assert_array_equal(np.asarray(out), q)


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------

def test_allocator_free_list_and_reservations():
    a = BlockAllocator(8, 16)
    ids = a.allocate(3)
    assert len(ids) == 3 and a.pages_in_use == 3
    a.check_invariants()
    a.reserve(owner=0, n=4)
    assert a.available() == 1
    assert a.allocate(2) is None            # would eat the reservation
    got = a.allocate(2, owner=0)            # owner draws its reservation
    assert len(got) == 2 and a.available() == 1
    a.check_invariants()
    a.unreserve(0)
    a.release(ids)
    assert a.pages_in_use == 2 and len(a.allocate(6)) == 6   # recycled
    a.check_invariants()


def test_allocator_prefix_sharing_and_cow():
    rng = np.random.default_rng(0)
    a = BlockAllocator(32, 16)
    prompt = rng.integers(0, 100, 40).astype(np.int32)

    # first request: no match, allocates 3 pages, registers them
    full, shared, partial = a.match_prefix(prompt, 39)
    assert (full, shared, partial) == ([], 0, None)
    row = a.allocate(3)
    a.register_prompt(prompt, row, 40)

    # identical prompt: shares both full pages and matches the partial
    # boundary page at its capped 39-token prefix
    full, shared, partial = a.match_prefix(prompt, 39)
    assert full == row[:2] and partial == row[2] and shared == 39
    a.claim(full)
    assert a.refcount(row[0]) == 2

    # shorter prompt sharing a mid-page span of page 0 only
    full2, shared2, _ = a.match_prefix(prompt[:12], 11)
    assert full2 == [] and shared2 == 11

    # diverging prompt (token 20 differs): full page 0 + a 4-token
    # partial span of page 1 (tokens 16..19 still match)
    other = prompt.copy()
    other[20] += 1
    full3, shared3, partial3 = a.match_prefix(other, 39)
    assert full3 == row[:1] and partial3 == row[1] and shared3 == 20

    # release the original; shared pages survive via their refcount,
    # exclusive pages return to the free list and leave the index
    a.release(row)
    a.check_invariants()
    assert a.refcount(row[0]) == 1 and a.refcount(row[2]) == 0
    full4, shared4, _ = a.match_prefix(prompt, 39)
    assert full4 == row[:2] and shared4 == 32   # partial page is gone
    a.release(full)
    assert a.pages_in_use == 0
    assert a.match_prefix(prompt, 39) == ([], 0, None)
    a.check_invariants()


def test_allocator_invariant_check_catches_corruption():
    """check_invariants flags each bookkeeping corruption class, and
    double-release is rejected outright."""
    rng = np.random.default_rng(1)
    a = BlockAllocator(16, 8)
    prompt = rng.integers(0, 100, 24).astype(np.int32)
    row = a.allocate(3)
    a.register_prompt(prompt, row, 24)
    a.check_invariants()

    with pytest.raises(RuntimeError, match="free page"):
        a.release([a._free[-1]])            # double release

    # free-list duplicate
    a._free.append(a._free[-1])
    with pytest.raises(AssertionError, match="duplicates"):
        a.check_invariants()
    a._free.pop()

    # refcount desync: referenced page also on the free list
    a._free.append(row[0])
    with pytest.raises(AssertionError, match="free-but-referenced"):
        a.check_invariants()
    a._free.pop()

    # leaked page: refcount zeroed without returning it to the free list
    a._ref[row[1]] = 0
    with pytest.raises(AssertionError, match="unreferenced-but-not-free"):
        a.check_invariants()
    a._ref[row[1]] = 1

    # prefix index pointing at a page whose key table forgot it
    key = next(iter(a._index))
    pid = a._index[key]
    a._key_of[pid] = [k for k in a._key_of[pid] if k != key]
    with pytest.raises(AssertionError, match="missing from _key_of"):
        a.check_invariants()

    # reservations exceeding the free pool
    b = BlockAllocator(4, 8)
    b.reserve(owner=0, n=3)
    b._reserved[0] = 99
    with pytest.raises(AssertionError, match="exceed the free pool"):
        b.check_invariants()


# ---------------------------------------------------------------------------
# Pallas kernel vs jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [16, 8, 6, 4, 3])
def test_paged_attention_kernel_matches_ref(bits, rng):
    P, page, KV, Dh, B, NP, G = 10, 8, 2, 16, 3, 4, 2
    kf = rng.normal(size=(P, KV, page, Dh)).astype(np.float32)
    vf = rng.normal(size=(P, KV, page, Dh)).astype(np.float32)
    ks = (np.abs(rng.normal(size=(P, KV))) * 0.05 + 0.02).astype(np.float32)
    vs = (np.abs(rng.normal(size=(P, KV))) * 0.05 + 0.02).astype(np.float32)
    if bits >= 16:
        k, v, kss, vss = jnp.asarray(kf), jnp.asarray(vf), None, None
    else:
        k = quantize_kv(jnp.asarray(kf), jnp.asarray(ks)[:, :, None], bits)
        v = quantize_kv(jnp.asarray(vf), jnp.asarray(vs)[:, :, None], bits)
        kss, vss = jnp.asarray(ks), jnp.asarray(vs)
        from repro.qtensor import PACKED_BITS, packed_size
        assert k.dtype == (jnp.uint8 if bits in PACKED_BITS else jnp.int8)
        assert k.shape[-1] == packed_size(Dh, bits)   # 12/8/8 at 6/4/3
    q = jnp.asarray(rng.normal(size=(B, 1, KV * G, Dh)).astype(np.float32))
    table = jnp.asarray(rng.integers(0, P, (B, NP)).astype(np.int32))
    pos = jnp.asarray([3, 17, 31], jnp.int32)

    want = ref.paged_attention(q, k, v, table, pos, kss, vss, bits)
    got = paged_attention_pallas(q.reshape(B, KV, G, Dh), k, v, table,
                                 pos + 1, kss, vss, bits=bits,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# engine parity: paged fp pages == dense cache, bit for bit
# ---------------------------------------------------------------------------

def _engines(arch, **paged_kw):
    cfg = dataclasses.replace(smoke_config(arch), scan_layers=False)
    params = init_params(cfg, jax.random.key(0))
    dense = Engine(params, cfg, EngineConfig(**ECFG))
    paged = Engine(params, cfg,
                   EngineConfig(**ECFG, kv_cache="paged", page_size=16),
                   **paged_kw)
    return cfg, params, dense, paged


def test_paged_engine_parity_dense_prefix_shared():
    """Sampled decoding, staggered arrivals, eviction + backfill, and a
    shared prompt prefix: identical outputs to the dense engine."""
    cfg, _, dense, paged = _engines("internlm2_1_8b")
    sp = SamplingParams(temperature=0.8, top_k=20, top_p=0.95, seed=7)
    fd, _ = dense.run(trace_requests(cfg, TRACE, sampling=sp, prefix_len=24))
    fp, mp = paged.run(trace_requests(cfg, TRACE, sampling=sp, prefix_len=24))
    assert len(fp) == len(TRACE)
    for a, b in zip(fd, fp):
        np.testing.assert_array_equal(a.output_tokens, b.output_tokens)
    s = mp.summary()
    assert s["kv_shared_tokens"] > 0          # sharing actually engaged
    assert s["kv_cow_copies"] > 0             # ...including a partial COW
    assert mp.kv_total_pages == 8             # (64/16) pages x 2 slots


def test_paged_engine_parity_hybrid():
    """Hybrid (shared-attention + mamba) family: attention pages paged,
    SSM state dense — still bit-identical to the dense engine."""
    cfg, _, dense, paged = _engines("zamba2_7b")
    fd, _ = dense.run(trace_requests(cfg, TRACE))
    fp, _ = paged.run(trace_requests(cfg, TRACE))
    for a, b in zip(fd, fp):
        np.testing.assert_array_equal(a.output_tokens, b.output_tokens)


def test_paged_engine_small_pool_defers_admission():
    """A pool too small for all slots at once still serves everything:
    admission defers until eviction frees pages (no deadlock, no drop).
    Parity must hold — deferral only changes WHEN a request is admitted,
    and each request's numerics are batch-independent."""
    cfg = dataclasses.replace(smoke_config("internlm2_1_8b"),
                              scan_layers=False)
    params = init_params(cfg, jax.random.key(0))
    dense = Engine(params, cfg, EngineConfig(**ECFG))
    fd, _ = dense.run(trace_requests(cfg, TRACE))
    # 5 pages of 16 tokens: enough for one long request or two short ones
    paged = Engine(params, cfg,
                   EngineConfig(**ECFG, kv_cache="paged", page_size=16,
                                kv_pages=5, prefix_sharing=False))
    fp, _ = paged.run(trace_requests(cfg, TRACE))
    assert len(fp) == len(TRACE)
    for a, b in zip(fd, fp):
        np.testing.assert_array_equal(a.output_tokens, b.output_tokens)


def test_paged_engine_quantized_kv_runs_deterministic():
    """int8 + packed-int4 mixed per-layer KV pages: engine completes,
    outputs are deterministic, and storage dtypes are real."""
    cfg = dataclasses.replace(smoke_config("internlm2_1_8b"),
                              scan_layers=False)
    params = init_params(cfg, jax.random.key(0))
    eng = Engine(params, cfg,
                 EngineConfig(**ECFG, kv_cache="paged", page_size=16),
                 kv_bits={0: 8, 1: 4})
    st = eng._fresh_state()
    assert st.paged.layers["0"].k.dtype == jnp.int8
    assert st.paged.layers["1"].k.dtype == jnp.uint8
    assert st.paged.layers["1"].k.shape[-1] == cfg.head_dim // 2
    f1, _ = eng.run(trace_requests(cfg, TRACE, prefix_len=8))
    f2, _ = eng.run(trace_requests(cfg, TRACE, prefix_len=8))
    assert [r.num_generated for r in f1] == [5, 7, 4, 6, 8]
    for a, b in zip(f1, f2):
        np.testing.assert_array_equal(a.output_tokens, b.output_tokens)


# ---------------------------------------------------------------------------
# FIT-driven KV bit allocation
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def kv_report():
    cfg = dataclasses.replace(smoke_config("internlm2_1_8b"),
                              scan_layers=False)
    params = init_params(cfg, jax.random.key(0))
    stream = lm_batches(LMStreamConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                       global_batch=4, seed=0))
    tap_loss, tap_shapes, act_fn = kv_report_fns(cfg)
    report = build_report(lambda p, b: loss_fn(p, b, cfg), tap_loss,
                          lambda b: tap_shapes(params, b), act_fn, params,
                          [next(stream) for _ in range(2)], microbatch=4,
                          tolerance=None, max_batches=2)
    return cfg, params, next(stream), report


def _kv_cost_bits(cfg, bits_by_layer, tokens):
    per = 2 * tokens * cfg.num_kv_heads * cfg.head_dim
    return sum(per * b for b in bits_by_layer.values())


def _kl_under_kv_quant(cfg, params, batch, act_bits):
    """KL(fp || kv-quantized) over the vocab — the degradation proxy of
    the rank-correlation harness (fig-1 style, no training loop)."""
    logits_fp, _ = forward(params, batch, cfg, ctx=Context())
    lv = {s: float(2 ** b - 1) for s, b in act_bits.items() if b < 16}
    logits_q, _ = forward(params, batch, cfg, ctx=QATContext({}, lv))
    lp = jax.nn.log_softmax(logits_fp[..., :cfg.vocab_size].astype(jnp.float32))
    lq = jax.nn.log_softmax(logits_q[..., :cfg.vocab_size].astype(jnp.float32))
    return float(jnp.mean(jnp.sum(jnp.exp(lp) * (lp - lq), axis=-1)))


def test_kv_sites_have_traces_and_ranges(kv_report):
    cfg, _, _, report = kv_report
    for ks, vs in kv_sites(cfg):
        assert ks in report.act_traces and ks in report.act_ranges
        assert vs in report.act_traces and vs in report.act_ranges
        assert report.act_traces[ks] > 0


def test_allocate_kv_bits_budget_and_roundtrip(kv_report):
    cfg, _, _, report = kv_report
    policy = QuantPolicy()
    tokens = 2 * 64
    # 6 bits/elem average: with levels {4, 8, 16} the allocator must mix
    budget_bits = _kv_cost_bits(cfg, {i: 6 for i in range(cfg.num_layers)},
                                tokens)
    bits = allocate_kv_bits(report, cfg, policy, budget_bits / 8.0, tokens)
    assert _kv_cost_bits(cfg, bits, tokens) <= budget_bits
    assert sorted(bits.values()) == [4, 8]    # one int8, one int4 layer
    # greedy matches the exact DP on this tiny instance
    assert bits == allocate_kv_bits(report, cfg, policy, budget_bits / 8.0,
                                    tokens, exact=True)
    # round-trip through the policy's BitConfig interchange form
    bc = kv_bit_config(bits, cfg, policy)
    assert kv_bits_from_config(bc, cfg) == bits
    assert set(bc.act_bits) == {s for pair in kv_sites(cfg) for s in pair}


def test_allocate_kv_bits_charges_realized_storage(kv_report):
    """Levels whose container is wider than their nominal grid (packed
    3-bit rides 4-bit nibbles) are charged at container size: the
    allocation can never overrun the byte budget in REAL pool HBM."""
    from repro.qtensor import bytes_per_element
    cfg, _, _, report = kv_report
    policy = QuantPolicy(kv_allowed_bits=(3, 4, 8, 16))
    tokens = 2 * 64
    elems = 2 * tokens * cfg.num_kv_heads * cfg.head_dim
    # a budget that exactly fits all layers at 4 bits (= the 3-bit
    # container width): 3-bit must NOT be treated as cheaper than 4-bit
    budget_bytes = cfg.num_layers * elems * bytes_per_element(4)
    for exact in (False, True):
        bits = allocate_kv_bits(report, cfg, policy, budget_bytes, tokens,
                                exact=exact)
        realized = sum(elems * bytes_per_element(b) for b in bits.values())
        assert realized <= budget_bytes + 1e-6, (bits, realized)
        # 3-bit costs the same bytes as 4-bit but quantizes harder —
        # the allocator should never leave a layer at 3 when 4 is free
        assert 3 not in bits.values(), bits


def test_fit_allocated_kv_beats_uniform_and_reverse(kv_report):
    """The acceptance harness: at an equal HBM budget, FIT's per-layer
    KV allocation degrades the model less (KL vs fp) than the uniform
    config that fits the budget AND than the reversed (anti-FIT)
    assignment; FIT scores rank the KL degradations."""
    cfg, params, batch, report = kv_report
    policy = QuantPolicy()
    tokens = 2 * 64
    budget_bits = _kv_cost_bits(cfg, {i: 6 for i in range(cfg.num_layers)},
                                tokens)
    fit_bits = allocate_kv_bits(report, cfg, policy, budget_bits / 8.0,
                                tokens)
    rev_bits = {0: fit_bits[1], 1: fit_bits[0]}        # anti-FIT, equal cost
    uni4 = {i: 4 for i in range(cfg.num_layers)}       # uniform that fits
    uni8 = {i: 8 for i in range(cfg.num_layers)}       # over budget
    assert _kv_cost_bits(cfg, uni8, tokens) > budget_bits

    configs = [fit_bits, rev_bits, uni4, uni8,
               {0: 4, 1: 16}, {0: 16, 1: 4}, {0: 16, 1: 16}]
    fits, kls = [], []
    for bl in configs:
        bc = kv_bit_config(bl, cfg, policy)
        fits.append(report.fit_acts(bc.act_bits))
        kls.append(_kl_under_kv_quant(cfg, params, batch, bc.act_bits))

    assert kls[0] <= kls[1] + 1e-9, (fits, kls)        # fit <= reverse
    assert kls[0] <= kls[2] + 1e-9, (fits, kls)        # fit <= uniform-4
    assert fits[0] <= fits[1] and fits[0] <= fits[2]
    assert spearman(fits, kls) > 0.7, (fits, kls)


def test_allocate_kv_bits_per_shard_budget(kv_report):
    """Tensor-parallel pools: ``budget_bytes`` means ONE shard's HBM.

    With kv-head-sharded pools each device stores 1/tp of every page, so
    a tp=4 allocation must (a) never overrun a single shard's real HBM
    and (b) afford at-least-as-rich widths as the replicated allocation
    at the same per-device budget (4x the aggregate HBM)."""
    from repro.qtensor import bytes_per_element
    cfg, _, _, report = kv_report
    cfg4 = dataclasses.replace(cfg, num_kv_heads=4)   # tp=4 must divide
    policy = QuantPolicy()
    tokens = 2 * 64
    elems = 2 * tokens * cfg4.num_kv_heads * cfg4.head_dim
    # per-DEVICE budget that fits every layer at 4 bits replicated
    budget = cfg4.num_layers * elems * bytes_per_element(4)
    bits1 = allocate_kv_bits(report, cfg4, policy, budget, tokens)
    bits4 = allocate_kv_bits(report, cfg4, policy, budget, tokens,
                             tp_shards=4)
    # (a) the tp=4 spend, charged at per-shard element counts, fits
    per_shard = sum((elems / 4) * bytes_per_element(b)
                    for b in bits4.values())
    assert per_shard <= budget + 1e-6, (bits4, per_shard, budget)
    # (b) 4x aggregate HBM at the same per-device budget: richer widths
    assert all(bits4[i] >= bits1[i] for i in bits1), (bits1, bits4)
    assert sum(bits4.values()) > sum(bits1.values()), (bits1, bits4)
    # a replicated-budget read of the tp=4 allocation WOULD overrun —
    # the regression this test pins: pre-shard-aware accounting handed
    # tp meshes an allocation no single device could hold
    replicated_cost = sum(elems * bytes_per_element(b)
                          for b in bits4.values())
    assert replicated_cost > budget
    # a mesh that does not divide the kv heads leaves pools replicated:
    # per-shard accounting must refuse rather than under-charge
    with pytest.raises(ValueError, match="num_kv_heads"):
        allocate_kv_bits(report, cfg4, policy, budget, tokens, tp_shards=3)
