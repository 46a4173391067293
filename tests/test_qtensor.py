"""QTensor storage layer: pack/unpack round-trips across widths/axes,
quantize/dequantize grids, the fused grouped-scale qmm kernel vs its
oracle vs the dense dequantized matmul, serving parity of packed storage
against the legacy int8-backed format, and checkpoint round-trips.

The load-bearing guarantees:
  * packed storage dequantizes to EXACTLY the values the legacy
    int8-backed format produced (same ±(2^(b-1)-1) grid), so engine
    outputs are bit-identical between the two formats at every width;
  * sub-byte widths actually shrink the payload (0.75/0.5 B/elem).
"""
import dataclasses
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro import qtensor as qt
from repro.configs import smoke_config
from repro.kernels import ref
from repro.kernels.qmm import qmm_pallas
from repro.models import init_params
from repro.quant.policy import BitConfig
from repro.serve import (
    Engine, EngineConfig, quantize_params, quantize_params_int8,
    trace_requests, weight_storage_bytes)
from repro.utils.pytree import named_leaves

ALL_BITS = (8, 6, 4, 3)


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(bits=st.sampled_from(ALL_BITS), seed=st.integers(0, 999),
       ndim=st.integers(1, 3), axis=st.integers(0, 2),
       n=st.integers(1, 33))
def test_pack_unpack_roundtrip_property(bits, seed, ndim, axis, n):
    """All widths x shapes x pack axes: unpack(pack(q)) == q."""
    rng = np.random.default_rng(seed)
    axis = axis % ndim
    shape = tuple(int(rng.integers(1, 7)) for _ in range(ndim - 1))
    shape = shape[:axis] + (n,) + shape[axis:]
    qmax = int(qt.qmax_for_bits(bits))
    q = rng.integers(-qmax, qmax + 1, shape).astype(np.int8)
    p = qt.pack(jnp.asarray(q), bits, axis)
    assert p.shape[axis] == qt.packed_size(n, bits)
    assert p.dtype == (jnp.int8 if bits == 8 else jnp.uint8)
    out = qt.unpack(p, bits, n, axis)
    np.testing.assert_array_equal(np.asarray(out), q)


def test_unpack_rows_matches_axis0_unpack(rng):
    for bits in (6, 4, 3):
        q = rng.integers(-3, 4, (24, 16)).astype(np.int8)
        p = qt.pack(jnp.asarray(q), bits, 0)
        np.testing.assert_array_equal(np.asarray(qt.unpack_rows(p, bits)), q)


def test_bytes_per_element_table():
    assert qt.bytes_per_element(16, 2.0) == 2.0
    assert qt.bytes_per_element(8) == 1.0
    assert qt.bytes_per_element(6) == 0.75
    assert qt.bytes_per_element(4) == 0.5
    assert qt.bytes_per_element(3) == 0.5         # nibble container
    assert qt.bytes_per_element(5) == 1.0         # grid-reduced int8


# ---------------------------------------------------------------------------
# quantize / dequantize
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", ALL_BITS)
@pytest.mark.parametrize("group_size", [None, 16])
def test_quantize_error_bounded_by_half_step(rng, bits, group_size):
    w = jnp.asarray(rng.normal(size=(32, 24)).astype(np.float32))
    q = qt.quantize(w, bits, group_size=group_size)
    assert q.shape == (32, 24) and q.bits == bits
    step = np.asarray(qt.expand_scale(q.scale, q.shape))
    err = np.abs(np.asarray(q.dequantize()) - np.asarray(w))
    assert (err <= step / 2 + 1e-6).all()


def test_w8_single_group_matches_legacy_int8_grid(rng):
    """QTensor W8 default granularity stores the EXACT bytes and scales
    the legacy int8 serving path produced."""
    w = jnp.asarray(rng.normal(size=(48, 16)).astype(np.float32))
    q = qt.quantize(w, 8)
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    legacy = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    np.testing.assert_array_equal(np.asarray(q.data), np.asarray(legacy))
    np.testing.assert_array_equal(np.asarray(q.scale), np.asarray(scale))
    np.testing.assert_array_equal(
        np.asarray(q.dequantize(jnp.float32)),
        np.asarray((legacy.astype(jnp.float32) * scale)))


def test_quantize_rejects_bad_shapes(rng):
    with pytest.raises(ValueError, match="matrix-like"):
        qt.quantize(jnp.zeros(8), 8)
    with pytest.raises(ValueError, match="group_size"):
        qt.quantize(jnp.zeros((10, 4)), 8, group_size=3)
    with pytest.raises(ValueError, match="divisible"):
        qt.quantize(jnp.zeros((7, 4)), 4)


# ---------------------------------------------------------------------------
# qmm: oracle vs dense dequant matmul vs Pallas kernel
# ---------------------------------------------------------------------------

def _rowquant(x):
    xs = np.maximum(np.abs(x).max(axis=1, keepdims=True), 1e-8) / 127.0
    return np.clip(np.round(x / xs), -127, 127).astype(np.int8), xs


@settings(max_examples=25, deadline=None)
@given(bits=st.sampled_from(ALL_BITS), seed=st.integers(0, 99),
       gs=st.sampled_from([None, 8, 16, 32]))
def test_qmm_ref_equals_dense_dequant_matmul(bits, seed, gs):
    """ref.qmm == (dequantized activations) @ (dequantized weight)."""
    rng = np.random.default_rng(seed)
    M, K, N = int(rng.integers(1, 20)), 32, int(rng.integers(1, 24))
    x = rng.normal(size=(M, K)).astype(np.float32)
    w = rng.normal(size=(K, N)).astype(np.float32)
    xq, xs = _rowquant(x)
    wq = qt.quantize(jnp.asarray(w), bits, group_size=gs)
    got = np.asarray(ref.qmm(jnp.asarray(xq), wq, jnp.asarray(xs)))
    want = (xq.astype(np.float32) * xs) @ np.asarray(wq.dequantize())
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)


SMALL_TILES = dict(bm=16, bn=32)


@pytest.mark.parametrize("bits", ALL_BITS)
@pytest.mark.parametrize("m,k,n,gs,tiles", [
    pytest.param(8, 32, 16, None, SMALL_TILES, id="8-32-16-None"),
    pytest.param(24, 64, 48, 16, SMALL_TILES, id="24-64-48-16"),
    pytest.param(5, 48, 33, 12, SMALL_TILES, id="5-48-33-12"),
    # the tiles qmm_pallas chooses itself, at the engine's prefill (M=1)
    # and decode (M=64) rows: every group in one K step
    pytest.param(1, 512, 384, 128, {}, id="prefill-folded"),
    pytest.param(64, 512, 384, 128, {}, id="decode-folded"),
    # one and two groups per K step
    pytest.param(64, 512, 384, 128, dict(bk=128), id="decode-gps1"),
    pytest.param(1, 512, 384, 128, dict(bk=256), id="prefill-gps2"),
    # N no chosen width divides: a partial last block (bn = 1024)
    pytest.param(1, 256, 1300, 128, {}, id="prefill-partial-n"),
    pytest.param(64, 256, 1300, 128, {}, id="decode-partial-n"),
    # per-channel weights (one group of K rows)
    pytest.param(64, 256, 384, None, {}, id="decode-per-channel"),
])
def test_qmm_pallas_matches_ref(rng, bits, m, k, n, gs, tiles):
    x = rng.normal(size=(m, k)).astype(np.float32)
    w = rng.normal(size=(k, n)).astype(np.float32)
    xq, xs = _rowquant(x)
    wq = qt.quantize(jnp.asarray(w), bits, group_size=gs)
    want = ref.qmm(jnp.asarray(xq), wq, jnp.asarray(xs))
    g = wq.scale.shape[0]
    got = qmm_pallas(jnp.asarray(xq), wq.data, jnp.asarray(xs),
                     wq.scale.reshape(g, n), bits=bits, k=k,
                     interpret=True, **tiles)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("bits", ALL_BITS)
@pytest.mark.parametrize("m", [1, 64])
@pytest.mark.parametrize("k,n,gs,tiles", [
    pytest.param(512, 384, 128, dict(bk=256), id="gps2"),
    pytest.param(512, 384, 128, {}, id="gps_all"),
    pytest.param(256, 1300, 128, {}, id="gps_all-partial_n"),
    pytest.param(256, 384, None, {}, id="per_channel"),
])
def test_qmm_folded_tiles_bit_identical_to_one_group_per_step(
        rng, bits, m, k, n, gs, tiles):
    """Folding several scale groups into one K step changes no bit of the
    output: each group's int32 dot is folded into the fp32 accumulator in
    the same order as with one group per step (``bk`` = group size), and
    a partial last N block (N = 1300 against bn = 1024) matches one block
    over the whole of N."""
    x = rng.normal(size=(m, k)).astype(np.float32)
    w = rng.normal(size=(k, n)).astype(np.float32)
    xq, xs = _rowquant(x)
    wq = qt.quantize(jnp.asarray(w), bits, group_size=gs)
    g = wq.scale.shape[0]
    args = (jnp.asarray(xq), wq.data, jnp.asarray(xs), wq.scale.reshape(g, n))
    folded = qmm_pallas(*args, bits=bits, k=k, interpret=True, **tiles)
    one_group = qmm_pallas(*args, bits=bits, k=k, bk=k // g, bn=n,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(folded), np.asarray(one_group))


QMM_CONFIGS = {"internlm2_1_8b": 5_000, "minitron_4b": 10_000}


def _served_qmm_shapes(cfg):
    """(K, N, bits) of every matrix a bench configuration serves (its
    layers' projections and the head), from its JSON file."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    leaves = {"attn/wq": (d, q), "attn/wk": (d, kv), "attn/wv": (d, kv),
              "attn/wo": (q, d), "mlp/w_up": (d, ff), "mlp/w_down": (ff, d),
              "mlp/w_gate": (d, ff)}
    out = []
    for name, bits in cfg["allocation"]["weight_bits"].items():
        if name == "head":
            out.append((d, -(-cfg["vocab_size"] // 16) * 16, bits))
        else:
            out.append((*leaves[name.split("/", 2)[2]], bits))
    return out


@pytest.mark.parametrize("m", [1, 64])
@pytest.mark.parametrize("name", sorted(QMM_CONFIGS))
def test_qmm_tile_choice_for_served_shapes(name, m):
    """The tiles qmm_pallas chooses for every matrix a bench configuration
    serves, at prefill (M=1) and decode (M=64) rows: legal TPU blocks
    (8-bit rows a multiple of 32 or the whole dimension, lanes a multiple
    of 128 or the whole dimension, whole scale groups dividing K), VMEM
    under the budget, and a whole weight pass in a few thousand grid
    steps (one 128-row group per step took ~52 k / ~104 k)."""
    from repro.kernels.qmm import VMEM_BUDGET, choose_tiles, vmem_bytes
    path = os.path.join(os.path.dirname(__file__), "..", "bench", "configs",
                        f"{name}.json")
    with open(path) as f:
        cfg = json.load(f)
    group = cfg["engine"]["group_size"]
    shapes = _served_qmm_shapes(cfg)
    assert len(shapes) == cfg["num_hidden_layers"] * (
        7 if cfg["hidden_act"] == "silu" else 6) + 1
    steps = 0
    for k, n, bits in shapes:
        bm, bk, bn = choose_tiles(m, k, n, bits, group)
        assert bm == m or bm % 32 == 0
        assert bn == n or bn % 128 == 0
        assert bk % group == 0 and k % bk == 0
        assert bk == k or (bk % 128 == 0
                           and qt.packed_size(bk, bits) % 32 == 0)
        assert vmem_bytes(bm, bk, bn, bits, group) <= VMEM_BUDGET
        steps += -(-m // bm) * -(-n // bn) * (k // bk)
    assert steps <= QMM_CONFIGS[name]


@pytest.mark.parametrize("bits", ALL_BITS)
@pytest.mark.parametrize("m,k,n,gs", [(8, 32, 16, 8), (5, 48, 33, 12)])
def test_qmm_groups_pallas_matches_group_products(rng, bits, m, k, n, gs):
    """The tensor-parallel shard-local kernel: per-group scaled partial
    products must match the jnp oracle BIT-exactly (each (G, M, N) slice
    is one exact int32 dot cast once and scaled elementwise — the
    invariant the row-parallel psum combine builds on)."""
    from repro.kernels.qmm import qmm_groups_pallas
    x = rng.normal(size=(m, k)).astype(np.float32)
    w = rng.normal(size=(k, n)).astype(np.float32)
    xq, _ = _rowquant(x)
    wq = qt.quantize(jnp.asarray(w), bits, group_size=gs)
    want = ref.qmm_group_products(jnp.asarray(xq), wq)
    g = wq.scale.shape[0]
    got = qmm_groups_pallas(jnp.asarray(xq), wq.data,
                            wq.scale.reshape(g, n), bits=bits, k=k,
                            bm=16, bn=32, interpret=True)
    assert got.shape == (g, m, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_qmm_w8_single_group_matches_int8_matmul(rng):
    """At W8 with one scale group, qmm degenerates to the int8 kernel's
    contract (per-row x per-channel dequant)."""
    x = rng.normal(size=(16, 64)).astype(np.float32)
    w = rng.normal(size=(64, 32)).astype(np.float32)
    xq, xs = _rowquant(x)
    wq = qt.quantize(jnp.asarray(w), 8)
    got = ref.qmm(jnp.asarray(xq), wq, jnp.asarray(xs))
    want = ref.int8_matmul(jnp.asarray(xq), wq.data, jnp.asarray(xs),
                           wq.scale.reshape(1, -1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# serving: packed storage == legacy int8-backed storage, bit for bit
# ---------------------------------------------------------------------------

TRACE = [(0, 8, 5), (0, 12, 7), (3, 6, 4)]
ECFG = dict(max_slots=2, max_len=64, max_new_tokens=16,
            prefill_chunk=4, decode_burst=4)


def _mixed_config(params):
    """Alternate W4/W8 over the blocks — a sub-byte-heavy split model."""
    wb = {n: (4 if i % 2 else 8)
          for i, (n, _) in enumerate(named_leaves(params))}
    return BitConfig(wb, {})


@pytest.fixture(scope="module")
def smoke_model():
    cfg = dataclasses.replace(smoke_config("internlm2_1_8b"),
                              scan_layers=False)
    return cfg, init_params(cfg, jax.random.key(0))


def test_engine_parity_qtensor_w8_vs_int8(smoke_model):
    """QTensor-packed W8 serving is bit-identical to the legacy int8
    path (which test_serve pins to isolated decode)."""
    cfg, params = smoke_model
    qp, sc = quantize_params_int8(params, 8)
    qtp, _ = quantize_params(params, 8)
    assert isinstance(qtp["layers"]["0"]["attn"]["wq"], qt.QTensor)
    f_int8, _ = Engine(qp, cfg, EngineConfig(**ECFG), scales=sc).run(
        trace_requests(cfg, TRACE))
    f_qt, _ = Engine(qtp, cfg, EngineConfig(**ECFG)).run(
        trace_requests(cfg, TRACE))
    assert len(f_qt) == len(TRACE)
    for a, b in zip(f_int8, f_qt):
        np.testing.assert_array_equal(a.output_tokens, b.output_tokens)


def test_engine_parity_mixed_w4_w8_packed_vs_int8_backed(smoke_model):
    """A W4/W8 split model: packed sub-byte storage dequantizes to the
    same grid as the int8-backed format -> identical engine outputs,
    at measurably smaller weight HBM."""
    cfg, params = smoke_model
    bc = _mixed_config(params)
    qp, sc = quantize_params_int8(params, bc)
    qtp, _ = quantize_params(params, bc)
    # the W4 blocks really are nibbles
    sizes = {b: 0 for b in (4, 8)}
    for path, node in jax.tree_util.tree_flatten_with_path(
            qtp, is_leaf=qt.is_qtensor)[0]:
        if isinstance(node, qt.QTensor):
            sizes[node.bits] += 1
            if node.bits == 4:
                assert node.data.dtype == jnp.uint8
                assert node.data.shape[0] == node.shape[0] // 2
    assert sizes[4] > 0 and sizes[8] > 0
    f_a, _ = Engine(qp, cfg, EngineConfig(**ECFG), scales=sc).run(
        trace_requests(cfg, TRACE))
    f_b, _ = Engine(qtp, cfg, EngineConfig(**ECFG)).run(
        trace_requests(cfg, TRACE))
    for a, b in zip(f_a, f_b):
        np.testing.assert_array_equal(a.output_tokens, b.output_tokens)
    assert weight_storage_bytes(qtp) < weight_storage_bytes(qp)


def test_quantized_block_bytes_shrink(smoke_model):
    """Quantized-block payloads: packed W4 is half of int8-backed and a
    quarter of fp16 (+ small scale overhead)."""
    cfg, params = smoke_model
    qtp4, _ = quantize_params(params, 4)
    qp4, _ = quantize_params_int8(params, 4)
    packed = int8b = fp16 = 0.0
    for path, node in jax.tree_util.tree_flatten_with_path(
            qtp4, is_leaf=qt.is_qtensor)[0]:
        if isinstance(node, qt.QTensor):
            elems = int(np.prod(node.shape))
            packed += node.nbytes
            int8b += elems
            fp16 += 2 * elems
    assert packed == int8b / 2 == fp16 / 4
    # the shared accounting helper agrees (it additionally counts scales)
    ws = qt.storage_summary(qtp4)
    assert ws["fp16_bytes"] == fp16
    scale_b = ws["packed_bytes"] - packed
    assert scale_b > 0 and ws["int8_backed_bytes"] == int8b + scale_b


def test_checkpoint_roundtrip_qtensor(tmp_path, smoke_model):
    """Calibrated quantized model -> save -> restore -> identical packed
    payloads and dequantized values (no re-quantization)."""
    from repro.checkpoint.checkpointer import Checkpointer
    cfg, params = smoke_model
    qtp, _ = quantize_params(params, _mixed_config(params))
    ck = Checkpointer(str(tmp_path))
    ck.save(3, qtp)
    man = json.load(open(os.path.join(str(tmp_path), "step_00000003",
                                      "manifest.json")))
    assert man["qtensors"]["layers/0/attn/wq"]["bits"] in (4, 8)
    back = ck.restore(3, qtp)
    wq_a = qtp["layers"]["0"]["attn"]["wq"]
    wq_b = back["layers"]["0"]["attn"]["wq"]
    assert isinstance(wq_b, qt.QTensor) and wq_b.bits == wq_a.bits
    np.testing.assert_array_equal(np.asarray(wq_a.data),
                                  np.asarray(wq_b.data))
    np.testing.assert_array_equal(np.asarray(wq_a.dequantize()),
                                  np.asarray(wq_b.dequantize()))


# ---------------------------------------------------------------------------
# shard() — the tensor-parallel split (serve.quantized.shard_params rests
# on these invariants; see tests/test_sharded_serve.py for the engine)
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(bits=st.sampled_from(ALL_BITS), seed=st.integers(0, 999),
       axis=st.sampled_from([0, 1]),
       n_shards=st.sampled_from([1, 2, 4, 8]),
       group_size=st.sampled_from([8, 16, 32, None]))
def test_shard_roundtrip_and_bytes_property(bits, seed, axis, n_shards,
                                            group_size):
    """Every (bits, axis, group_size, shard count) combo: either
    ``shard_error`` names the violated alignment rule and ``shard``
    raises it, or the shards reassemble bit-identically (pack/unpack AND
    dequantize) and ``storage_summary`` byte accounting is additive."""
    rng = np.random.default_rng(seed)
    k, n = 32, 16
    w = rng.normal(size=(k, n)).astype(np.float32)
    full = qt.quantize(jnp.asarray(w), bits, group_size=group_size)
    err = qt.shard_error(full, n_shards, axis)
    if err is not None:
        with pytest.raises(ValueError, match="cannot shard"):
            qt.shard(full, n_shards, axis)
        # the only legal failure modes on these shapes: a pack-axis span
        # that splits a scale group / pack unit (dims always divide)
        assert axis == full.axis and n_shards > 1
        return
    shards = qt.shard(full, n_shards, axis)
    assert len(shards) == n_shards
    # payload + scale reassembly is exact in PACKED coordinates
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(s.data) for s in shards], axis),
        np.asarray(full.data))
    # unpack/dequantize of each self-contained shard concatenates to the
    # whole — bit-identical, the property sharded serving relies on
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(s.unpack()) for s in shards], axis),
        np.asarray(full.unpack()))
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(s.dequantize()) for s in shards], axis),
        np.asarray(full.dequantize()))
    # storage_summary additivity: sharding never changes total bytes
    whole = qt.storage_summary([full])
    parts = [qt.storage_summary([s]) for s in shards]
    for key in ("packed_bytes", "int8_backed_bytes", "fp16_bytes",
                "predicted_bytes"):
        assert sum(p[key] for p in parts) == pytest.approx(whole[key])
    assert sum(s.nbytes for s in shards) == full.nbytes
    assert sum(s.scale_bytes for s in shards) == full.scale_bytes


def test_shard_six_bit_pack_unit_boundary(rng):
    """The sharp 6-bit case: 4 values share 3 bytes, so a pack-axis
    shard span that is not a multiple of 4 would split a byte group."""
    w = rng.normal(size=(8, 16)).astype(np.float32)
    full = qt.quantize(jnp.asarray(w), 6, group_size=4)
    # span 4 = one pack unit per shard: fine
    a, b = qt.shard(full, 2, 0)
    assert a.data.shape == (3, 16) and a.shape == (4, 16)
    # span 2 < pack unit: must refuse, naming the pack unit
    assert "pack unit" in qt.shard_error(full, 4, 0)
    with pytest.raises(ValueError, match="pack unit"):
        qt.shard(full, 4, 0)


def test_shard_error_paths(rng):
    w = rng.normal(size=(32, 12)).astype(np.float32)
    # one scale group spanning the whole pack axis cannot be split
    whole_group = qt.quantize(jnp.asarray(w), 4)          # group_size=None
    assert "single scale group" in qt.shard_error(whole_group, 2, 0)
    with pytest.raises(ValueError, match="group"):
        qt.shard(whole_group, 2, 0)
    # group boundaries must align with shard boundaries (G=2, shards=4)
    grouped = qt.quantize(jnp.asarray(w), 4, group_size=16)
    assert "scale groups" in qt.shard_error(grouped, 4, 0)
    # a non-dividing logical dim refuses on any axis
    assert "does not divide" in qt.shard_error(grouped, 5, 1)
    # out-channel (non-pack) axis has no pack/group constraint: N=12 into
    # 4 shards slices payload bytes and per-channel scales together
    shards = qt.shard(grouped, 4, 1)
    assert all(s.data.shape == (16, 3) for s in shards)
    assert all(s.scale.shape == (2, 3) for s in shards)
