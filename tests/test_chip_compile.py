"""The main-path Pallas kernels compile for a TPU v5e chip.

Interpret mode (what every other kernel test runs) accepts block shapes
and memory spaces that the chip's compiler refuses, so these tests hand
each kernel to the TPU compiler for a *described* ``v5e:2x2`` topology —
nothing runs and no chip is attached. Shapes are the published widths of
``internlm2_1_8b`` (d_model 2048, d_ff 8192, GQA 16/8, head_dim 128,
16-token pages) at a decode batch of 8, and ``olmoe_1b_7b``'s expert
stack for the grouped MoE kernel.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and under pytest-xdist every worker
imports this file. Each compile runs with JAX's persistent compilation
cache off — a compile for a described chip cannot be read back without
one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ef_sqnorm import ef_sqnorm_pallas
from repro.kernels.fake_quant import (
    fake_quant_pallas, fake_quant_per_channel_pallas)
from repro.kernels.grouped_qmm import grouped_qmm_pallas
from repro.kernels.int8_matmul import int8_matmul_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.qmm import MAX_GROUP, qmm_groups_pallas, qmm_pallas
from repro.qtensor import PACKED_BITS, packed_size

M, D, FF = 8, 2048, 8192                 # decode rows, d_model, d_ff
KV, G, DH, PAGE, NP = 8, 2, 128, 16, 10  # GQA 16/8, 16-token pages
GROUP = 128


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler otherwise writes its logs under the temp dir
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler here"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(one_chip):
    """``compile(fn, *shapes)`` for the described chip, cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()   # the Mosaic kernel
        return compiled

    try:
        yield compile_
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _payload_dtype(bits):
    return jnp.uint8 if bits in PACKED_BITS else jnp.int8


# (K, N) of the widest matrices the bench configurations serve: the heads
# of internlm2_1_8b (92 544, which no chosen block width divides) and
# minitron_4b, and their down projections (K past MAX_GROUP, so grouped
# only)
WIDEST = [(2048, 92544), (3072, 256000), (8192, 2048), (9216, 3072)]


def _compile_qmm(chip_compile, m, k, n, bits, group):
    n_groups = k // group if group else 1
    return chip_compile(
        lambda x, w, xs, ws: qmm_pallas(x, w, xs, ws, bits=bits, k=k),
        ((m, k), jnp.int8), ((packed_size(k, bits), n), _payload_dtype(bits)),
        ((m, 1), jnp.float32), ((n_groups, n), jnp.float32))


@pytest.mark.parametrize("group", [GROUP, None], ids=["g128", "per_channel"])
@pytest.mark.parametrize("bits", [8, 6, 4, 3])
def test_qmm_compiles(chip_compile, bits, group):
    """At the decode batch above, then with the tiles qmm_pallas chooses
    at the widest served shapes, at prefill (M=1) and decode (M=64) rows:
    a tile past the chip's VMEM shows here. No shape pads its payload."""
    _compile_qmm(chip_compile, M, D, FF, bits, group)
    for k, n in WIDEST:
        if not group and k > MAX_GROUP:
            continue
        for m in (1, 64):
            hlo = _compile_qmm(chip_compile, m, k, n, bits, group).as_text()
            assert " pad(" not in hlo


@pytest.mark.parametrize("bits", [8, 4])
def test_qmm_row_parallel_compiles(chip_compile, bits):
    """w_down at full K and a quarter of it (the tp=4 shard-local group
    products), group 128."""
    chip_compile(
        lambda x, w, xs, ws: qmm_pallas(x, w, xs, ws, bits=bits, k=FF),
        ((M, FF), jnp.int8), ((packed_size(FF, bits), D), _payload_dtype(bits)),
        ((M, 1), jnp.float32), ((FF // GROUP, D), jnp.float32))
    k = FF // 4
    chip_compile(
        lambda x, w, ws: qmm_groups_pallas(x, w, ws, bits=bits, k=k),
        ((M, k), jnp.int8), ((packed_size(k, bits), D), _payload_dtype(bits)),
        ((k // GROUP, D), jnp.float32))


@pytest.mark.parametrize("kv", [KV, KV // 4], ids=["kv8", "kv2_tp4_shard"])
@pytest.mark.parametrize("bits", [16, 8, 6, 4])
def test_paged_attention_compiles(chip_compile, bits, kv):
    pages = M * NP
    dtype = jnp.bfloat16 if bits == 16 else _payload_dtype(bits)
    pool = ((pages, kv, PAGE, packed_size(DH, bits)), dtype)
    chip_compile(
        lambda q, k, v, t, ln, ks, vs: paged_attention_pallas(
            q, k, v, t, ln, ks, vs, bits=bits),
        ((M, kv, G, DH), jnp.bfloat16), pool, pool,
        ((M, NP), jnp.int32), ((M,), jnp.int32),
        ((pages, kv), jnp.float32), ((pages, kv), jnp.float32))


@pytest.mark.parametrize("bits", [8, 4])
def test_grouped_qmm_compiles(chip_compile, bits):
    e, c, d, ff = 64, 8, 2048, 1024      # olmoe_1b_7b expert stack
    chip_compile(
        lambda x, w, xs, ws, cnt, eid: grouped_qmm_pallas(
            x, w, xs, ws, cnt, eid, bits=bits, k=d),
        ((e, c, d), jnp.int8),
        ((e, packed_size(d, bits), ff), _payload_dtype(bits)),
        ((e, c, 1), jnp.float32), ((e, d // GROUP, ff), jnp.float32),
        ((e,), jnp.int32), ((e,), jnp.int32))


def test_ef_sqnorm_compiles(chip_compile):
    """One per-sample gradient row of the w_up block (microbatch 1)."""
    chip_compile(ef_sqnorm_pallas, ((1, D * FF), jnp.bfloat16))


@pytest.mark.parametrize("per_channel", [False, True],
                         ids=["per_tensor", "per_channel"])
def test_fake_quant_compiles(chip_compile, per_channel):
    if per_channel:
        chip_compile(lambda x, s, z: fake_quant_per_channel_pallas(
            x, s, z, bits=4), ((256, D), jnp.float32), ((D,), jnp.float32),
            ((D,), jnp.float32))
    else:
        chip_compile(lambda x, s, z: fake_quant_pallas(x, s, z, bits=4),
                     ((256, D), jnp.float32), ((), jnp.float32),
                     ((), jnp.float32))


def test_int8_matmul_compiles(chip_compile):
    chip_compile(
        lambda x, w, xs, ws: int8_matmul_pallas(x, w, xs, ws),
        ((M, D), jnp.int8), ((D, FF), jnp.int8), ((M, 1), jnp.float32),
        ((FF,), jnp.float32))
