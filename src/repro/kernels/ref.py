"""Pure-jnp oracles for every Pallas kernel in this package.

Tests sweep shapes/dtypes and assert_allclose kernel-vs-ref; the ops.py
dispatcher also falls back to these on non-TPU backends (e.g. the CPU
dry-run container).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fake_quant(x: jnp.ndarray, scale: jnp.ndarray, zero_point: jnp.ndarray,
               bits: int, levels: float | None = None) -> jnp.ndarray:
    """Quantize–dequantize on a uniform grid.

    ``levels`` is the largest grid index — default the affine 2^bits − 1;
    pass ``QuantSpec.levels`` (2^bits − 2) for symmetric specs so
    out-of-calibration values clip to the odd symmetric grid instead of
    escaping one step above it.
    """
    if levels is None:
        levels = 2.0 ** bits - 1.0
    inv = 1.0 / scale
    q = jnp.clip(jnp.round(x * inv + zero_point), 0.0, levels)
    return ((q - zero_point) * scale).astype(x.dtype)


def ef_sqnorm(g: jnp.ndarray) -> jnp.ndarray:
    """Per-row squared L2 norm: g (B, N) -> (B,) float32.

    This is the inner reduction of the Empirical Fisher trace,
    Tr(Î) = (1/N) Σ_i ||∇f(z_i)||² (paper Prop. 5).
    """
    g32 = g.astype(jnp.float32)
    return jnp.sum(g32 * g32, axis=-1)


def int8_matmul(x_q: jnp.ndarray, w_q: jnp.ndarray, x_scale: jnp.ndarray,
                w_scale: jnp.ndarray, out_dtype=jnp.float32) -> jnp.ndarray:
    """W8A8 matmul: int8 x (M,K) @ int8 w (K,N), int32 accumulate, dequant.

    x_scale: scalar or (M,1); w_scale: scalar or (1,N) per-channel.
    """
    acc = jax.lax.dot_general(
        x_q, w_q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(out_dtype)


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """int8 values in [-8, 7], even last dim -> uint8 nibbles, 2 per byte.

    Thin alias of ``repro.qtensor.pack(q, 4)`` — the framework-wide pack
    convention. Packing runs along the LAST axis (head_dim for KV pages):
    one token's (KV, Dh) row owns whole bytes, so single-token cache
    writes never read-modify-write a byte shared with another token.
    """
    from repro import qtensor as _qt
    return _qt.pack(q, 4, axis=-1)


def unpack_int4(p: jnp.ndarray) -> jnp.ndarray:
    """uint8 nibble pairs -> int8 (..., 2*D) (inverse of ``pack_int4``)."""
    from repro import qtensor as _qt
    return _qt.unpack(p, 4, axis=-1)


def qmm_group_products(x_q: jnp.ndarray, w) -> jnp.ndarray:
    """Per-group scaled partial products of the grouped quantized matmul:
    (M, K) int8 x QTensor(K, N) -> (G, M, N) fp32, NO group reduction.

    Group g's slice is ``f32(int32_dot(x_g, w_g)) * w_scale[g]`` — an
    EXACT int32 dot cast once and scaled elementwise, so its value does
    not depend on which device computes it or on how the other groups
    are laid out. This is the invariant the tensor-parallel serving path
    builds on: a K-shard that owns whole scale groups computes exactly
    the same (G_local, M, N) terms the single-device oracle would, and
    the cross-shard combine (a zero-padded psum over disjoint group
    slots) is bit-exact for any shard count. ``qmm`` is literally
    ``sum(qmm_group_products(...), axis=0) * x_scale``.
    """
    k, n = w.shape
    wi = w.unpack()                                   # (K, N) int8
    g = w.scale.shape[w.axis]
    ws = w.scale.reshape(g, n)
    gs = k // g
    acc = jax.lax.dot_general(
        x_q.reshape(x_q.shape[0], g, gs),
        wi.reshape(g, gs, n),
        (((2,), (1,)), ((1,), (0,))),                 # contract gs, batch g
        preferred_element_type=jnp.int32,
    )                                                 # (G, M, N)
    return acc.astype(jnp.float32) * ws[:, None, :]


def qmm(x_q: jnp.ndarray, w, x_scale: jnp.ndarray,
        out_dtype=jnp.float32) -> jnp.ndarray:
    """Grouped-scale quantized matmul oracle: W{8,6,4,3}A8.

    x_q: (M, K) int8 activations; x_scale: (M, 1) (or scalar) per-row
    fp32 activation scales; ``w``: a ``repro.qtensor.QTensor`` of logical
    shape (K, N) packed along axis 0 with scales (G, N) — G groups of
    K/G rows each sharing one scale per output channel.

    Mirrors the Pallas kernel's accumulation structure exactly: one
    int32 dot per (group, tile), scaled into an fp32 accumulator per
    group — so kernel-vs-ref tests see only fp32 summation-order noise.
    The group reduction is ``jnp.sum`` over the stacked
    ``qmm_group_products`` terms — the same canonical per-element fold
    the sharded engine applies after its group psum, which is what makes
    tp>1 serving bit-identical to this oracle.
    """
    y = jnp.sum(qmm_group_products(x_q, w), axis=0)
    return (y * jnp.asarray(x_scale, jnp.float32)).astype(out_dtype)


def grouped_qmm(x_q: jnp.ndarray, w, x_scale: jnp.ndarray,
                counts: jnp.ndarray, expert_ids: jnp.ndarray | None = None,
                out_dtype=jnp.float32) -> jnp.ndarray:
    """Grouped ragged quantized matmul oracle: every MoE expert's FFN
    projection in ONE batched W{8,6,4,3}A8 dispatch.

    x_q: (S, C, K) int8 activation segments — S token→expert segments of
    capacity C rows each (the capacity-sorted layout ``models.moe``
    builds); x_scale: (S, C, 1) per-row fp32 activation scales;
    ``w``: a ``qtensor.quantize_experts`` stack — logical (E, K, N)
    packed along axis 1 with PER-EXPERT scales (E, G, N);
    counts: (S,) int32 valid rows per segment (rows >= count are masked
    to exact 0.0 — empty experts cost nothing and poison nothing);
    expert_ids: (S,) int32 expert feeding each segment (default
    ``arange(S)`` — the identity layout where segment s IS expert s).

    Bit-identity contract (pinned by ``tests/test_grouped_qmm.py``):
    output segment s equals ``qmm(x_q[s], expert_slice(w, ids[s]),
    x_scale[s])`` on its valid rows — same int32 group dots, same fp32
    scale folds, same group-axis ``jnp.sum`` — so the grouped MoE path
    is bitwise the dense per-expert loop, only batched.
    """
    e, k, n = w.shape
    s, c = x_q.shape[0], x_q.shape[1]
    wi = w.unpack()                                   # (E, K, N) int8
    g = w.scale.shape[w.axis]
    ws = w.scale.reshape(w.scale.shape[0], g, n)
    if ws.shape[0] != e:                              # legacy shared scales
        ws = jnp.broadcast_to(ws, (e, g, n))
    gs = k // g
    if expert_ids is None:
        expert_ids = jnp.arange(s, dtype=jnp.int32)
    wsel = jnp.take(wi, expert_ids, axis=0)           # (S, K, N)
    wssel = jnp.take(ws, expert_ids, axis=0)          # (S, G, N)
    acc = jax.lax.dot_general(
        x_q.reshape(s, c, g, gs),
        wsel.reshape(s, g, gs, n),
        (((3,), (2,)), ((0, 2), (0, 1))),   # contract gs; batch (seg, group)
        preferred_element_type=jnp.int32,
    )                                                 # (S, G, C, N)
    y = jnp.sum(acc.astype(jnp.float32) * wssel[:, :, None, :], axis=1)
    y = y * jnp.asarray(x_scale, jnp.float32)         # (S, C, N)
    rows = jnp.arange(c, dtype=jnp.int32)[None, :, None]
    y = jnp.where(rows < counts[:, None, None], y, 0.0)
    return y.astype(out_dtype)


NEG_INF = -1e30


def paged_attention(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                    table: jnp.ndarray, pos: jnp.ndarray,
                    k_scale=None, v_scale=None, bits: int = 16) -> jnp.ndarray:
    """Decode-time GQA over a paged KV pool — the jnp oracle.

    q: (B, 1, H, Dh) current-token queries (post-RoPE);
    k_pages/v_pages: (P, KV, page, Dh') — int8 or packed uint8 on the
    ``repro.qtensor`` byte layout when ``bits`` < 16 (Dh' =
    packed_size(Dh, bits)), else a float dtype;
    table: (B, NP) page ids per slot (entries >= P are padding);
    pos: (B,) per-slot current position (positions <= pos attend);
    k_scale/v_scale: (P, KV) per-page per-kv-head dequant scales.
    Returns (B, KV, G, Dh).

    At float precision this is BIT-IDENTICAL to the dense
    ``attention_decode`` read path (same gathered values, same einsum
    shapes/dtypes, same masked-softmax construction) — the serving
    engine's paged-vs-dense parity contract rests on it, so mirror any
    change here in ``repro.models.attention.attention_decode``.
    """
    b = q.shape[0]
    num_pages, kvh, page = k_pages.shape[:3]
    ids = jnp.clip(table, 0, num_pages - 1)
    kg = k_pages[ids].transpose(0, 1, 3, 2, 4)     # (B, NP, page, KV, Dh')
    vg = v_pages[ids].transpose(0, 1, 3, 2, 4)
    if bits < 16:
        from repro import qtensor as _qt
        kg, vg = _qt.unpack(kg, bits), _qt.unpack(vg, bits)
        ks = k_scale[ids][:, :, None, :, None]      # (B, NP, 1, KV, 1)
        vs = v_scale[ids][:, :, None, :, None]
        kg = kg.astype(jnp.float32) * ks
        vg = vg.astype(jnp.float32) * vs
    dh = kg.shape[-1]
    t = table.shape[1] * page
    kg = kg.reshape(b, t, kvh, dh)
    vg = vg.reshape(b, t, kvh, dh)
    g = q.shape[2] // kvh
    qg = q.reshape(b, kvh, g, dh)
    sc = jnp.einsum("bkgd,btkd->bkgt", qg, kg,
                    preferred_element_type=jnp.float32) * (dh ** -0.5)
    mask = jnp.arange(t)[None, None, None, :] <= pos[:, None, None, None]
    sc = jnp.where(mask, sc, NEG_INF)
    pr = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bkgt,btkd->bkgd", pr.astype(vg.dtype), vg)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, scale: float | None = None) -> jnp.ndarray:
    """Reference attention. q,k,v: (B, H, S, D) -> (B, H, S, D).

    Plain softmax(QK^T)V with optional causal mask; fp32 softmax.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k).astype(jnp.float32) * scale
    if causal:
        s, t = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((s, t), jnp.bool_), k=t - s)
        logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p.astype(v.dtype), v)
