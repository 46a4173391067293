"""GQA attention with RoPE: chunked online-softmax (train/prefill) and
KV-cache decode.

The chunked path never materializes the S×T score matrix: a scan over KV
chunks carries (running-max, denominator, accumulator) — the jnp mirror
of the Pallas flash kernel, used on non-TPU backends and for the
compile-time dry-run. On TPU ``repro.kernels.ops`` dispatches to the
Pallas kernel.

Decode attends one query position against the full cache with a length
mask; GQA keeps the cache at kv_heads and contracts with grouped queries
(no cache repetition — 4× less HBM traffic for kv=8/H=32).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models.layers import apply_rope, grad_barrier, init_dense
from repro.models.partition import constrain

NEG_INF = -1e30


def init_attention(key, cfg: ModelConfig, dtype, abstract: bool) -> Dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": init_dense(ks[0], d, h * hd, dtype, abstract),
        "wk": init_dense(ks[1], d, kv * hd, dtype, abstract),
        "wv": init_dense(ks[2], d, kv * hd, dtype, abstract),
        "wo": init_dense(ks[3], h * hd, d, dtype, abstract),
    }


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      causal: bool = True, chunk: int = 1024) -> jnp.ndarray:
    """Online-softmax attention. q: (B,S,H,Dh); k,v: (B,T,H,Dh) -> (B,S,H,Dh)."""
    b, s, h, dh = q.shape
    t = k.shape[1]
    chunk = min(chunk, t)
    nk = -(-t // chunk)
    pad = nk * chunk - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qs = (q * (dh ** -0.5)).astype(q.dtype)

    def body(carry, idx):
        m, l, acc = carry
        ks = jax.lax.dynamic_slice_in_dim(k, idx * chunk, chunk, 1)
        vs = jax.lax.dynamic_slice_in_dim(v, idx * chunk, chunk, 1)
        sc = jnp.einsum("bshd,bthd->bhst", qs, ks,
                        preferred_element_type=jnp.float32)
        kpos = idx * chunk + jnp.arange(chunk)
        valid = kpos[None, :] < t                       # padded tail
        if causal:
            qpos = jnp.arange(s)
            valid = valid & (qpos[:, None] >= kpos[None, :])
        sc = jnp.where(valid[None, None], sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhst,bthd->bshd", p.astype(vs.dtype), vs,
                        preferred_element_type=jnp.float32)
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + pv
        return (m_new, l, acc), None

    m0 = jnp.full((b, h, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    a0 = jnp.zeros((b, s, h, dh), jnp.float32)
    # per-chunk remat = flash-attention backward: without it the scan
    # saves every chunk's (B,H,S,chunk) probability tensor for the bwd
    # pass (GiBs); with it only the O(B·H·S) carries are stored and
    # scores/probs are recomputed per chunk.
    body = jax.checkpoint(body, prevent_cse=False)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nk))
    l = jnp.maximum(l, 1e-30)
    return (acc / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def attention_apply(x: jnp.ndarray, p: Dict, cfg: ModelConfig, ctx,
                    positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full (causal) attention for train / prefill. x: (B, S, D)."""
    b, s, d = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if positions is None:
        positions = jnp.arange(s)

    q = grad_barrier(ctx.matmul("wq", x, p["wq"]).reshape(b, s, h, hd))
    k = grad_barrier(ctx.matmul("wk", x, p["wk"]).reshape(b, s, kv, hd))
    v = grad_barrier(ctx.matmul("wv", x, p["wv"]).reshape(b, s, kv, hd))
    # land on the attention layout BEFORE the GQA repeat: the seq
    # all-gather (SP boundary) then moves the small kv-head tensor, and
    # the repeat + head-shard below is a local broadcast/slice.
    q = constrain(q, "batch", "seq_noshard", "heads", None)
    k = constrain(k, "batch", "seq_noshard", "kv_heads", None)
    v = constrain(v, "batch", "seq_noshard", "kv_heads", None)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = ctx.tap("q", q)
    k = ctx.tap("k", k)
    v = ctx.tap("v", v)
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        k = constrain(k, "batch", "seq_noshard", "heads", None)
        v = constrain(v, "batch", "seq_noshard", "heads", None)
    o = chunked_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
    o = ctx.tap("attn_out", o.reshape(b, s, h * hd))
    return ctx.matmul("wo", o, p["wo"])


class KVCache(NamedTuple):
    k: jnp.ndarray        # (B, T, KV, Dh)
    v: jnp.ndarray        # (B, T, KV, Dh)

    @classmethod
    def zeros(cls, b: int, t: int, kv: int, hd: int, dtype) -> "KVCache":
        return cls(jnp.zeros((b, t, kv, hd), dtype),
                   jnp.zeros((b, t, kv, hd), dtype))

    @classmethod
    def abstract(cls, b: int, t: int, kv: int, hd: int, dtype) -> "KVCache":
        s = jax.ShapeDtypeStruct((b, t, kv, hd), dtype)
        return cls(s, s)


def attention_decode(x: jnp.ndarray, p: Dict, cfg: ModelConfig, ctx,
                     cache: KVCache, pos: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, KVCache]:
    """Decode x: (B, T, D) query tokens at consecutive positions.

    ``pos`` is either a () scalar (whole batch at one position — the
    static-batch path) or a (B,) vector of per-slot positions (the
    continuous-batching engine, where every slot runs its own request at
    its own offset); row b's tokens land at pos[b] .. pos[b]+T-1.
    Per-row cache scatter + per-row causal masks keep each row's numerics
    identical to a batch-of-one decode.

    T > 1 is the speculative-verify path: all T K/V rows are written
    first, then every query attends under its own causal mask — masked
    scores are forced to NEG_INF before softmax (exp -> exact 0.0), so
    position j's output never sees the in-block writes at j' > j and each
    row is bitwise identical to T sequential one-token decodes.
    """
    b, tq = x.shape[0], x.shape[1]
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kv
    t = cache.k.shape[1]

    q = ctx.matmul("wq", x, p["wq"]).reshape(b, tq, h, hd)
    knew = ctx.matmul("wk", x, p["wk"]).reshape(b, tq, kv, hd)
    vnew = ctx.matmul("wv", x, p["wv"]).reshape(b, tq, kv, hd)
    offs = jnp.arange(tq, dtype=jnp.int32)
    if pos.ndim == 0:
        posb = jnp.broadcast_to((pos + offs)[None, :], (b, tq))
    else:
        posb = pos[:, None] + offs[None, :]
    q = apply_rope(q, posb, cfg.rope_theta)
    knew = apply_rope(knew, posb, cfg.rope_theta)

    # int8 KV cache: symmetric per-cache static scale (paper Appendix E
    # noise model at b=8; calibrated scale would come from EmaObserver)
    KV_SCALE = 0.05
    quant_cache = cache.k.dtype == jnp.int8

    def to_cache(x):
        if not quant_cache:
            return x.astype(cache.k.dtype)
        return jnp.clip(jnp.round(x.astype(jnp.float32) / KV_SCALE),
                        -127, 127).astype(jnp.int8)

    if pos.ndim == 0:
        kc = jax.lax.dynamic_update_slice_in_dim(cache.k, to_cache(knew), pos, 1)
        vc = jax.lax.dynamic_update_slice_in_dim(cache.v, to_cache(vnew), pos, 1)
    else:
        rows = jnp.arange(b)
        kc = cache.k.at[rows[:, None], posb].set(to_cache(knew))
        vc = cache.v.at[rows[:, None], posb].set(to_cache(vnew))
    mask = jnp.arange(t)[None, None, :] <= posb[:, :, None]    # (B, T, t)
    kc = constrain(kc, "batch", "cache_seq", "kv_heads", None)
    vc = constrain(vc, "batch", "cache_seq", "kv_heads", None)
    k_eff = kc.astype(x.dtype) * KV_SCALE if quant_cache else kc
    v_eff = vc.astype(x.dtype) * KV_SCALE if quant_cache else vc

    # grouped-query attention against the cache (no KV repetition); the T
    # query positions fold into the grouped-head axis so one einsum pair
    # serves the whole block (per-row dots — bitwise equal to T calls)
    qg = (q.reshape(b, tq, kv, g, hd).transpose(0, 2, 1, 3, 4)
          .reshape(b, kv, tq * g, hd))
    sc = jnp.einsum("bkgd,btkd->bkgt", qg, k_eff,
                    preferred_element_type=jnp.float32) * (hd ** -0.5)
    mg = jnp.broadcast_to(mask[:, None, :, None, :],
                          (b, kv, tq, g, t)).reshape(b, kv, tq * g, t)
    sc = jnp.where(mg, sc, NEG_INF)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", pr.astype(v_eff.dtype), v_eff)
    o = (o.reshape(b, kv, tq, g, hd).transpose(0, 2, 1, 3, 4)
         .reshape(b, tq, h * hd))
    o = ctx.tap("attn_out", o)
    return ctx.matmul("wo", o, p["wo"]), KVCache(kc, vc)


def attention_decode_paged(x: jnp.ndarray, p: Dict, cfg: ModelConfig, ctx,
                           lp, table: jnp.ndarray, pos: jnp.ndarray,
                           write_limit: jnp.ndarray):
    """One-token decode against a paged KV pool (``repro.kvcache``).

    ``lp`` is this layer's ``LayerPages`` pool; ``table`` (B, NP) maps
    each slot's logical pages to physical ones; ``pos`` is the (B,)
    per-slot position vector (the continuous-batching engine is the only
    caller). The new token's K/V scatter into the slot's current page —
    quantized with the page's scale when the pool stores int8/int4 —
    and the read walks the page table (Pallas kernel on TPU, the
    bit-identical jnp oracle elsewhere). Writes at positions >=
    ``write_limit`` (slot budget exhausted / slot inactive after its
    table row was unmapped) are dropped so a recycled page can never be
    corrupted by a stale slot.

    At fp page precision each row's output is bit-identical to
    ``attention_decode`` over a dense cache — the paged-vs-dense engine
    parity contract (see ``kernels.ref.paged_attention``).
    """
    from repro.kernels import ops as kops       # deferred: import cycle
    from repro.kvcache.paged import quantize_kv

    b, tq = x.shape[0], x.shape[1]
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    q = ctx.matmul("wq", x, p["wq"]).reshape(b, tq, h, hd)
    knew = ctx.matmul("wk", x, p["wk"]).reshape(b, tq, kv, hd)
    vnew = ctx.matmul("wv", x, p["wv"]).reshape(b, tq, kv, hd)
    posb = pos[:, None] + jnp.arange(tq, dtype=jnp.int32)[None, :]
    q = apply_rope(q, posb, cfg.rope_theta)
    knew = apply_rope(knew, posb, cfg.rope_theta)

    page, num_pages = lp.page_size, lp.num_pages
    rows = jnp.arange(b)
    col = jnp.clip(posb // page, 0, table.shape[1] - 1)     # (B, T)
    pid = jnp.where(posb < write_limit[:, None],
                    table[rows[:, None], col], num_pages)
    off = posb % page
    sp = jnp.clip(pid, 0, num_pages - 1)

    shards = getattr(ctx, "kv_shards", 1)
    if shards > 1 and kv % shards == 0:
        if tq != 1:
            raise NotImplementedError(
                "multi-token paged decode (speculative verify) is not "
                "supported under kv-head-sharded serving (mesh=...)")
        kc, vc, o = _paged_update_attend_sharded(
            ctx, lp, q, knew, vnew, table, pos, pid[:, 0], off[:, 0],
            sp[:, 0], cfg)
    else:
        if lp.bits < 16:
            kq = quantize_kv(knew, lp.k_scale[sp], lp.bits)
            vq = quantize_kv(vnew, lp.v_scale[sp], lp.bits)
        else:
            kq = knew.astype(lp.k.dtype)
            vq = vnew.astype(lp.v.dtype)
        # write the whole block first ((pid, off) pairs are distinct), then
        # read per query position with its own length mask — positions
        # past a query's own offset are masked by the read, so each read
        # is bitwise identical to the sequential one-token decode
        kc = lp.k.at[pid, :, off].set(kq, mode="drop")
        vc = lp.v.at[pid, :, off].set(vq, mode="drop")
        outs = [kops.paged_attention(q[:, j:j + 1], kc, vc, table,
                                     posb[:, j], lp.k_scale, lp.v_scale,
                                     lp.bits)
                for j in range(tq)]
        o = outs[0] if tq == 1 else jnp.stack(outs, axis=1)
    o = o.reshape(b, tq, h * hd).astype(x.dtype)
    o = ctx.tap("attn_out", o)
    return ctx.matmul("wo", o, p["wo"]), dataclasses.replace(lp, k=kc, v=vc)


def _paged_update_attend_sharded(ctx, lp, q, knew, vnew, table, pos, pid,
                                 off, sp, cfg: ModelConfig):
    """KV-head-sharded page write + paged-attention read (tensor-parallel
    serving, ``ShardedDequantContext.kv_shards`` > 1).

    The page pools live sharded along the kv-head axis; each shard
    quantizes and scatters its own heads' K/V (per-head elementwise —
    identical values to the replicated path), decodes paged attention
    purely locally (every kv head is independent: scores, softmax and
    the value contraction never mix heads), and the grouped-head outputs
    are concatenated with an all-gather. Concatenation of per-head
    results computed on identical data is exact, so the sharded read
    path is BIT-IDENTICAL to the replicated ``kops.paged_attention`` —
    the tp-vs-tp=1 engine parity contract.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.kvcache.paged import quantize_kv

    b = q.shape[0]
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    g = cfg.num_heads // kv
    ax = ctx.axis_name
    bits = lp.bits

    def body(k_pool, v_pool, ks, vs, qg, kn, vn, tbl, ps, pidb, offb, spb):
        # local kv-head block: (P, KV/tp, page, Dh'), scales (P, KV/tp)
        if bits < 16:
            kq = quantize_kv(kn[:, 0], ks[spb], bits)
            vq = quantize_kv(vn[:, 0], vs[spb], bits)
        else:
            kq = kn[:, 0].astype(k_pool.dtype)
            vq = vn[:, 0].astype(v_pool.dtype)
        kc = k_pool.at[pidb, :, offb].set(kq, mode="drop")
        vc = v_pool.at[pidb, :, offb].set(vq, mode="drop")
        kvl = kc.shape[1]
        ql = qg.reshape(b, 1, kvl * g, hd)         # local grouped heads
        ol = kops.paged_attention(ql, kc, vc, tbl, ps, ks, vs, bits)
        o = jax.lax.all_gather(ol, ax, axis=1, tiled=True)   # (B,KV,G,Dh)
        return kc, vc, o

    from repro.kernels import ops as kops       # deferred: import cycle
    from repro.obs import runtime as obs_rt
    qg = q.reshape(b, 1, kv, g * hd)
    rep2 = P(None, None)
    rep1 = P(None)
    fn = shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(None, ax, None, None), P(None, ax, None, None),
                  P(None, ax), P(None, ax),
                  P(None, None, ax, None), P(None, None, ax, None),
                  P(None, None, ax, None),
                  rep2, rep1, rep1, rep1, rep1),
        out_specs=(P(None, ax, None, None), P(None, ax, None, None),
                   P(None, None, None, None)),
        check_vma=False)
    if obs_rt.emitting():
        # counted from the REPLICATED positions (tp-invariant); the
        # ops-level emit inside the shard_map body is suspended below
        from repro.kernels.paged_attention import read_token_stats
        obs_rt.emit("paged_calls", 1.0)
        obs_rt.emit("paged_tokens_read", read_token_stats(pos))
    with obs_rt.suspended():
        return fn(lp.k, lp.v, lp.k_scale, lp.v_scale, qg, knew, vnew,
                  table, pos, pid, off, sp)
