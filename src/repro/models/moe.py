"""Mixture-of-Experts FFN: top-k routing, capacity-bounded scatter
dispatch, optional shared experts (DeepSeek-MoE style).

Dispatch is scatter/gather based (not the O(N·E·C) one-hot einsum of
Mesh-TF — infeasible at 1M tokens): tokens are ranked within their expert
via a cumsum over the (N·k, E) assignment matrix, dropped beyond capacity
C = ceil(cf·N·k/E), scattered into an (E, C, D) buffer, processed through
``ctx.expert_matmul`` per projection, and gathered back weighted by the
renormalized gate values. The capacity-sorted (E, C, D) segment layout
plus the per-expert ``counts`` vector IS the interface of the grouped
ragged quantized kernel: a fp/QAT/tap context runs the E batched FFNs as
one einsum, while ``DequantContext`` streams the whole packed expert
stack through ``kernels.grouped_qmm`` in ONE dispatch (and
``ShardedDequantContext`` shards it by expert — see ``_qmm_ep``).

Sharding modes (launch/sharding.py, training):
  * TP  — expert hidden dim sharded over "model" (always lowers cleanly)
  * EP  — expert axis sharded over "model"; XLA SPMD materializes the
          token exchange as all-to-alls on the dispatch scatter/gather.

Routers stay fp32 and are pinned to ≥8 bits by QuantPolicy (top-k flips
under aggressive router quantization — see DESIGN.md §5); the
``router_logits`` tap feeds ``obs.drift``'s live top-k flip gauge.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models.layers import init_dense
from repro.models.partition import constrain


def init_moe(key, cfg: ModelConfig, dtype, abstract: bool) -> Dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)

    def experts_mat(k, d_in, d_out):
        if abstract:
            return jax.ShapeDtypeStruct((e, d_in, d_out), dtype)
        return (jax.random.normal(k, (e, d_in, d_out), jnp.float32) * (d_in ** -0.5)
                ).astype(dtype)

    p = {
        "router": init_dense(ks[0], d, e, jnp.float32, abstract),
        "w_up": experts_mat(ks[1], d, f),
        "w_gate": experts_mat(ks[2], d, f),
        "w_down": experts_mat(ks[3], f, d),
    }
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        p["shared"] = {
            "w_up": init_dense(ks[4], d, fs, dtype, abstract),
            "w_gate": init_dense(ks[4], d, fs, dtype, abstract),
            "w_down": init_dense(ks[4], fs, d, dtype, abstract),
        }
    return p


def _topk_route(logits: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """logits (N, E) -> (gates (N,k) renormalized fp32, idx (N,k))."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, idx


def moe_apply(x: jnp.ndarray, p: Dict, cfg: ModelConfig, ctx
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B,S,D) -> (y, aux_loss). Dispatches to the shard_map EP path
    when partition rules are active (distributed), else the single-device
    auto path below."""
    from repro.models.partition import current_rules
    rules = current_rules()
    if (rules is not None and cfg.num_experts and "model" in rules.mesh.shape
            and cfg.num_experts % rules.mesh.shape["model"] == 0):
        return moe_apply_ep(x, p, cfg, ctx, rules)
    return _moe_apply_auto(x, p, cfg, ctx)


def _moe_apply_auto(x: jnp.ndarray, p: Dict, cfg: ModelConfig, ctx
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Reference path (single device / tests)."""
    b, s, d = x.shape
    e, k, f = cfg.num_experts, cfg.top_k, cfg.d_ff
    n = b * s
    xt = x.reshape(n, d)

    logits = xt.astype(jnp.float32) @ ctx.qw("router", p["router"])
    logits = ctx.tap("router_logits", logits)
    gates, idx = _topk_route(logits, k)                   # (N,k)

    # load-balance aux loss (Switch-style): E * Σ_e f_e · p_e
    me = jnp.mean(jax.nn.softmax(logits, -1), axis=0)                # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (n * k)
    aux = e * jnp.sum(me * ce)

    # position-in-expert via cumsum over flattened (N·k, E) assignments
    cap = int(cfg.capacity_factor * n * k / e + 0.999)
    flat_idx = idx.reshape(-1)                                       # (N·k,)
    onehot = jax.nn.one_hot(flat_idx, e, dtype=jnp.int32)            # (N·k, E)
    pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot                  # rank per expert
    pos = jnp.sum(pos, axis=-1)                                      # (N·k,)
    keep = pos < cap

    # ragged segment fill: tokens landing in expert e, capped — the
    # grouped kernel's per-segment row counts (empty experts are 0)
    assigned = jnp.sum(onehot, axis=0)                               # (E,)
    counts = jnp.minimum(assigned, cap).astype(jnp.int32)
    from repro.obs import runtime as obs_rt
    if obs_rt.emitting():
        obs_rt.emit("moe_dropped_tokens",
                    jnp.sum(assigned - counts).astype(jnp.float32))

    # scatter tokens into (E, cap, D) buffers
    xk = jnp.repeat(xt, k, axis=0)       # (N·k, D) — repeat, NOT xt[tok]:
    # a data-dependent-looking gather across a sharded token dim makes
    # XLA SPMD fall back to a dense one-hot dot_general.
    safe_pos = jnp.where(keep, pos, cap - 1)
    upd = jnp.where(keep[:, None], xk, 0).astype(x.dtype)
    buf = jnp.zeros((e, cap, d), x.dtype).at[flat_idx, safe_pos].add(
        upd, mode="drop")
    buf = constrain(buf, "experts", None, None)

    # per-projection expert dispatch: one fp einsum OR one grouped
    # ragged quantized kernel over the whole packed expert stack
    up = ctx.expert_matmul("w_up", buf, p["w_up"], counts)
    gate = jax.nn.silu(ctx.expert_matmul("w_gate", buf, p["w_gate"], counts))
    h = ctx.tap("moe_h", up * gate)
    h = constrain(h, "experts", None, "expert_ff")
    out_buf = ctx.expert_matmul("w_down", h, p["w_down"], counts)
    out_buf = constrain(out_buf, "experts", None, None)

    # gather back, weighted by gates; the k slots of one token are
    # contiguous, so the combine is a reshape + sum (no scatter).
    pulled = out_buf[flat_idx, safe_pos]                             # (N·k, D)
    pulled = jnp.where(keep[:, None], pulled, 0)
    w = gates.reshape(-1)[:, None].astype(pulled.dtype)
    y = jnp.sum((pulled * w).astype(jnp.float32).reshape(n, k, d), axis=1)
    y = y.astype(x.dtype)

    if cfg.num_shared_experts:
        # first-class matmul sites: quantized shared experts take the
        # fused kernel (and col/row sharding) like any other FFN block
        sp = p["shared"]
        su = ctx.matmul("shared_w_up", xt, sp["w_up"])
        sg = jax.nn.silu(ctx.matmul("shared_w_gate", xt, sp["w_gate"]))
        y = y + ctx.matmul("shared_w_down", ctx.tap("shared_h", su * sg),
                           sp["w_down"])

    return y.reshape(b, s, d), aux


# --------------------------------------------------------------------------
# Expert-parallel path (shard_map): the production dispatch
# --------------------------------------------------------------------------

def _local_expert_ffn(xf, p, cfg: ModelConfig, ctx, e_loc: int, cap: int,
                      gates, idx, e_offset):
    """Route xf (N,D local-row tokens) through THIS column's e_loc experts.

    All scatters/gathers here are per-device local, so XLA lowers them as
    real scatters (no SPMD one-hot rewrite). Returns the PARTIAL combine
    (only local experts' contributions) — caller reduces over "model".
    """
    n, d = xf.shape
    k = cfg.top_k
    flat_idx = idx.reshape(-1)                            # (N·k,) global ids
    local = flat_idx - e_offset                           # id within my slab
    mine = (local >= 0) & (local < e_loc)
    local_c = jnp.clip(local, 0, e_loc - 1)

    onehot = jax.nn.one_hot(local_c, e_loc, dtype=jnp.int32) * mine[:, None]
    pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot
    pos = jnp.sum(pos, axis=-1)
    keep = mine & (pos < cap)
    safe_pos = jnp.where(keep, pos, cap - 1)

    xk = jnp.repeat(xf, k, axis=0)
    upd = jnp.where(keep[:, None], xk, 0).astype(xf.dtype)
    buf = jnp.zeros((e_loc, cap, d), xf.dtype).at[local_c, safe_pos].add(
        upd, mode="drop")

    up = jnp.einsum("ecd,edf->ecf", buf, ctx.qw("w_up", p["w_up"]))
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, ctx.qw("w_gate", p["w_gate"])))
    h = ctx.tap("moe_h", up * gate)
    out_buf = jnp.einsum("ecf,efd->ecd", h, ctx.qw("w_down", p["w_down"]))

    pulled = out_buf[local_c, safe_pos]
    pulled = jnp.where(keep[:, None], pulled, 0)
    w = gates.reshape(-1)[:, None].astype(pulled.dtype)
    return jnp.sum((pulled * w).astype(jnp.float32).reshape(n, k, d), axis=1)


def moe_apply_ep(x: jnp.ndarray, p: Dict, cfg: ModelConfig, ctx, rules
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert parallelism over the "model" axis via shard_map.

    Tokens stay where they are (batch over data/pod, seq over model when
    SP is active); every model column all-gathers its data-row's tokens,
    routes them through its E/mp local experts with LOCAL scatters, and
    the partial outputs are reduce-scattered back to the SP layout (or
    psum'd when tokens are model-replicated, e.g. decode). Shared experts
    ride the same reduction as column-parallel FFNs over x_full.
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    mesh = rules.mesh
    mp = mesh.shape["model"]
    daxes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    e_loc = e // mp

    batch_ax = rules.table.get("batch")
    seq_ax = rules.table.get("seq")
    seq_sharded = seq_ax == "model" and s % mp == 0
    x_spec = P(batch_ax, "model" if seq_sharded else None, None)

    ep_spec = P("model", None, None)
    shared_specs = {"w_up": P(None, "model"), "w_gate": P(None, "model"),
                    "w_down": P("model", None)}
    p_specs = {"router": P(None, None), "w_up": ep_spec, "w_gate": ep_spec,
               "w_down": ep_spec}
    if cfg.num_shared_experts:
        p_specs["shared"] = shared_specs

    n_row = (b // _axis_prod(mesh, batch_ax)) * s      # tokens per data row
    cap = int(cfg.capacity_factor * n_row * k / e + 0.999)

    def body(xl, pl):
        nl = xl.shape[0] * xl.shape[1]
        xf = xl.reshape(nl, d)
        if seq_sharded:
            xf = jax.lax.all_gather(xf, "model", tiled=True)   # (n_row, D)

        logits = xf.astype(jnp.float32) @ ctx.qw("router", pl["router"])
        logits = ctx.tap("router_logits", logits)
        gates, idx = _topk_route(logits, k)

        me = jnp.mean(jax.nn.softmax(logits, -1), axis=0)
        ce = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (xf.shape[0] * k)
        aux = e * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, daxes)

        e_offset = jax.lax.axis_index("model") * e_loc
        y = _local_expert_ffn(xf, pl, cfg, ctx, e_loc, cap, gates, idx, e_offset)

        if cfg.num_shared_experts:
            sp = pl["shared"]
            su = xf @ ctx.qw("shared_w_up", sp["w_up"])
            sg = jax.nn.silu(xf @ ctx.qw("shared_w_gate", sp["w_gate"]))
            y = y + (ctx.tap("shared_h", su * sg) @ ctx.qw("shared_w_down", sp["w_down"])
                     ).astype(jnp.float32)

        if seq_sharded:
            # rpr-ok: RPR002 training-path fp32 expert combine — not under the serving exactness contract; fp reduction noise is part of the training numerics budget
            y = jax.lax.psum_scatter(y, "model", scatter_dimension=0, tiled=True)
        else:
            # rpr-ok: RPR002 training-path fp32 expert combine — not under the serving exactness contract (serving MoE dispatch is replicated, never psum'd)
            y = jax.lax.psum(y, "model")
        return y.astype(xl.dtype).reshape(xl.shape), aux

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, p_specs),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return mapped(x, p)


def _axis_prod(mesh, ax) -> int:
    if ax is None:
        return 1
    if isinstance(ax, (tuple, list)):
        out = 1
        for a in ax:
            out *= mesh.shape[a]
        return out
    return mesh.shape[ax]
