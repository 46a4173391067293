"""Plain float32 reference of the served model, and the comparison that
decides ``correct`` for a serving cell.

It imports nothing of the program. It regenerates each layer's bf16
weights from the seed (``weights``), quantizes them itself to the bits
the configuration stores (symmetric, one scale per group of 128 rows and
column: the scheme the configuration states), and runs the forward pass
in float32 at ``highest`` matmul precision, one layer at a time over all
sampled sequences, so that the whole model is never held at once.
Activations and the KV cache stay float32: the int8 activation and KV
grids of the served path are a departure it must stay within.

The number compared, per served token, is how far the token's logit lies
below the reference's best, in units of the standard deviation of the
reference's logits at that position; a run is judged by the widest such
gap over a seeded sample of its finished requests.

``act_bits`` quantizes every matmul input per row to that many bits: at
``CONTROL_BITS`` it is the control, the reference one notch below the
int8 activations the configuration states.

Every pass has one shape per cell, so it compiles once and the cache
keeps it: ``BATCH`` sequences (empty ones fill a short batch), each
padded to a whole number of ``TILE`` positions, and the head applied to
``TILE`` rows at a time.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import weights

CONTROL_BITS = 4
BATCH = 4
TILE = 256


def dequantized(w: jnp.ndarray, bits, group: int) -> jnp.ndarray:
    """bf16 (K, N) -> float32 values on the symmetric ``bits`` grid with
    one scale per (group of rows, column); ``bits`` may be traced."""
    k, n = w.shape
    w32 = w.astype(jnp.float32)
    qmax = 2.0 ** (jnp.minimum(bits, 8) - 1) - 1
    amax = jnp.max(jnp.abs(w32).reshape(k // group, group, n), axis=1)
    scale = jnp.maximum(amax, 1e-12) / qmax
    s = jnp.repeat(scale, group, axis=0)
    return jnp.clip(jnp.round(w32 / s), -qmax, qmax) * s


def act_round(x: jnp.ndarray, bits: Optional[int]) -> jnp.ndarray:
    """Per-row symmetric rounding of a matmul input (``None``: none)."""
    if bits is None:
        return x
    qmax = 2.0 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / qmax
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


def rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, theta):
    """Rotate adjacent pairs (x[2i], x[2i+1]) of each head by position.
    x: (B, T, H, D)."""
    t, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs      # (T, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def layer_bits(dims, alloc: Dict[str, int], i: int) -> jnp.ndarray:
    return jnp.asarray([alloc.get(f"layers/{i}/{p}", 16)
                        for p, _ in weights.layer_leaves(dims)], jnp.float32)


def _mm(x, w, bits, group, act_bits):
    return act_round(x, act_bits) @ jnp.where(
        bits < 16, dequantized(w, bits, group), w.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("dims_items", "group",
                                             "act_bits"))
def _layer(x, key, i, bits, *, dims_items, group, act_bits):
    dims = dict(dims_items)
    b, t, _ = x.shape
    h, kv, hd = (dims["num_attention_heads"], dims["num_key_value_heads"],
                 dims["head_dim"])
    names = [p for p, _ in weights.layer_leaves(dims)]
    w = {}
    for j, (p, shape) in enumerate(weights.layer_leaves(dims)):
        w[p] = weights._normal(key, shape, shape[0] ** -0.5, 2 + 8 * i + j)
    mm = lambda a, p: _mm(a, w[p], bits[names.index(p)], group, act_bits)

    with jax.default_matmul_precision("highest"):
        a = rmsnorm(x, dims["rms_norm_eps"])
        q = rope(mm(a, "attn/wq").reshape(b, t, h, hd), dims["rope_theta"])
        k = rope(mm(a, "attn/wk").reshape(b, t, kv, hd), dims["rope_theta"])
        v = mm(a, "attn/wv").reshape(b, t, kv, hd)
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        sc = jnp.einsum("bshd,bthd->bhst", q, k) * hd ** -0.5
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bhst,bthd->bshd", pr, v).reshape(b, t, h * hd)
        x = x + mm(o, "attn/wo")
        a = rmsnorm(x, dims["rms_norm_eps"])
        if dims["hidden_act"] == "silu":
            f = mm(a, "mlp/w_up") * jax.nn.silu(mm(a, "mlp/w_gate"))
        else:
            f = jnp.square(jax.nn.relu(mm(a, "mlp/w_up")))
        return x + mm(f, "mlp/w_down")


@functools.partial(jax.jit, static_argnames=("dims_items", "group",
                                             "act_bits"))
def _head_rows(xr, key, bits, *, dims_items, group, act_bits):
    """float32 logits of the hidden rows ``xr`` (R, D)."""
    dims = dict(dims_items)
    head = weights.make_top(dims, key)["head"]
    with jax.default_matmul_precision("highest"):
        a = rmsnorm(xr, dims["rms_norm_eps"])
        lg = _mm(a, head, bits, group, act_bits)
    return lg[:, :dims["vocab_size"]]


@jax.jit
def _gaps(ref_lg, picked):
    """(best - logit of ``picked``) / std, per row of the reference."""
    best = jnp.max(ref_lg, axis=-1)
    got = jnp.take_along_axis(ref_lg, picked[:, None], axis=-1)[:, 0]
    return (best - got) / jnp.std(ref_lg, axis=-1)


def hidden_states(dims, alloc, group, seed, seqs: Sequence[np.ndarray],
                  act_bits: Optional[int] = None,
                  length: Optional[int] = None) -> List[np.ndarray]:
    """Final hidden states (before the final norm) of each sequence, the
    sequences padded to ``length`` (default: the longest)."""
    key = weights.seed_key(seed)
    items = tuple(sorted(dims.items()))
    t = length or max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), t), np.int32)
    for r, s in enumerate(seqs):
        toks[r, :len(s)] = s
    embed = weights.make_top(dims, key)["embed"]
    x = jnp.take(embed, jnp.asarray(toks), axis=0).astype(jnp.float32)
    del embed
    for i in range(dims["num_hidden_layers"]):
        x = _layer(x, key, jnp.int32(i), layer_bits(dims, alloc, i),
                   dims_items=items, group=group, act_bits=act_bits)
    return x


def served_gaps(dims, alloc, group, seed, prompts, outputs,
                control_bits: Optional[int] = None) -> Dict[str, np.ndarray]:
    """Per served token, the reference's gap (see the module docstring).

    With ``control_bits`` also the gap of the token the control (the
    reference at ``control_bits`` activations) puts first at each
    position, under ``"control"``."""
    seqs = [np.concatenate([p, o]).astype(np.int32)
            for p, o in zip(prompts, outputs)]
    length = TILE * -(-max(len(s) for s in seqs) // TILE)
    items = tuple(sorted(dims.items()))
    key = weights.seed_key(seed)
    hbits = jnp.float32(alloc.get("head", 16))
    runs = {"served": None}
    if control_bits is not None:
        runs["control"] = control_bits
    out = {name: [] for name in runs}
    for b0 in range(0, len(seqs), BATCH):
        part = seqs[b0:b0 + BATCH]
        part += [part[0][:0]] * (BATCH - len(part))
        hidden = {name: hidden_states(dims, alloc, group, seed, part, bits,
                                      length)
                  for name, bits in runs.items()}
        for r, (p, o) in enumerate(zip(prompts[b0:b0 + BATCH],
                                       outputs[b0:b0 + BATCH])):
            pos = np.arange(len(p) - 1, len(p) + len(o) - 1)
            for lo in range(0, len(pos), TILE):
                rows = pos[lo:lo + TILE]
                n = len(rows)
                rows = np.pad(rows, (0, TILE - n), mode="edge")
                ref = _head_rows(hidden["served"][r, rows], key, hbits,
                                 dims_items=items, group=group, act_bits=None)
                picked = jnp.asarray(np.asarray(o)[rows - len(p) + 1], jnp.int32)
                out["served"].append(np.asarray(_gaps(ref, picked))[:n])
                if control_bits is not None:
                    ctl = _head_rows(hidden["control"][r, rows], key, hbits,
                                     dims_items=items, group=group,
                                     act_bits=control_bits)
                    out["control"].append(np.asarray(_gaps(
                        ref, jnp.argmax(ctl, axis=-1).astype(jnp.int32)))[:n])
    return {name: np.concatenate(v) for name, v in out.items()}
