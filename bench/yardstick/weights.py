"""Seeded random weights, made by the benchmark and not by the program.

The tree has the program's unrolled parameter layout (``embed``,
``head``, ``final_norm``, ``layers/<i>/{ln1, attn/{wq,wk,wv,wo}, ln2,
mlp/{w_up, w_gate, w_down}}``) so the program can serve it, and every
leaf comes from its own key, folded from the seed by the leaf's index:
the reference regenerates any one layer alone, in the same values,
without holding the whole model.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

EMBED_STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A key for any whole-number seed up to 2**64 - 1."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def vocab_rows(dims) -> int:
    v = int(dims["vocab_size"])
    return -(-v // 16) * 16


def layer_leaves(dims) -> List[Tuple[str, Tuple[int, int]]]:
    """(path inside a layer, shape) of one layer's matrices, in key order."""
    d, h, kv, hd = (dims["hidden_size"], dims["num_attention_heads"],
                    dims["num_key_value_heads"], dims["head_dim"])
    ff = dims["intermediate_size"]
    out = [("attn/wq", (d, h * hd)), ("attn/wk", (d, kv * hd)),
           ("attn/wv", (d, kv * hd)), ("attn/wo", (h * hd, d)),
           ("mlp/w_up", (d, ff)), ("mlp/w_down", (ff, d))]
    if dims["hidden_act"] == "silu":
        out.append(("mlp/w_gate", (d, ff)))
    return out


def _normal(key, shape, std, index: int) -> jnp.ndarray:
    k = jax.random.fold_in(key, index)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(jnp.bfloat16)


def make_top(dims, key) -> Dict[str, jnp.ndarray]:
    """Embedding, head and final norm (bf16)."""
    v, d = vocab_rows(dims), dims["hidden_size"]
    return {"embed": _normal(key, (v, d), EMBED_STD, 0),
            "head": _normal(key, (d, v), EMBED_STD, 1),
            "final_norm": jnp.ones((d,), jnp.bfloat16)}


def make_layer(dims, key, i: int) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Layer ``i`` (bf16), in the program's nested layout."""
    d = dims["hidden_size"]
    layer: Dict[str, Dict[str, jnp.ndarray]] = {
        "ln1": jnp.ones((d,), jnp.bfloat16), "attn": {},
        "ln2": jnp.ones((d,), jnp.bfloat16), "mlp": {}}
    for j, (path, shape) in enumerate(layer_leaves(dims)):
        group, leaf = path.split("/")
        layer[group][leaf] = _normal(key, shape, shape[0] ** -0.5,
                                     2 + 8 * i + j)
    return layer


def make_params(dims, key) -> Dict:
    """The whole bf16 tree; jit it with ``dims`` closed over."""
    params = make_top(dims, key)
    params["layers"] = {str(i): make_layer(dims, key, i)
                        for i in range(dims["num_hidden_layers"])}
    return params
