"""The system under test, as the benchmark drives it: the program's model
config built from a configuration file, the packed weights made in one
jitted call from the seed, and the serving engine with the file's
settings. Everything else the benchmark uses is its own (``yardstick``).
"""
from __future__ import annotations

import dataclasses

import jax

from yardstick import weights

ACTS = {"silu": "swiglu", "relu2": "relu2"}


def model_config(conf: dict):
    """The program's ``ModelConfig``, with every size taken from the
    configuration file."""
    from repro.configs import get_config
    base = get_config(conf["registry"])
    return dataclasses.replace(
        base, num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        act=ACTS[conf["hidden_act"]], norm_eps=conf["rms_norm_eps"],
        rope_theta=conf["rope_theta"], dtype=conf["torch_dtype"],
        scan_layers=False)


def bit_config(conf: dict):
    from repro.quant.policy import BitConfig
    return BitConfig(dict(conf["allocation"]["weight_bits"]), {})


def packed_weights(conf: dict, dims, seed: int):
    """bf16 weights from the seed, packed by the program's PTQ at the
    stored allocation, in one jitted call on the device."""
    from repro.quant.policy import QuantPolicy
    from repro.serve import quantize_params
    alloc = conf["allocation"]
    policy = QuantPolicy(allowed_bits=tuple(alloc["allowed_bits"]))
    bit_cfg = bit_config(conf)
    gs = conf["engine"]["group_size"]
    make = jax.jit(lambda k: quantize_params(
        weights.make_params(dims, k), bit_cfg, policy, group_size=gs)[0])
    return make(weights.seed_key(seed))


def engine(conf: dict, cfg, qparams):
    from repro.serve import Engine, EngineConfig
    e = conf["engine"]
    ecfg = EngineConfig(
        max_slots=e["max_slots"], max_len=e["max_len"],
        max_new_tokens=e["max_new_tokens"], prefill_chunk=e["prefill_chunk"],
        decode_burst=e["decode_burst"], clock="wall",
        int8_compute=e["int8_compute"], kv_cache=e["kv_cache"],
        page_size=e["page_size"], kv_pages=e.get("kv_pages"))
    return Engine(qparams, cfg, ecfg, kv_bits=e["kv_bits"])
