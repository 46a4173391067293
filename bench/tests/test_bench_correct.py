"""The serving cell's check of ``correct``, on the CPU at a size a test
run can hold: a whole run (the chip look skipped) passes; the control,
the reference one notch below the int8 activations the configuration
states, fails the same limit; and a run whose tokens are altered where
they are produced comes out not correct."""
import json
import time
from pathlib import Path

import numpy as np
import pytest

import _paths  # noqa: F401
from harness import serve
from yardstick import reference, registry

CONF = json.loads((Path(__file__).parent / "data" / "tiny_dense.json").read_text())
MIX = {"kind": "serve", "arrivals": {"rate_per_s": 2.0},
       "prompt_tokens": {"dist": "lognormal", "mean": 40, "sigma_log": 1.0,
                         "min": 4, "max": 120},
       "output_tokens": {"dist": "lognormal", "mean": 30, "sigma_log": 0.8,
                         "min": 8, "max": 60}}
SEED = 2 ** 31 + 3


def run(seed=SEED):
    return serve.run_cell(CONF, MIX, seed, 3.0, False, time.perf_counter(),
                          CONF["limits"])


@pytest.fixture(scope="module")
def sound():
    return run()


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["failed"] == 0 and sound["attempted"] == 6
    assert set(sound["e2e"]) == {"setup_s", "ttft_p95_s", "tpot_p95_ms",
                                 "output_tokens_per_s"}
    assert sound["checks"]["widest_gap_sd"]["value"] < CONF["limits"]["widest_gap_sd"]


def test_control_fails_the_limit(sound):
    s = sound["sample"]
    gaps = reference.served_gaps(registry.model_dims(CONF),
                                 CONF["allocation"]["weight_bits"], 128, SEED,
                                 s["prompts"], s["outputs"],
                                 control_bits=reference.CONTROL_BITS)
    assert float(np.max(gaps["served"])) == \
        pytest.approx(sound["checks"]["widest_gap_sd"]["value"])
    assert float(np.max(gaps["control"])) > CONF["limits"]["widest_gap_sd"]


def test_altered_tokens_are_not_correct(monkeypatch):
    import jax.numpy as jnp
    import repro.serve.engine as engine_mod

    real = engine_mod.greedy_tokens
    vocab = CONF["vocab_size"]
    monkeypatch.setattr(engine_mod, "greedy_tokens",
                        lambda lg: (real(lg) + 1) % jnp.int32(vocab))
    res = run()
    assert res["failed"] == 0
    assert not res["correct"]
    assert res["checks"]["widest_gap_sd"]["value"] > CONF["limits"]["widest_gap_sd"]
