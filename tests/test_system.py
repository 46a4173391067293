"""End-to-end behaviour: the full FIT workflow + fault-tolerant training.

These are the paper's pipelines run at CPU scale: train an FP model →
compute FIT from it → allocate mixed-precision bits → QAT → verify the
quantized accuracy holds. Plus checkpoint/restart and watchdog behaviour
of the training driver.
"""
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import build_report, greedy_allocate
from repro.data.synthetic import ClassifyConfig, batched, classify_dataset
from repro.launch.fault import Watchdog, supervise
from repro.launch.train import train
from repro.models.cnn import (
    cnn_accuracy, cnn_act_fn, cnn_loss, cnn_tap_loss, cnn_tap_shapes, init_cnn)
from repro.models.context import QATContext
from repro.quant.policy import QuantPolicy


def test_end_to_end_fit_mpq_workflow():
    """FP train → FIT report → greedy MPQ → QAT — the quickstart path."""
    dcfg = ClassifyConfig(input_hw=8, num_classes=4, seed=5)
    xtr, ytr = classify_dataset(dcfg, 1024)
    xte, yte = classify_dataset(dcfg, 256, split_seed=9)
    params = init_cnn(jax.random.key(0), num_classes=4, input_hw=8,
                      filters=8, batchnorm=False)

    @jax.jit
    def step(p, b):
        loss, g = jax.value_and_grad(cnn_loss)(p, b)
        return jax.tree.map(lambda a, gg: a - 3e-3 * gg, p, g), loss

    for i, b in enumerate(batched(xtr, ytr, 128, seed=0)):
        if i >= 300:
            break
        params, _ = step(params, (jnp.asarray(b[0]), jnp.asarray(b[1])))
    fp_acc = cnn_accuracy(params, jnp.asarray(xte), jnp.asarray(yte))
    assert fp_acc > 0.7

    batch = (jnp.asarray(xtr[:256]), jnp.asarray(ytr[:256]))
    report = build_report(cnn_loss, cnn_tap_loss,
                          lambda b: cnn_tap_shapes(params, b), cnn_act_fn,
                          params, [batch], tolerance=None, max_batches=1)
    assert set(report.act_traces) == {"act1", "act2", "act3"}

    policy = QuantPolicy(allowed_bits=(8, 6, 4, 3), pinned_substrings=())
    total = sum(report.param_sizes.values())
    cfg = greedy_allocate(report, policy, budget_bits=5.0 * total)

    # QAT with the chosen config
    lw = {k: float(2 ** b - 1) for k, b in cfg.weight_bits.items()}
    la = {k: float(2 ** b - 1) for k, b in cfg.act_bits.items()}

    @jax.jit
    def qstep(p, b):
        loss, g = jax.value_and_grad(
            lambda pp: cnn_loss(pp, b, ctx=QATContext(lw, la)))(p)
        return jax.tree.map(lambda a, gg: a - 1e-3 * gg, p, g), loss

    qparams = params
    for i, b in enumerate(batched(xtr, ytr, 128, seed=1)):
        if i >= 100:
            break
        qparams, _ = qstep(qparams, (jnp.asarray(b[0]), jnp.asarray(b[1])))

    # quantized-eval accuracy of the QAT model
    from repro.models.cnn import cnn_forward
    logits = cnn_forward(qparams, jnp.asarray(xte), ctx=QATContext(lw, la))
    q_acc = float(jnp.mean((jnp.argmax(logits, -1) == jnp.asarray(yte))))
    assert q_acc > fp_acc - 0.12, (fp_acc, q_acc)


def test_train_driver_checkpoint_resume(tmp_path):
    """Kill-and-resume: step counts and loss trajectory stay consistent."""
    d = str(tmp_path / "ck")
    r1 = train("llama3_8b", smoke=True, steps=6, batch=4, seq=32,
               ckpt_dir=d, resume=False, ckpt_every=3,
               qat_weight_bits=None, qat_act_bits=None, watchdog_s=None)
    # fresh process state; resume from step 6 checkpoint and continue
    r2 = train("llama3_8b", smoke=True, steps=10, batch=4, seq=32,
               ckpt_dir=d, resume=True, ckpt_every=5,
               qat_weight_bits=None, qat_act_bits=None, watchdog_s=None)
    assert len(r2["losses"]) == 4          # resumed at 6, ran 6..9
    # margin-robust: a strict single-step comparison flakes on step-level
    # noise (resumed losses sit within ~0.01 of the first-run losses), so
    # anchor on the first run's final loss plus a noise margin — still
    # catches a resume that restores wrong params or diverges.
    assert np.isfinite(r2["final_loss"])
    assert r2["final_loss"] < r1["losses"][-1] + 0.05, \
        (r2["final_loss"], r1["losses"])


def test_train_driver_qat_path():
    r = train("internlm2_1_8b", smoke=True, steps=5, batch=4, seq=32,
              ckpt_dir=None, resume=False, ckpt_every=0,
              qat_weight_bits=4, qat_act_bits=8, watchdog_s=None)
    assert np.isfinite(r["final_loss"])


def test_serve_driver_quantized():
    from repro.launch.serve import serve
    out8 = serve("internlm2_1_8b", smoke=True, batch=2, prompt_len=8,
                 gen_len=4, weight_bits=8)
    out_fp = serve("internlm2_1_8b", smoke=True, batch=2, prompt_len=8,
                   gen_len=4, weight_bits=None)
    assert out8["generated"].shape == (2, 4)
    # 8-bit weights rarely flip greedy tokens on a random-init model, but
    # both paths must at least produce valid token ids
    assert out8["generated"].min() >= 0
    assert out8["generated"].max() < 384


def test_watchdog_fires_and_supervise_restarts():
    fired = []
    wd = Watchdog(0.15, on_timeout=lambda: fired.append(1))
    wd.arm()
    time.sleep(0.4)
    assert fired, "watchdog must fire on missed deadline"
    wd.stop()

    # disarm prevents firing
    fired2 = []
    wd2 = Watchdog(0.15, on_timeout=lambda: fired2.append(1))
    wd2.arm()
    wd2.disarm()
    time.sleep(0.3)
    assert not fired2
    wd2.stop()

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("simulated node failure")

    restarts = supervise(flaky, max_restarts=5, backoff_s=0.01)
    assert restarts == 2


ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("kernels", [None, "ref"])
def test_chip_smoke_refuses_without_chip(tmp_path, kernels):
    """No TPU (this CPU host), or the oracle kernel route: the chip smoke
    exits non-zero, names the platform it found, and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_KERNELS", None)
    if kernels:
        env["REPRO_KERNELS"] = kernels
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env,
                       cwd=tmp_path, timeout=300)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_compile_cache_placed_from_outside(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins and is left to JAX; without it
    the cache goes to the one fixed path in the checkout."""
    code = (
        "import jax; from repro.utils.compile_cache import "
        "REPO_CACHE_DIR, use_compile_cache; d = use_compile_cache(); "
        "jax.jit(lambda x: x + 1)(1.0).block_until_ready(); "
        "print(d, '|', REPO_CACHE_DIR, '|', "
        "jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300, check=True)
    used, repo_dir, configured = (
        s.strip() for s in r.stdout.strip().splitlines()[-1].split("|"))
    assert used == configured == str(tmp_path / "cache")
    assert any((tmp_path / "cache").iterdir())       # written there
    assert Path(repo_dir) == ROOT / ".jax_cache"
