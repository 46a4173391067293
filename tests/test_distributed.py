"""Distributed semantics on an 8-device CPU host mesh.

Each test runs in a subprocess so XLA_FLAGS (device count) can be set
before jax initializes — the main pytest process stays single-device.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.setdefault("REPRO_KERNELS", "ref")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_dp_tp_training_step_matches_single_device():
    """One pjit train step on a (2,4) mesh == the same step on 1 device."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import smoke_config, ShapeSpec
        from repro.launch.mesh import make_mesh
        from repro.launch.sharding import ShardOptions
        from repro.launch.steps import TrainState, build_train_step
        from repro.models import init_params
        from repro.optim.adamw import init_adam

        cfg = smoke_config("llama3_8b")
        shape = ShapeSpec("t", 32, 4, "train")
        rng = np.random.default_rng(0)
        batch = {"tokens": rng.integers(0, cfg.vocab_size, (4, 32)).astype(np.int32),
                 "labels": rng.integers(0, cfg.vocab_size, (4, 32)).astype(np.int32)}
        params = init_params(cfg, jax.random.key(0))

        # independent copies: train_step donates its input state
        p1 = jax.tree.map(jnp.array, params)
        p8 = jax.tree.map(jnp.array, params)

        # single device
        mesh1 = make_mesh((1, 1), ("data", "model"))
        b1 = build_train_step(cfg, shape, mesh1, ShardOptions(zero1=False))
        s1, m1 = b1.fn(TrainState(p1, init_adam(p1)), batch)

        # 2x4 mesh
        mesh8 = make_mesh((2, 4), ("data", "model"))
        b8 = build_train_step(cfg, shape, mesh8, ShardOptions(zero1=True))
        s8, m8 = b8.fn(TrainState(p8, init_adam(p8)), batch)

        assert np.isclose(float(m1["loss"]), float(m8["loss"]), rtol=1e-4), \\
            (float(m1["loss"]), float(m8["loss"]))
        l1 = jax.tree_util.tree_leaves(s1.params)
        l8 = jax.tree_util.tree_leaves(s8.params)
        for a, b in zip(l1, l8):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=3e-2, atol=3e-3)
        print("DP/TP train step parity OK")
    """)


def test_moe_ep_matches_single_device():
    run_sub("""
        import dataclasses
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import smoke_config, ShapeSpec
        from repro.models import init_params, forward
        from repro.models.partition import use_rules
        from repro.launch.mesh import make_mesh
        from repro.launch.sharding import make_rules, ShardOptions, param_pspecs

        mesh = make_mesh((2, 4), ("data", "model"))
        for arch in ["olmoe_1b_7b", "deepseek_moe_16b"]:
            cfg = dataclasses.replace(smoke_config(arch), capacity_factor=16.0)
            params = init_params(cfg, jax.random.key(0))
            rng = np.random.default_rng(0)
            inputs = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)),
                                            jnp.int32)}
            ref, _ = jax.jit(lambda p, i: forward(p, i, cfg))(params, inputs)
            shape = ShapeSpec("t", 32, 4, "train")
            rules = make_rules(cfg, shape, mesh, ShardOptions())
            p_sh = param_pspecs(params, cfg, mesh, ShardOptions())
            params_s = jax.device_put(params, p_sh)
            def fwd(p, i):
                with use_rules(rules):
                    return forward(p, i, cfg)[0]
            out = jax.jit(fwd)(params_s, inputs)
            rel = float(jnp.max(jnp.abs(ref - out))) / float(jnp.max(jnp.abs(ref)))
            assert rel < 2e-3, (arch, rel)
        print("MoE EP parity OK")
    """)


def test_compressed_psum_matches_mean():
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.optim.compression import compressed_psum

        mesh = make_mesh((8,), ("data",))
        x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (8, 128)).astype(np.float32))

        f = shard_map(lambda xl: compressed_psum(xl[0], "data")[None],
                      mesh=mesh, in_specs=P("data", None), out_specs=P("data", None))
        out = f(x)
        expected = np.mean(np.asarray(x), axis=0)
        for row in np.asarray(out):
            np.testing.assert_allclose(row, expected, atol=np.abs(expected).max()*0.03 + 1e-3)
        print("compressed psum OK")
    """)


def test_pipeline_parallel_matches_sequential():
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.launch.pipeline import pipeline_apply, sequential_apply

        mesh = make_mesh((4,), ("pipe",))
        S, M, MB, D = 4, 6, 8, 16
        rng = np.random.default_rng(0)
        params = {"w": jnp.asarray(rng.normal(0, 0.3, (S, D, D)).astype(np.float32)),
                  "b": jnp.asarray(rng.normal(0, 0.1, (S, D)).astype(np.float32))}
        x = jnp.asarray(rng.normal(size=(M, MB, D)).astype(np.float32))

        def layer(p, h):
            return jnp.tanh(h @ p["w"] + p["b"])

        out_p = pipeline_apply(layer, params, x, mesh)
        out_s = sequential_apply(layer, params, x)
        np.testing.assert_allclose(out_p, out_s, rtol=1e-5, atol=1e-5)

        # differentiability: grad of sum flows through ppermute
        g = jax.grad(lambda pp: jnp.sum(pipeline_apply(layer, pp, x, mesh)))(params)
        assert np.isfinite(float(jnp.sum(g["w"])))
        print("pipeline parallel OK")
    """)


def test_elastic_checkpoint_restore_across_meshes(tmp_path):
    run_sub(f"""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.checkpoint.checkpointer import Checkpointer

        tree = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
        mesh8 = make_mesh((8,), ("data",))
        sh8 = {{"w": NamedSharding(mesh8, P("data", None))}}
        sharded = jax.device_put(tree, sh8)

        ck = Checkpointer({str(tmp_path)!r})
        ck.save(5, sharded)

        # restore onto a DIFFERENT mesh shape (elastic scale-down 8 -> 2x2)
        mesh4 = make_mesh((2, 2), ("data", "model"))
        sh4 = {{"w": NamedSharding(mesh4, P("model", "data"))}}
        restored = ck.restore(5, tree, sh4)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        assert restored["w"].sharding.spec == P("model", "data")
        print("elastic restore OK")
    """)


def test_ef_trace_sharded_matches_single_device():
    """Data-parallel EF trace (shard_map batch axis + psum of per-block
    squared norms) == single-device traces on an 8-device host mesh."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ef_trace_weights, build_report
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(0)
        p = {"l1": {"w": jnp.asarray(rng.normal(0, .5, (8, 16)), jnp.float32),
                    "b": jnp.zeros(16)},
             "l2": {"w": jnp.asarray(rng.normal(0, .5, (16, 4)), jnp.float32),
                    "b": jnp.zeros(4)}}
        X = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
        Y = jnp.asarray(rng.integers(0, 4, 64), jnp.int32)

        def loss_fn(p, batch):
            x, y = batch
            h = jnp.tanh(x @ p["l1"]["w"] + p["l1"]["b"])
            logits = h @ p["l2"]["w"] + p["l2"]["b"]
            lp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(lp, y[:, None], 1))

        mesh = make_mesh((8,), ("data",))
        ref = ef_trace_weights(loss_fn, p, (X, Y))
        sh = ef_trace_weights(loss_fn, p, (X, Y), mesh=mesh)
        assert set(ref) == set(sh)
        for k in ref:
            np.testing.assert_allclose(sh[k], ref[k], rtol=1e-5)

        # microbatched within each shard: same estimate
        shmb = ef_trace_weights(loss_fn, p, (X, Y), microbatch=4, mesh=mesh)
        for k in ref:
            np.testing.assert_allclose(shmb[k], ref[k], rtol=1e-5)

        # end-to-end through build_report
        rep1 = build_report(loss_fn, None, None, None, p, [(X, Y)],
                            tolerance=None, max_batches=1)
        rep8 = build_report(loss_fn, None, None, None, p, [(X, Y)],
                            tolerance=None, max_batches=1, mesh=mesh)
        for k in rep1.weight_traces:
            np.testing.assert_allclose(rep8.weight_traces[k],
                                       rep1.weight_traces[k], rtol=1e-5)
        print("sharded EF trace parity OK")
    """)


def test_dryrun_single_cell_small_mesh():
    """The dry-run machinery end-to-end on a small mesh (fast CI proxy
    for the 512-device run)."""
    run_sub("""
        import jax
        from repro.configs import SHAPES, smoke_config
        import dataclasses
        from repro.launch.mesh import make_mesh
        from repro.launch.sharding import ShardOptions
        from repro.launch.steps import build_step

        cfg = dataclasses.replace(smoke_config("llama3_8b"), scan_layers=True)
        mesh = make_mesh((2, 4), ("data", "model"))
        shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=4)
        build = build_step(cfg, shape, mesh, ShardOptions())
        compiled = build.fn.lower(*build.args).compile()
        assert compiled.cost_analysis().get("flops", 0) > 0
        ma = compiled.memory_analysis()
        assert ma.temp_size_in_bytes >= 0
        print("small-mesh dryrun OK")
    """)
