"""Static analysis subsystem (repro.analysis): each pass must catch its
golden bad fixture, the kernel-facing validators must refuse unsafe
shapes at trace time, and the repo's own tree must come back clean.

Structure mirrors the three passes:

  * lint (RPR0xx)   — AST fixtures fed through ``lint_source``;
  * jaxpr (RPR1xx)  — hand-built bad jaxprs fed through
    ``check_closed_jaxpr`` (lossy cast, float64, hot-path callback,
    unproven fp psum) plus the good constructions that must NOT fire
    (int32 psum, zeros + disjoint dynamic_update_slice slots);
  * bounds (RPR2xx) — overflow arithmetic, the raising validators, and
    the kernel entry points that now refuse statically-unsafe shapes.

The clean-tree test runs the full CLI (``python -m repro.analysis
--all``) in a subprocess with an 8-virtual-device host platform — the
acceptance oracle that the shipped tree has zero errors.
"""
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import RULES, Report, run_all
from repro.analysis.findings import Finding, suppressed_codes
from repro.analysis import bounds as B
from repro.analysis.jaxpr_check import check_closed_jaxpr
from repro.analysis.lint import _check_pack_tables, lint_source

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def codes(findings):
    return sorted(f.code for f in findings)


# ---------------------------------------------------------------------------
# findings plumbing
# ---------------------------------------------------------------------------

def test_finding_validates_code_and_severity():
    with pytest.raises(ValueError, match="unknown rule code"):
        Finding("RPR999", "error", "x", "m")
    with pytest.raises(ValueError, match="unknown severity"):
        Finding("RPR001", "fatal", "x", "m")
    f = Finding("RPR201", "error", "t", "boom", line=3, path="a/b.py")
    assert "a/b.py:3" in f.render() and f.render().startswith("ERROR")
    gh = f.render_github()
    assert gh.startswith("::error file=a/b.py,line=3::RPR201:")


def test_report_exit_code_severity_tiers():
    r = Report()
    r.add(Finding("RPR203", "warning", "t", "rounding tier"))
    r.add(Finding("RPR100", "info", "t", "env note"))
    assert r.exit_code() == 0                    # warnings/info tolerated
    r.add(Finding("RPR201", "error", "t", "overflow"))
    assert r.exit_code() == 1 and len(r.errors) == 1


def test_suppression_marker_requires_reason():
    lines = ["x = f()  # rpr-ok: RPR002 int32 operand",
             "# rpr-ok: RPR003",            # bare marker: no reason
             "y = g()"]
    assert suppressed_codes(lines, 1) == {"RPR002"}
    assert suppressed_codes(lines, 3) == set()   # reasonless marker ignored
    # marker on the line above the flagged one
    assert suppressed_codes(["# rpr-ok: RPR007 bounds-checked", "assert x"],
                            2) == {"RPR007"}


# ---------------------------------------------------------------------------
# lint fixtures (RPR0xx)
# ---------------------------------------------------------------------------

def test_lint_rpr001_quantize_pack_unit_violation():
    src = "w = quantize(x, 4, group_size=9)\n"    # 4-bit pack unit is 2
    fs = lint_source(src, "repro/somewhere.py")
    assert codes(fs) == ["RPR001"] and "pack unit" in fs[0].message
    # aligned group: clean; keyword form also parsed
    assert lint_source("w = quantize(x, bits=4, group_size=8)\n",
                       "repro/s.py") == []
    # non-literal args: not statically decidable, stays quiet
    assert lint_source("w = quantize(x, bits, group_size=g)\n",
                       "repro/s.py") == []


def test_lint_rpr002_unmarked_psum():
    fs = lint_source("y = jax.lax.psum(x, 'tp')\n", "repro/m.py")
    assert codes(fs) == ["RPR002"]
    ok = ("# rpr-ok: RPR002 int32 operand - integer adds are exact\n"
          "y = jax.lax.psum(x, 'tp')\n")
    assert lint_source(ok, "repro/m.py") == []


def test_lint_rpr003_float64():
    assert codes(lint_source("y = x.astype('float64')\n",
                             "repro/m.py")) == ["RPR003"]
    assert codes(lint_source("y = jnp.zeros(3, jnp.float64)\n",
                             "repro/m.py")) == ["RPR003"]
    # host-side numpy doubles are fine (never enter a trace)
    assert lint_source("y = x.astype(np.float64)\n", "repro/m.py") == []


def test_lint_rpr004_and_rpr007_kernel_grade_rules():
    src = "v = float(levels)\nassert x.shape[0] == k\n"
    fs = lint_source(src, "repro/kernels/foo.py")
    assert codes(fs) == ["RPR004", "RPR007"]
    # the same code outside kernels/ is not held to kernel grade
    assert lint_source(src, "repro/core/foo.py") == []
    # float() on a literal is fine even in kernels
    assert lint_source("v = float(2)\n", "repro/kernels/foo.py") == []


def test_lint_rpr006_set_iteration_order_hazard():
    fs = lint_source("out = [f(k) for k in set(names)]\n", "repro/m.py")
    assert codes(fs) == ["RPR006"]
    assert lint_source("out = [f(k) for k in sorted(set(names))]\n",
                       "repro/m.py") == []


def test_lint_rpr005_pack_tables_in_sync():
    assert _check_pack_tables() == []


# ---------------------------------------------------------------------------
# jaxpr fixtures (RPR1xx)
# ---------------------------------------------------------------------------

def test_jaxpr_rpr102_lossy_int32_downcast():
    closed = jax.make_jaxpr(lambda x: x.astype(jnp.bfloat16))(
        jnp.zeros((4,), jnp.int32))
    fs = check_closed_jaxpr(closed, "fixture")
    assert codes(fs) == ["RPR102"] and "int32 -> bfloat16" in fs[0].message


def test_jaxpr_rpr102_found_inside_sub_jaxprs():
    # the walker must recurse through scan/pjit bodies
    def f(x):
        def body(c, t):
            return c, t.astype(jnp.float16)
        return jax.lax.scan(body, jnp.int32(0), x)

    closed = jax.make_jaxpr(f)(jnp.zeros((4, 2), jnp.int32))
    assert "RPR102" in codes(check_closed_jaxpr(closed, "fixture"))


def test_jaxpr_exact_widenings_not_flagged():
    # int32 -> fp32 is the bounds pass's 2^24 tier, not a jaxpr error;
    # int8 -> bf16 is exact
    closed = jax.make_jaxpr(
        lambda x, y: (x.astype(jnp.float32), y.astype(jnp.bfloat16)))(
        jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int8))
    assert check_closed_jaxpr(closed, "fixture") == []


def test_jaxpr_rpr101_float64():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(lambda x: x * 2.0)(
            jnp.zeros((3,), jnp.float64))
    fs = check_closed_jaxpr(closed, "fixture")
    assert "RPR101" in codes(fs)


def test_jaxpr_rpr103_callback_only_in_hot_path():
    def f(x):
        jax.debug.print("step {}", x[0])
        return x + 1

    closed = jax.make_jaxpr(f)(jnp.zeros((3,), jnp.int32))
    assert "RPR103" in codes(check_closed_jaxpr(closed, "fix", hot=True))
    # prefill-grade (hot=False) tolerates callbacks
    assert "RPR103" not in codes(check_closed_jaxpr(closed, "fix", hot=False))


def _tp1_mesh():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]), ("tp",))


def test_jaxpr_rpr104_unproven_fp_psum():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = _tp1_mesh()
    f = shard_map(lambda x: jax.lax.psum(x, "tp"), mesh=mesh,
                  in_specs=P("tp"), out_specs=P())
    closed = jax.make_jaxpr(f)(jnp.ones((4, 4), jnp.float32))
    assert "RPR104" in codes(check_closed_jaxpr(closed, "fixture"))


def test_jaxpr_rpr104_proves_safe_constructions():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = _tp1_mesh()

    def int_psum(x):
        return jax.lax.psum(x, "tp")             # integer adds are exact

    def disjoint_slots(x):
        # the PR 5 row-parallel contract: zeros + per-shard disjoint
        # dynamic_update_slice slots, psum'd (zero-padded fp adds)
        buf = jnp.zeros((4, 8), x.dtype)
        col = jax.lax.axis_index("tp") * 4
        buf = jax.lax.dynamic_update_slice(buf, x, (0, col))
        return jax.lax.psum(buf, "tp")

    ci = jax.make_jaxpr(shard_map(int_psum, mesh=mesh, in_specs=P("tp"),
                                  out_specs=P()))(jnp.ones((4,), jnp.int32))
    cf = jax.make_jaxpr(shard_map(disjoint_slots, mesh=mesh,
                                  in_specs=P("tp"), out_specs=P()))(
        jnp.ones((4, 4), jnp.float32))
    assert check_closed_jaxpr(ci, "fixture") == []
    assert check_closed_jaxpr(cf, "fixture") == []


# ---------------------------------------------------------------------------
# bounds (RPR2xx)
# ---------------------------------------------------------------------------

def test_bounds_arithmetic_pins_the_published_limits():
    # W8A8: qmax 127 each -> 16129/term; 2^31 wrap at group 133145
    assert B.max_safe_group(8, 8) == (2**31 - 1) // (127 * 127)
    assert B.fp32_exact_group(8, 8) == 2**24 // (127 * 127)
    g = B.max_safe_group(8, 8)
    assert B.check_group_dot(8, 8, g, "t") != [] or True  # warning tier ok
    assert codes(B.check_group_dot(8, 8, g + 1, "t")) == ["RPR201"]
    # below the fp32-exact limit: totally clean
    assert B.check_group_dot(8, 8, B.fp32_exact_group(8, 8), "t") == []
    # between 2^24 and 2^31: the tolerated warning tier
    fs = B.check_group_dot(8, 8, 2048, "t")
    assert codes(fs) == ["RPR203"] and fs[0].severity == "warning"
    assert codes(B.check_full_k(8, 8, 200_000, "t")) == ["RPR202"]
    assert B.check_full_k(8, 8, 8192, "t") == []


def test_bounds_validators_raise_with_rule_codes():
    with pytest.raises(ValueError, match="RPR201"):
        B.require_group_dot_safe(8, 8, 140_000, where="t")
    with pytest.raises(ValueError, match="RPR202"):
        B.require_full_k_safe(8, 8, 140_000, where="t")
    B.require_group_dot_safe(4, 8, 4096, where="t")      # safe: no raise
    with pytest.raises(ValueError, match="budget_bits"):
        B.require_act_alloc_sane(float("nan"), [8.0], [4, 8])
    with pytest.raises(ValueError, match="non-positive"):
        B.require_act_alloc_sane(100.0, [0.0], [4, 8])
    with pytest.raises(ValueError, match="container range"):
        B.require_act_alloc_sane(100.0, [8.0], [4, 32])


def test_bounds_verify_configs_no_errors_on_registered_archs():
    fs = B.verify_configs(archs=["internlm2_1_8b"])
    assert [f for f in fs if f.severity == "error"] == []
    # the W8 per-channel warning tier is expected to be present
    assert any(f.code == "RPR203" for f in fs)


# ---------------------------------------------------------------------------
# kernel entry points refuse statically-unsafe shapes (satellite a/b)
# ---------------------------------------------------------------------------

def test_int8_matmul_refuses_overflowing_k():
    from repro.kernels import ops
    k = 140_000                                   # 140000 * 127^2 >= 2^31
    x_q = jnp.zeros((2, k), jnp.int8)
    w_q = jnp.zeros((k, 4), jnp.int8)
    with pytest.raises(ValueError, match="RPR202"):
        ops.int8_matmul(x_q, w_q, jnp.ones((2, 1)), jnp.ones((4,)))


def test_qmm_pallas_refuses_bad_shapes_with_diagnostics():
    from repro.kernels.qmm import qmm_pallas
    from repro.qtensor import quantize

    w = quantize(jnp.ones((32, 16)), 4, group_size=8)
    x_q = jnp.zeros((8, 32), jnp.int8)
    xs = jnp.ones((8, 1), jnp.float32)
    with pytest.raises(ValueError, match="does not match k"):
        qmm_pallas(x_q[:, :16], w.data, xs, w.scale, 4, 32, interpret=True)
    with pytest.raises(ValueError, match="do not divide"):
        qmm_pallas(x_q, w.data, xs, w.scale[:3], 4, 32, interpret=True)
    with pytest.raises(ValueError, match="packed payload"):
        qmm_pallas(x_q, w.data[:-1], xs, w.scale, 4, 32, interpret=True)


def test_allocate_act_sites_refuses_insane_problems():
    from repro.core.fit import SensitivityReport
    from repro.core.mpq import allocate_act_sites
    from repro.quant.policy import QuantPolicy

    rep = SensitivityReport(
        weight_traces={}, act_traces={"s0": 1.0}, weight_ranges={},
        act_ranges={"s0": (-1.0, 1.0)}, param_sizes={})
    with pytest.raises(ValueError, match="budget_bits"):
        allocate_act_sites(rep, QuantPolicy(), float("inf"),
                           [["s0"]], [64.0])
    with pytest.raises(ValueError, match="non-positive"):
        allocate_act_sites(rep, QuantPolicy(), 1024.0,
                           [["s0"]], [float("nan")])


# ---------------------------------------------------------------------------
# clean tree (acceptance oracle)
# ---------------------------------------------------------------------------

def test_lint_pass_clean_on_repo_tree():
    from repro.analysis import lint
    fs = lint.run()
    assert [f for f in fs if f.severity == "error"] == [], \
        "\n".join(f.render() for f in fs)


def test_full_cli_clean_on_repo_tree():
    """`python -m repro.analysis --all` must exit 0 on the shipped tree
    (the CLI forces an 8-device host platform, covering the sharded
    shard_map traces)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)                    # CLI sets its own
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--all", "-q"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=1800)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "0 error(s)" in r.stdout


def test_run_all_in_process_reports_env_note_on_small_hosts():
    # in-process (1 CPU device): the sharded targets are skipped with an
    # RPR100 info note, never silently
    rep = run_all(jaxpr=True, bounds=False, lint=False)
    if len(jax.devices()) < 2:
        assert any(f.code == "RPR100" and f.severity == "info"
                   for f in rep.findings)
    assert rep.exit_code() == 0, \
        "\n".join(f.render() for f in rep.errors)
    assert set(RULES) >= {f.code for f in rep.findings}
