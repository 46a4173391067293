"""Jaxpr-level numerics/sharding checker (the RPR1xx rules).

Traces the real serving graphs — engine decode/prefill step functions
over smoke configs in every storage mode (dense fp, packed QTensor with
int8 compute, legacy int8, paged KV, tensor-parallel sharded when the
host exposes enough devices) plus the standalone kernel wrappers — and
walks the jaxprs, recursing into every sub-jaxpr (pjit, scan, cond,
shard_map, custom_vjp), to verify:

  RPR101  no float64 aval anywhere (doubles are outside every contract)
  RPR102  no lossy convert_element_type on an accumulation path: an
          int32 accumulator may only widen to fp32 (exactness of THAT
          cast is the bounds pass's 2^24 tier); int32 -> fp16/bf16
          silently truncates group dots
  RPR103  no host callbacks / device->host transfers in the decode hot
          path (a callback inside the per-step scan serializes the burst)
  RPR104  every psum/all_reduce operand is exactness-safe: an integer
          dtype, or an fp32 value provably built as zeros +
          dynamic_update_slice of disjoint per-shard slots (the PR 5
          row-parallel contract) — anything else reintroduces
          order-dependent float summation across shards

Tracing is abstract (``jax.make_jaxpr``): no kernels execute, so the
pass costs seconds even where the engine itself would need a TPU.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Iterator, List, Optional, Tuple

from repro.analysis.findings import Finding

# device<->host transfers with no public tracing API
_HOST_FEED_PRIMS = {"infeed", "outfeed"}
# structural ops a zeros-rooted buffer may pass through untouched
_STRUCTURAL_PRIMS = {"reshape", "squeeze", "transpose", "broadcast_in_dim",
                     "convert_element_type", "copy", "sharding_constraint"}


def _output_prim(closed) -> str:
    """Name of the primitive producing a traced function's output,
    looking through the shard_map wrapper."""
    jx = closed.jaxpr
    while True:
        eqn = jx.eqns[-1]
        subs = [s for v in eqn.params.values() for s in _sub_jaxprs(v)]
        if not subs:
            return eqn.primitive.name
        jx = subs[0]


@functools.lru_cache(maxsize=None)
def _api_prims() -> Tuple[frozenset, frozenset, frozenset]:
    """(host callbacks, cross-device sums, value-preserving markers) as
    the installed JAX names them, found by tracing the public API that
    does each thing — so a release that renames a primitive (JAX 0.9:
    ``debug_print``, ``psum_invariant``, ``pvary``) is still matched by
    what it does. Tracing is abstract: no device is touched."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import io_callback
    from jax.sharding import AbstractMesh, PartitionSpec as P

    x = jax.ShapeDtypeStruct((4,), jnp.float32)
    out = jax.ShapeDtypeStruct((4,), jnp.float32)
    callbacks = (
        lambda a: (jax.debug.print("{}", a), a)[1],
        lambda a: (jax.debug.callback(lambda _: None, a), a)[1],
        lambda a: jax.pure_callback(lambda b: b, out, a),
        lambda a: io_callback(lambda b: b, out, a),
    )
    # each trace is the callback primitive alone
    host = {e.primitive.name for f in callbacks
            for e in jax.make_jaxpr(f)(x).eqns}

    mesh = AbstractMesh((1,), ("i",))
    reduce_, marker = set(), set()
    for check_vma in (True, False):
        def smap(body, out_spec=P()):
            return jax.make_jaxpr(jax.shard_map(
                body, mesh=mesh, in_specs=P("i"), out_specs=out_spec,
                check_vma=check_vma))(x)
        # rpr-ok: RPR002 traced abstractly only, to learn the primitive's name
        reduce_.add(_output_prim(smap(lambda a: jax.lax.psum(a, "i"))))
        reduce_.add(_output_prim(smap(
            # rpr-ok: RPR002 traced abstractly only, to learn the primitive's name
            lambda a: jax.lax.psum_scatter(a, "i", tiled=True), P("i"))))
        if check_vma:
            marker.add(_output_prim(smap(
                lambda a: jax.lax.pcast(jnp.zeros(a.shape, a.dtype), "i",
                                        to="varying"), P("i"))))
    return (frozenset(host | _HOST_FEED_PRIMS), frozenset(reduce_),
            frozenset(marker))


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _sub_jaxprs(value) -> Iterator:
    """Yield every (open) jaxpr buried in an eqn-param value."""
    if hasattr(value, "jaxpr") and hasattr(value, "consts"):
        yield value.jaxpr                       # ClosedJaxpr
    elif hasattr(value, "eqns") and hasattr(value, "invars"):
        yield value                             # Jaxpr
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)
    elif isinstance(value, dict):
        for v in value.values():
            yield from _sub_jaxprs(v)


def iter_eqns(jaxpr) -> Iterator[Tuple[object, object]]:
    """(enclosing jaxpr, eqn) pairs, depth-first through all sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield jaxpr, eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def _producers(jaxpr) -> Dict[object, object]:
    """var -> producing eqn, within one (non-nested) jaxpr scope."""
    out: Dict[object, object] = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out[v] = eqn
    return out


def _is_literal_zero(var) -> bool:
    val = getattr(var, "val", None)
    if val is None:
        return False
    try:
        return float(val) == 0.0
    except (TypeError, ValueError):
        return False


def _zero_rooted(var, producers: Dict, depth: int = 0) -> bool:
    """True if ``var`` is provably a zeros buffer updated only through
    ``dynamic_update_slice`` — the disjoint-slot construction whose psum
    is exact by the row-parallel contract."""
    if depth > 64:
        return False
    if _is_literal_zero(var):
        return True
    eqn = producers.get(var)
    if eqn is None:
        return False                      # crosses a scope boundary: fail
    name = eqn.primitive.name
    if name == "dynamic_update_slice":
        # updates land in disjoint slots per the contract; the BASE must
        # trace back to literal zeros
        return _zero_rooted(eqn.invars[0], producers, depth + 1)
    if name in ("broadcast_in_dim", "fill"):
        return _is_literal_zero(eqn.invars[0]) or \
            _zero_rooted(eqn.invars[0], producers, depth + 1)
    if name in _STRUCTURAL_PRIMS or name in _api_prims()[2]:
        return _zero_rooted(eqn.invars[0], producers, depth + 1)
    if name in ("mul",):                  # 0 * x == 0 (finite int grids)
        return any(_zero_rooted(v, producers, depth + 1)
                   for v in eqn.invars)
    return False


def _dtype_of(var):
    aval = getattr(var, "aval", None)
    return getattr(aval, "dtype", None)


# ---------------------------------------------------------------------------
# per-trace checks
# ---------------------------------------------------------------------------

def check_closed_jaxpr(closed, target: str, hot: bool = False
                       ) -> List[Finding]:
    """Walk one traced computation and emit RPR1xx findings."""
    import numpy as np

    findings: List[Finding] = []
    prod_cache: Dict[int, Dict] = {}
    seen_f64 = False

    def is_f64(var) -> bool:
        dt = _dtype_of(var)
        return dt is not None and dt == np.dtype("float64")

    callback_prims, reduce_prims, _ = _api_prims()
    top = closed.jaxpr
    for var in top.invars:
        if is_f64(var) and not seen_f64:
            seen_f64 = True
            findings.append(Finding(
                "RPR101", "error", target,
                "float64 input to the traced computation"))

    for jx, eqn in iter_eqns(top):
        name = eqn.primitive.name
        if not seen_f64:
            for v in eqn.outvars:
                if is_f64(v):
                    seen_f64 = True
                    findings.append(Finding(
                        "RPR101", "error", target,
                        f"float64 aval produced by `{name}` — doubles are "
                        "outside every exactness contract (and TPUs "
                        "emulate them at ~100x cost)"))
                    break
        if name == "convert_element_type":
            src = _dtype_of(eqn.invars[0])
            dst = eqn.params.get("new_dtype")
            if src is not None and dst is not None:
                src, dst = np.dtype(src), np.dtype(dst)
                if src == np.dtype("int32") and \
                        dst in (np.dtype("float16"), np.dtype("bfloat16")):
                    findings.append(Finding(
                        "RPR102", "error", target,
                        f"lossy cast int32 -> {dst.name}: a group/K "
                        "accumulator truncated before the scale fold "
                        "(int32 must widen to fp32; fold first, downcast "
                        "after)"))
        if hot and (name in callback_prims or "callback" in name):
            findings.append(Finding(
                "RPR103", "error", target,
                f"host callback `{name}` in the decode hot path — every "
                "burst step would synchronize device -> host"))
        if name in reduce_prims:
            for v in eqn.invars:
                dt = _dtype_of(v)
                if dt is None:
                    continue
                if np.issubdtype(dt, np.integer) or dt == np.dtype("bool"):
                    continue              # integer adds are exact
                if dt == np.dtype("float32"):
                    prods = prod_cache.setdefault(id(jx), _producers(jx))
                    if _zero_rooted(v, prods):
                        continue          # zeros + disjoint DUS slots
                findings.append(Finding(
                    "RPR104", "error", target,
                    f"`{name}` over a {np.dtype(dt).name} operand that is "
                    "not provably exact: reduce int32, or build the "
                    "operand as zeros + disjoint dynamic_update_slice "
                    "slots (row-parallel contract) so the float adds are "
                    "zero-padded"))
    return findings


# ---------------------------------------------------------------------------
# trace targets
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TraceTarget:
    name: str
    thunk: Callable[[], object]     # () -> ClosedJaxpr
    hot: bool = False               # held to the decode hot-path rules


def _kernel_targets() -> List[TraceTarget]:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.qtensor import quantize

    def qmm_jaxpr():
        x_q = jnp.zeros((8, 32), jnp.int8)
        w = quantize(jnp.ones((32, 16)), 4, group_size=8)
        xs = jnp.ones((8, 1), jnp.float32)
        return jax.make_jaxpr(lambda a, qt, s: ops.qmm(a, qt, s))(x_q, w, xs)

    def int8_jaxpr():
        x_q = jnp.zeros((8, 32), jnp.int8)
        w_q = jnp.zeros((32, 16), jnp.int8)
        xs = jnp.ones((8, 1), jnp.float32)
        ws = jnp.ones((16,), jnp.float32)
        return jax.make_jaxpr(ops.int8_matmul)(x_q, w_q, xs, ws)

    def paged_jaxpr():
        q = jnp.zeros((2, 1, 4, 16), jnp.float32)
        kp = jnp.zeros((6, 2, 4, 16), jnp.float32)    # (P, KV, page, Dh)
        table = jnp.zeros((2, 3), jnp.int32)
        pos = jnp.zeros((2,), jnp.int32)
        return jax.make_jaxpr(
            lambda *a: ops.paged_attention(*a))(q, kp, kp, table, pos)

    return [
        TraceTarget("kernels.ops.qmm[W4A8,g=8]", qmm_jaxpr, hot=True),
        TraceTarget("kernels.ops.int8_matmul[W8A8]", int8_jaxpr, hot=True),
        TraceTarget("kernels.ops.paged_attention[fp]", paged_jaxpr, hot=True),
    ]


def _smoke_engine(variant: str, mesh=None):
    """Build a smoke-scale Engine in one of the serving storage modes."""
    import dataclasses as dc

    import jax

    from repro.configs import smoke_config
    from repro.models import init_params
    from repro.serve import (
        Engine, EngineConfig, quantize_params, quantize_params_int8)

    moe = variant.startswith("moe")
    spec = variant.startswith("spec")
    cfg = smoke_config("deepseek_moe_16b" if moe else "internlm2_1_8b")
    ecfg = dict(max_slots=2, max_len=32, max_new_tokens=8,
                prefill_chunk=8, decode_burst=4)
    scales = None
    if variant == "dense":
        params = init_params(cfg, jax.random.key(0))
    else:
        cfg = dc.replace(cfg, scan_layers=False)
        params = init_params(cfg, jax.random.key(0))
        if variant in ("qtensor", "paged", "sharded", "obs", "trace") \
                or moe or spec:
            params, scales = quantize_params(params, 4, group_size=8)
            ecfg["int8_compute"] = True
        elif variant == "int8":
            params, scales = quantize_params_int8(params, 8)
            ecfg["int8_compute"] = True
        if variant in ("paged", "sharded", "obs", "trace", "spec-paged"):
            ecfg.update(kv_cache="paged", page_size=8)
        if spec:
            # draft/verify loop: W4 serving tree narrowed to a W3 draft,
            # low-bit draft KV lane (int8 dense / packed int4 paged)
            from repro.serve import SpecConfig
            ecfg["spec"] = SpecConfig(
                k=3, draft_bits=3,
                draft_kv_bits=4 if variant == "spec-paged" else 8)
        if variant == "moe-dense":
            # the per-expert qmm loop the grouped kernel is pinned against
            ecfg["moe_dispatch"] = "dense"
        if variant in ("sharded", "moe-ep"):
            ecfg["mesh"] = mesh
        if variant == "obs":
            # device counters accumulate INSIDE the decode scan; the hot
            # decode target below proves the stats graph adds no host
            # callbacks / transfers (RPR103) — drains happen outside it
            from repro.obs import ObsConfig
            ecfg["obs"] = ObsConfig(device_metrics=True)
        if variant == "trace":
            # tracing + counters on: the phase spans and their timing are
            # host-side around the audited syncs — the traced decode /
            # prefill graphs must stay identical to the obs variant (no
            # host callbacks, RPR103)
            from repro.obs import ObsConfig
            ecfg["obs"] = ObsConfig(trace=True, device_metrics=True)
    return Engine(params, cfg, EngineConfig(**ecfg), scales=scales)


def _engine_target_pair(variant: str, mesh=None) -> List[TraceTarget]:
    import functools as ft

    import jax
    import jax.numpy as jnp

    from repro.models.decode import init_decode_state

    def decode_jaxpr(variant=variant, mesh=mesh):
        eng = _smoke_engine(variant, mesh)
        state = eng._fresh_state()
        tok = eng._put_repl(jnp.zeros(eng._tok_shape, jnp.int32))
        out = eng._put_repl(jnp.zeros(eng._out_shape, jnp.int32))
        slots = eng._fresh_slot_table()
        ctr = eng._fresh_counters()
        if variant.startswith("spec"):
            # the speculative dispatch: k draft invocations (2-token
            # catch-up + k-1 steps) + one fused multi-token verify +
            # coupled accept, all in one graph — the same hot-path
            # rules apply (the only host transfer is the audited
            # n_emit fetch OUTSIDE this function)
            dstate = eng._fresh_draft_state()
            ptok = eng._put_repl(jnp.zeros(eng._tok_shape, jnp.int32))
            step = ft.partial(eng._spec_step, k=eng._spec.k,
                              mode="greedy", stats=bool(ctr))
            return jax.make_jaxpr(
                lambda *a: step(*a))(eng.params, eng.scales,
                                     eng._draft_params, state, dstate,
                                     ptok, tok, out, slots, ctr)
        # stats=True traces the WORST-case burst flavor (sampled
        # element-wise clip stats included) — the hot-path audit must
        # hold for the heaviest graph the cadence can dispatch
        step = ft.partial(eng._engine_step, steps=2, mode="greedy",
                          stats=bool(ctr))
        return jax.make_jaxpr(
            lambda *a: step(*a))(eng.params, eng.scales, state, tok, out,
                                 slots, ctr)

    def prefill_jaxpr(variant=variant, mesh=mesh):
        eng = _smoke_engine(variant, mesh)
        ps = eng._put_repl(
            init_decode_state(eng.cfg, 1, eng.ecfg.max_len))
        chunk = jnp.zeros((1, eng.ecfg.prefill_chunk), jnp.int32)
        return jax.make_jaxpr(
            lambda *a: eng._prefill(*a))(eng.params, eng.scales, ps, chunk)

    return [
        TraceTarget(f"engine[{variant}].decode_step", decode_jaxpr, hot=True),
        TraceTarget(f"engine[{variant}].prefill", prefill_jaxpr, hot=False),
    ]


def collect_targets(sharded: Optional[bool] = None) -> Tuple[
        List[TraceTarget], List[Finding]]:
    """All trace targets + environment notes (skipped sharded paths)."""
    import jax

    notes: List[Finding] = []
    targets = _kernel_targets()
    # moe-grouped/moe-dense: the packed MoE engine in both dispatch modes
    # (one grouped ragged kernel per projection vs the per-expert qmm
    # loop it replaced — both graphs must satisfy the same hot-path and
    # exactness rules, since either can serve as the parity oracle)
    # spec/spec-paged: the speculative draft/verify dispatch — both KV
    # lane shapes (dense int8 draft cache, paged packed-int4 draft pools)
    for variant in ("dense", "qtensor", "int8", "paged", "obs", "trace",
                    "moe-grouped", "moe-dense", "spec", "spec-paged"):
        targets.extend(_engine_target_pair(variant))
    want_sharded = (len(jax.devices()) >= 2) if sharded is None else sharded
    if want_sharded:
        from repro.launch.mesh import make_tp_mesh
        targets.extend(_engine_target_pair("sharded", mesh=make_tp_mesh(2)))
        # expert-parallel MoE: expert stacks sharded over the tp mesh —
        # RPR104 must prove the ep combine's psum exact (zeros + disjoint
        # per-expert dynamic_update_slice slots)
        targets.extend(_engine_target_pair("moe-ep", mesh=make_tp_mesh(2)))
    else:
        notes.append(Finding(
            "RPR100", "info", "engine[sharded]",
            f"sharded + expert-parallel traces skipped: host exposes "
            f"{len(jax.devices())} device(s); run `python -m repro.analysis` "
            "(the CLI forces an 8-device host platform) to cover the "
            "shard_map paths"))
    return targets, notes


def run(sharded: Optional[bool] = None,
        dump_dir: Optional[str] = None) -> List[Finding]:
    """Trace every target and check it; optionally dump jaxprs for CI
    artifact caching/inspection."""
    from pathlib import Path

    targets, findings = collect_targets(sharded)
    for t in targets:
        try:
            closed = t.thunk()
        except Exception as e:  # noqa: BLE001 - surface as a finding
            findings.append(Finding(
                "RPR100", "error", t.name,
                f"trace failed: {type(e).__name__}: {e}"))
            continue
        if dump_dir:
            p = Path(dump_dir)
            p.mkdir(parents=True, exist_ok=True)
            safe = t.name.replace("/", "_").replace("[", ".").replace(
                "]", "")
            (p / f"{safe}.jaxpr.txt").write_text(str(closed))
        findings.extend(check_closed_jaxpr(closed, t.name, hot=t.hot))
    return findings
