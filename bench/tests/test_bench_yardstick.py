"""The benchmark's yardstick on the CPU: trace reduction, work counts,
the peaks table, the traffic generator and finding parts by name."""
import json

import numpy as np
import pytest

import _paths  # noqa: F401
from yardstick import peaks, registry, traffic, work
from yardstick import trace as tr

MIX = {"kind": "serve", "arrivals": {"rate_per_s": 0.5},
       "prompt_tokens": {"dist": "lognormal", "mean": 70, "sigma_log": 1.0,
                         "min": 4, "max": 1024},
       "output_tokens": {"dist": "lognormal", "mean": 215, "sigma_log": 0.8,
                         "min": 8, "max": 1024}}


def test_reduce_events_busy_union_idle_and_kernels():
    # window [0, 100] ns; ops overlap on one device; a host dispatch spans
    # the first gap and a transfer the second
    host = [(tr.WINDOW, 0, 100), ("PjitFunction(step)", 0, 30),
            ("TransferToDevice", 45, 60)]
    dev = [("qmm_kernel", 10, 30), ("fusion.1", 25, 40), ("qmm_kernel", 60, 70),
           ("outside", 120, 130)]
    r = tr.reduce_events(host, [dev])
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(40e-9)           # [10,40] + [60,70]
    assert r["op_s"]["qmm_kernel"] == pytest.approx(30e-9)
    assert "outside" not in r["op_s"]
    assert r["op_n"]["qmm_kernel"] == 2
    # gaps [0,10] and [40,60] and [70,100]
    assert r["idle_gaps"]["PjitFunction(step)"] == pytest.approx(10e-9)
    assert r["idle_gaps"]["TransferToDevice"] == pytest.approx(20e-9)
    assert r["idle_gaps"]["(none)"] == pytest.approx(30e-9)


def test_reduce_events_averages_busy_over_chips():
    host = [(tr.WINDOW, 0, 100)]
    r = tr.reduce_events(host, [[("a", 0, 100)], [("a", 0, 50)]])
    assert r["busy_s"] == pytest.approx(75e-9)
    assert r["devices"] == 2


def test_reduce_events_needs_window_and_device_ops():
    with pytest.raises(ValueError):
        tr.reduce_events([], [[("a", 0, 1)]])
    with pytest.raises(ValueError):
        tr.reduce_events([(tr.WINDOW, 0, 1)], [[]])


@pytest.mark.parametrize("bits", [8, 6, 4, 3])
def test_qmm_weight_bytes_match_storage_summary(bits):
    import jax
    from repro.qtensor import quantize, storage_summary
    k, n = 256, 384
    w = jax.random.normal(jax.random.key(bits), (k, n), jax.numpy.float32)
    qt = quantize(w, bits, group_size=128)
    assert work.qmm_weight_bytes(k, n, bits, 128) == \
        storage_summary([qt])["packed_bytes"]


def test_serve_flops_count_rows_and_context():
    dims = {"num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 2,
            "num_key_value_heads": 1, "head_dim": 64, "intermediate_size": 256,
            "vocab_size": 500, "hidden_act": "silu"}
    w = work.serve_flops(dims, [(5, 3), (40, 1)])
    # rows: prompts, plus outputs but the last; positions 0..6 and 0..39
    assert w["rows"] == 5 + 2 + 40
    ctx = 7 * 8 / 2 + 40 * 41 / 2
    assert w["model_flops"] == 2 * 47 * work.matmul_params(dims) + 4 * ctx * 2 * 64 * 2
    # the head's rows are the vocabulary padded to 16
    assert work.matrices(dims)["head"] == (128, 512)


def test_peaks_known_and_unknown_kind():
    assert peaks.peaks_for("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v99")


def test_traffic_is_deterministic_and_seeds_share_the_work():
    a = traffic.requests(MIX, 51, 1000, 2 ** 31 + 7)
    b = traffic.requests(MIX, 51, 1000, 2 ** 31 + 7)
    c = traffic.requests(MIX, 51, 1000, 12)
    assert len(a) == int(0.5 * 51)
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s and x.max_new_tokens == y.max_new_tokens
        np.testing.assert_array_equal(x.prompt, y.prompt)
    # another seed: the same sizes and arrivals, other tokens
    assert [(x.arrival_s, len(x.prompt), x.max_new_tokens) for x in a] == \
        [(x.arrival_s, len(x.prompt), x.max_new_tokens) for x in c]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    assert all(x.arrival_s <= 51 for x in a) and a[0].arrival_s == 0.0


def test_traffic_lengths_follow_the_mix():
    a = traffic.requests(dict(MIX, arrivals={"rate_per_s": 20.0}), 50, 1000, 3)
    plens = np.array([len(x.prompt) for x in a])
    glens = np.array([x.max_new_tokens for x in a])
    assert plens.min() >= 4 and plens.max() <= 1024
    assert abs(plens.mean() - 70) < 5 and abs(glens.mean() - 215) < 10


def test_parts_added_as_files_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "new_model.json").write_text(json.dumps({"name": "new_model"}))
    (bench / "traffic" / "new_mix.json").write_text(json.dumps({"kind": "serve"}))
    (bench / "metrics" / "new_metric.x.py").write_text(
        "LAYER = 'kernels'\nUNIT = '%'\nSOURCE = 'device_trace'\n"
        "MOVES = 'ttft_p95_s'\n\ndef read(rec):\n    return rec.get('v')\n")
    assert registry.load_config("new_model", bench)["name"] == "new_model"
    assert registry.load_traffic("new_mix", bench)["kind"] == "serve"
    m = registry.load_metric("new_metric.x", bench)
    assert m.read({"v": 3.0}) == 3.0 and m.read({}) is None
    bj = {"per_layer": [{"name": "a"}, {"name": "b", "workloads": ["c1"]}]}
    assert [m["name"] for m in registry.metrics_for(bj, "per_layer", "c2")] == ["a"]


def test_every_metric_of_the_benchmark_has_its_reader():
    bj = registry.load_benchmark()
    for m in bj["per_layer"]:
        mod = registry.load_metric(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == \
            (m["layer"], m["unit"], m["source"], m["moves"])
    for w in bj["workloads"]:
        conf = registry.load_config(w["config"])
        assert conf["name"] == w["config"]
        assert registry.load_traffic(w["traffic"])["kind"] in ("serve",)


def test_reduce_recorded_v5e_trace():
    """A trace recorded on one v5e (``bench/tools/record_trace.py``): three
    qmm calls and three fused XLA ops inside the window annotation, each
    dispatched from the host after a 2 ms sleep."""
    from pathlib import Path
    path = Path(__file__).parent / "data" / "small_v5e.xplane.pb"
    r = tr.reduce_trace(str(path))
    assert r["devices"] == 1
    assert r["op_n"] == {"qmm_pallas": 3, "multiply_add_fusion": 3, "copy": 3}
    assert 1.5e-4 < r["op_s"]["qmm_pallas"] < 2e-4
    assert r["busy_s"] == pytest.approx(sum(r["op_s"].values()), rel=1e-6)
    assert 0.012 < r["window_s"] < 0.015
    idle = r["window_s"] - r["busy_s"]
    assert sum(r["idle_gaps"].values()) == pytest.approx(idle, rel=1e-6)
    # the host slept (marking nothing) through most of the idle time, and
    # dispatched the jitted calls through the rest
    assert max(r["idle_gaps"], key=r["idle_gaps"].get) == "(none)"
    assert r["idle_gaps"]["(none)"] > 0.005
    assert any(k.startswith("PjitFunction") for k in r["idle_gaps"])


def test_op_base_names():
    assert tr.op_base("%qmm_pallas.1929 = f32[1,256000]{1,0} custom-call(s8[1,3072] %x)") \
        == "qmm_pallas"
    assert tr.op_base("%while.3 = (s32[]) while(...)") == "while"
    assert tr.op_base("fusion") == "fusion"


def test_traced_work_of_the_recorded_qmm_calls():
    from pathlib import Path
    path = Path(__file__).parent / "data" / "small_v5e.xplane.pb"
    r = tr.reduce_trace(str(path), keep=work.KERNELS)
    r["at_s"] = 0.0
    dims = {"num_hidden_layers": 1, "hidden_size": 2048, "num_attention_heads": 16,
            "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 8192,
            "vocab_size": 1000, "hidden_act": "silu"}
    eng = {"group_size": 128, "page_size": 16, "kv_bits": 8, "max_slots": 64}
    w = work.traced_serve_work(r, [], dims, eng)
    # three W4 (2048, 2048) calls at 8 rows: payload + scales + activations
    one = work.qmm_weight_bytes(2048, 2048, 4, 128) + 8 * (2048 + 4) + 4 * 8 * 2048
    assert w["qmm_n"] == 3 and w["qmm_bytes"] == 3 * one
    assert w["qmm_ops"] == 3 * 2 * 8 * 2048 * 2048
    assert w["qmm_s"] == pytest.approx(r["op_s"]["qmm_pallas"])
    assert w["attn_n"] == 0


def test_live_requests_and_their_context():
    reqs = [{"first": 1.0, "finished": 3.0, "prompt": 10, "output": 5},
            {"first": 2.5, "finished": 4.0, "prompt": 7, "output": 2},
            {"first": None, "finished": None, "prompt": 3, "output": 1}]
    assert work.live(reqs, 2.0) == [(10, 13.0)]
    assert [p for p, _ in work.live(reqs, 2.9)] == [10, 7]
    assert work.live(reqs, 3.0) == [(7, pytest.approx(8 + 1 / 3))]
