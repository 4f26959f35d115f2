"""Trace reduction and the operation/byte counts, on synthetic traces
with known intervals and on a small trace recorded on a TPU v5e."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import flops, trace

DATA = Path(__file__).resolve().parent / "data"


def _events(events, names):
    ids = {n: i + 1 for i, n in enumerate(names)}
    evs = "".join(f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * 1e3)}"
                  f" duration_ps: {int(d * 1e3)} }}\n" for n, s, d in events)
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                   for n, i in ids.items())
    return evs, meta


def _plane(pid, name, lines):
    """lines: {line name: [(event name, start_ns, duration_ns)]}"""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    body, meta = "", ""
    for lid, (lname, evs) in enumerate(lines.items(), 1):
        e, meta = _events(evs, names)
        body += f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n{e}}}\n'
    return f'planes {{ id: {pid} name: "{name}"\n{body}{meta}}}\n'


def _space(*planes):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto("".join(planes))


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    data = _space(
        _plane(1, "/device:TPU:0", {
            "XLA Ops": [("%a = f32[] add(x, y)", 0, 100),
                        ("%b = f32[] mul(x, y)", 50, 100),  # overlap: 150
                        ("k", 400, 50), ("k", 900, 200)],  # last one clipped
            "XLA Modules": [("jit_step", 0, 160), ("jit_step", 400, 60)]}),
        _plane(2, "/host:CPU", {"python": [
            ("bench.window", 0, 1000), ("bench.generate", 150, 250),
            ("PjitFunction(step)", 460, 430)]}))
    s = trace.reduce_data(data)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx((150 + 50 + 100) * 1e-9)
    assert s.idle_share == pytest.approx(0.7)
    assert s.ops_s["k"] == pytest.approx(150e-9)
    assert s.ops_s["a"] == pytest.approx(100e-9)
    assert s.modules_s["jit_step"] == pytest.approx(220e-9)
    assert s.module_runs["jit_step"] == 2
    assert s.span_s("generate") == pytest.approx(250e-9)
    # gaps: 150-400 under bench.generate, 450-900 mostly under the call
    assert s.idle_by_host["generate"] == pytest.approx(250e-9)
    assert s.idle_by_host["outside spans / PjitFunction(step)"] \
        == pytest.approx(450e-9)
    assert trace.breakdown(s)["device_ops"][0] == ["k", pytest.approx(150e-9)]


def test_busy_is_averaged_over_chips():
    ops = {"XLA Ops": [("a", 0, 400)]}
    data = _space(_plane(1, "/device:TPU:0", ops),
                  _plane(2, "/device:TPU:1", {"XLA Ops": [("a", 0, 200)]}),
                  _plane(3, "/host:CPU", {"python": [("bench.window", 0, 800)]}))
    s = trace.reduce_data(data)
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx(300e-9)


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e: three runs of a jitted matmul
    and three of the aging kernel, each under its own span."""
    s = trace.reduce(str(DATA / "small.xplane.pb"))
    assert s.n_devices == 1
    assert len(s.spans["matmul"]) == 3 and len(s.spans["aging"]) == 3
    assert 0 < s.busy_s < s.window_s
    assert sum(s.ops_s.values()) >= s.busy_s * 0.999
    assert any(k.startswith("aging_update") for k in s.ops_s)
    assert sum(s.module_runs.values()) >= 5


def test_fleet_scan_counts():
    shape = flops.FleetShape(n_prompt=45, n_token=155, num_cores=40,
                             n_macro=3600, steps_per_period=4)
    micro = 20 * 45 + 35 * 155 + 20 * 200 + 3 * 8000
    macro = 5 * 8000 + 32 * 8000 + 4 * 8000 * 5.321928    # log2(40)
    assert flops.fleet_scan_flops(shape) == pytest.approx(
        3600 * (4 * micro + macro), rel=1e-6)
    per_call, calls = flops.aging_update_bytes(shape)
    assert per_call == 5 * 8192 * 4 and calls == 7200


MINICPM3 = {"hidden_size": 2560, "num_hidden_layers": 62,
            "num_attention_heads": 40, "intermediate_size": 6400,
            "vocab_size": 73448, "q_lora_rank": 768, "kv_lora_rank": 256,
            "qk_nope_head_dim": 64, "qk_rope_head_dim": 32, "v_head_dim": 64}


def test_mla_counts():
    # non-embedding parameters per layer of MiniCPM3-4B, counted by hand
    per_layer = (2560 * 768 + 768 * 40 * 96 + 2560 * 288 + 256 * 40 * 128
                 + 40 * 64 * 2560 + 3 * 2560 * 6400)
    assert flops.mla_token_flops(MINICPM3) == 2 * per_layer
    assert flops.mla_pair_flops(MINICPM3) == 2 * 40 * 160
    s = 1024
    want = 62 * (s * 2 * per_layer + s * (s + 1) / 2 * 12800) \
        + 2 * 2560 * 73448
    assert flops.prefill_flops(MINICPM3, s) == pytest.approx(want)
    # one decode token of a 1,024-token context
    want = 62 * (2 * per_layer + 1025 * 12800) + 2 * 2560 * 73448
    assert flops.decode_flops(MINICPM3, [1025]) == pytest.approx(want)
    # weights once, plus 288 bf16 latent values per layer per position
    assert flops.decode_bytes(MINICPM3, [1025, 10], 8.5e9) == \
        pytest.approx(8.5e9 + 2 * 288 * 62 * 1035)


def test_peaks_are_keyed_by_device_kind():
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
