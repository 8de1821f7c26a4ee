"""CPU tests of the reduction from traces to per-layer metrics: on traces
recorded on the chip (``bench/fixtures``) every reader of a cell returns a
value and no share of a peak exceeds 100 %; on small synthetic traces the
interval arithmetic, the gap attribution and the byte counts are exact."""
from __future__ import annotations

import math

import pytest

from bench import cells, roofline, run
from bench import trace as tracelib

KIND = "TPU v5 lite"
FIXTURES = {"k20.fixed": "k20.fixed.trace.json.gz",
            "k1024.fading": "k1024.fading.trace.json.gz"}
# kernel calls as the chip's trace names them: XLA placed the stacked
# gradients, scales, noise and result on chip (S(1)); only the gain (and
# one moments output) lie in HBM
SUPERPOSE_K20 = (
    "%ota_superpose.10 = f32[1,55296]{1,0:T(1,128)S(1)} custom-call("
    "f32[20,55296]{1,0:T(8,128)S(1)} %pad.31, f32[20,1]{1,0:T(8,128)S(1)} "
    "%bitcast_multiply_fusion.2, f32[1,55296]{1,0:T(1,128)S(1)} %bitcast.146,"
    " f32[1,1]{1,0:T(1,128)} %bitcast.137), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    "{f32[20,55296]{1,0}, f32[20,1]{1,0}, f32[1,55296]{1,0}, f32[1,1]{1,0}}")
MOMENTS_K20 = (
    "%batched_moments.10 = (f32[20,1,1,128]{3,2,1,0:T(1,128)S(1)}, "
    "f32[20,1,1,128]{3,2,1,0:T(1,128)}) custom-call(f32[20,54,1024]"
    "{2,1,0:T(8,128)S(1)} %reshape.398), custom_call_target="
    '"tpu_custom_call", operand_layout_constraints={f32[20,54,1024]{2,1,0}}')
# the same superpose with every buffer in HBM, types only in the constraints
SUPERPOSE_HBM = (
    "%ota_superpose.1 = f32[1,55296]{1,0:T(1,128)} custom-call(%pad.4, "
    '%copy.1, %bitcast.3, %constant.6), custom_call_target="tpu_custom_call"'
    ", operand_layout_constraints={f32[20,55296]{1,0}, f32[20,1]{1,0}, "
    "f32[1,55296]{1,0}, f32[1,1]{1,0}}, frontend_attributes={}")


def _fixture(name: str) -> dict:
    return tracelib.load(str(cells.HERE / "fixtures" / FIXTURES[name]))


def _run(name: str, trace: dict) -> run.Run:
    return run.Run(cells.resolve(name), KIND,
                   {"task_s": 1.5, "compile_s": 30.0}, 150.0, trace)


@pytest.mark.parametrize("name", list(FIXTURES))
def test_every_reader_reads_the_recorded_trace(name):
    r = _run(name, _fixture(name))
    for m in r.cell.per_layer:
        value = cells.metric_reader(m["name"]).read(r)
        assert value is not None and math.isfinite(value), m["name"]
        if m["unit"] == "%":
            assert 0.0 < value <= 100.0, (m["name"], value)


@pytest.mark.parametrize("name", list(FIXTURES))
def test_kernel_rates_are_taken_per_call(name):
    r = _run(name, _fixture(name))
    for kernel in ("ota_superpose", "batched_moments"):
        calls = tracelib.kernel_calls(r.trace, kernel)
        value = cells.metric_reader(f"{kernel}.gb_per_s").read(r)
        if not calls:
            assert value is None
            continue
        want = (sum(roofline.call_bytes(h) for _, h in calls)
                / sum(d for d, _ in calls))
        assert value == pytest.approx(want)
        # one call's bytes over one call's time: the count of calls, not
        # of the window's rounds, divides the time
        per_call = [roofline.call_bytes(h) / d for d, h in calls]
        assert min(per_call) <= value <= max(per_call)


@pytest.mark.parametrize("name", list(FIXTURES))
def test_recorded_trace_busy_and_breakdown(name):
    trace = _fixture(name)
    busy = tracelib.busy_seconds(trace)
    assert 0.0 < busy < tracelib.window_seconds(trace)
    idle = sum(e - s for s, e in tracelib.idle(trace)) / 1e9
    assert math.isclose(busy + idle, tracelib.window_seconds(trace),
                        rel_tol=1e-9)
    b = tracelib.breakdown(trace)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(t > 0 for _, t in b["device_ops"] + b["idle_gaps"])
    assert tracelib.gaps_between_round_programs(trace)


def test_moments_reader_finds_nothing_in_the_streamed_round():
    r = _run("k1024.fading", _fixture("k1024.fading"))
    assert cells.metric_reader("batched_moments.gb_per_s").read(r) is None


def _synthetic() -> dict:
    # two round programs (0-10, 30-40) with nested ops, a kernel call, and
    # host spans: one request covering 0-45 with a sync span at 12-28
    return {"window": [0, 45], "chips": 1,
            "ops": [["while.1", 0, 10, 0], ["fusion.1", 1, 3, 0],
                    ["ota_superpose.3", 5, 4, 0], ["copy.2", 20, 2, 0],
                    ["while.1", 30, 10, 0]],
            "modules": [["jit_run_chunk", 0, 10], ["jit_small", 20, 2],
                        ["jit_run_chunk", 30, 10]],
            "host": [["bench.request", 0, 45], ["ReadSyncFlag", 12, 16]],
            "hlo": {"ota_superpose.3": SUPERPOSE_K20}}


def test_interval_arithmetic():
    t = _synthetic()
    assert tracelib.busy(t) == [(0, 10), (20, 22), (30, 40)]
    assert tracelib.idle(t) == [(10, 20), (22, 30), (40, 45)]
    assert tracelib.busy_seconds(t) == 22e-9
    assert tracelib.round_program(t) == "jit_run_chunk"
    assert tracelib.gaps_between_round_programs(t) == [18.0]
    assert tracelib.kernel_calls(t, "ota_superpose") == [(4, SUPERPOSE_K20)]
    assert tracelib.kernel_calls(t, "batched_moments") == []


def test_breakdown_counts_self_time_and_names_gaps():
    b = tracelib.breakdown(_synthetic())
    ops = dict(b["device_ops"])
    assert ops["while.1"] == pytest.approx(13e-9)   # 20 - 3 - 4 nested
    assert ops["ota_superpose.3"] == pytest.approx(4e-9)
    assert b["idle_gaps"][0] == ["ReadSyncFlag", pytest.approx(10e-9)]
    assert [n for n, _ in b["idle_gaps"]] == ["ReadSyncFlag", "ReadSyncFlag",
                                              "bench.request"]


def test_trim_keeps_the_first_requests():
    t = _synthetic()
    t["host"].append(["bench.request", 50, 10])
    assert tracelib.trim(t, 1)["window"] == [0, 45]


def test_call_bytes_count_every_operand_and_result_at_padded_shapes():
    # [20, 55296] gradients (N = 55,050 padded to a multiple of 1024),
    # [20] scales, [55296] noise, the gain and the [55296] result, fp32,
    # whether the compiler placed them on chip or in HBM
    want = 4 * (20 * 55296 + 20 + 55296 + 1 + 55296)
    assert roofline.call_bytes(SUPERPOSE_K20) == want
    assert roofline.call_bytes(SUPERPOSE_HBM) == want
    # the [20, 54, 1024] stack in, two [20, 1, 1, 128] lane rows out
    assert roofline.call_bytes(MOMENTS_K20) == 4 * (20 * 54 * 1024
                                                    + 2 * 20 * 128)


def test_kernel_rate_sums_bytes_over_summed_time():
    b = roofline.call_bytes(SUPERPOSE_K20)
    calls = [(1000.0, SUPERPOSE_K20), (3000.0, SUPERPOSE_K20)]
    assert roofline.calls_gb_per_s(calls) == pytest.approx(2 * b / 4000.0)


def test_round_mfu_counts_traced_rounds_over_busy_time():
    r = _run("k20.fixed", _synthetic())
    s, m = r.cell.config["spec"], r.cell.config["model"]
    flops = (roofline.mlp_train_flops_per_sample(
        [m["in_dim"], m["hidden"], m["hidden"], m["num_classes"]])
        * s["num_devices"] * s["batch_size"] * r.cell.traffic["segment_rounds"])
    want = 100.0 * flops / 22e-9 / 197e12     # one request, 22 ns busy
    assert cells.metric_reader("round_mfu").read(r) == pytest.approx(want)


def test_unknown_device_has_no_peaks():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("TPU v9 imaginary")


def test_mlp_training_flops():
    # forward 2*(784*64 + 64*64 + 64*10); weight grads the same; activation
    # grads for the two layers after the input layer
    fwd = 2 * (784 * 64 + 64 * 64 + 64 * 10)
    assert roofline.mlp_train_flops_per_sample([784, 64, 64, 10]) == (
        2 * fwd + 2 * (64 * 64 + 64 * 10))
