"""The trace reduction on a small recorded trace whose shares can be worked
out on paper: window 10 us; the decode burst's ``while`` busy 1.5-4.5 us, the
mixed quantum busy 6.5-9.0 us; a synchronous all-gather 8.5-9.0 us and an
asynchronous one 7.0-9.5 us, of which 7.0-8.5 us lies under a fusion."""

import json
import os

import pytest

from benchmarks.lib import manifest as mf, trace

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = json.load(open(os.path.join(HERE, "recorded_trace_v5e.json")))
US = 1e-6


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_trace(TRACE)


@pytest.mark.parametrize("a,b,want", [
    ([(0, 2), (1, 3), (5, 6)], None, [(0, 3), (5, 6)]),
    ([(0, 3), (5, 6)], [(0.5, 1), (2.5, 5.5)], [(0, 0.5), (1, 2.5), (5.5, 6)]),
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
])
def test_interval_arithmetic(a, b, want):
    got = trace.merge(a) if b is None else trace.subtract(trace.merge(a), trace.merge(b))
    assert got == want


def test_clip_and_total():
    assert trace.clip([(0, 3), (5, 6)], 1, 5.5) == [(1, 3), (5, 5.5)]
    assert trace.total([(1, 3), (5, 5.5)]) == 2.5


@pytest.mark.parametrize("name,own,opcode,collective", [
    ("%fusion.710 = (bf16[8]{0}, bf16[8]{0}) fusion(bf16[8]{0} %all-gather.2)", "fusion.710", "fusion", False),
    ("%all-gather-start.3 = (bf16[2]{0}, bf16[8]{0}) all-gather-start(bf16[2]{0} %p)", "all-gather-start.3", "all-gather-start", True),
    ("%reduce-scatter.1 = f32[4]{0} reduce-scatter(f32[16]{0} %g), channel_id=3", "reduce-scatter.1", "reduce-scatter", True),
    ("jit_fused(123)", "jit_fused(123)", "", False),
])
def test_an_operation_is_named_by_its_own_hlo_name_not_by_its_operands(name, own, opcode, collective):
    assert trace.parse_op(name)[:2] == (own, opcode)
    assert trace.is_collective(name) is collective


def test_window_busy_and_idle(reduced):
    assert reduced["window_s"] == pytest.approx(10 * US)           # the driver's bench/window span
    assert reduced["busy_s"] == pytest.approx(5.5 * US)            # 3.0 (the while) + 2.5
    assert reduced["idle_share"] == pytest.approx(0.45)
    assert [n for n, _, _ in reduced["devices"][0]["modules"]] == ["jit_fused(6201523710541453722)",
                                                                  "jit_fused(6201523710541453723)"]


def test_exposed_collectives(reduced):
    dev = reduced["devices"][0]
    assert dev["collective_s"] == pytest.approx(2.5 * US)          # 7.0-9.5, the two merged
    assert dev["collective_exposed_s"] == pytest.approx(1.0 * US)  # 8.5-9.5: nothing else runs
    assert reduced["collective_exposed_share"] == pytest.approx(0.10)


def test_self_time_leaves_out_what_is_nested(reduced):
    ops = reduced["devices"][0]["ops"]
    by = lambda needle: sum(v for k, v in ops.items() if needle in k)
    assert by("paged_decode") == pytest.approx(0.8 * US)
    assert by("convolution_multiply_fusion") == pytest.approx(1.2 * US)
    assert by("while") == pytest.approx(1.0 * US)                  # 3.0 less 8 nested operations
    assert trace.ops_matching(reduced, "paged_decode") == (pytest.approx(0.8 * US), 4)


def test_spans_and_the_busy_time_inside_them(reduced):
    spans = [s for s in reduced["spans"] if s[0] == "bench/run_fused"]
    assert [s[3]["what"].split()[0] for s in spans] == ["decode", "mixed"]
    assert all(s[0].startswith("bench/") for s in reduced["spans"])
    assert trace.busy_inside(reduced, spans[0][1], spans[0][2]) == pytest.approx(3.0 * US)


def test_breakdown_names_ops_and_gaps(reduced):
    b = trace.breakdown(reduced)
    assert len(b["device_ops"]) <= 10 and b["device_ops"][0][0].startswith("fusion fusion")
    gaps = dict(b["idle_gaps"])
    assert gaps["in bench/run_fused[decode dec16 pre0x0 steps4]"] == pytest.approx(1.0 * US)   # 1.0-1.5, 4.5-5.0
    assert gaps["in bench/run_fused[mixed dec16 pre1x300 steps1]"] == pytest.approx(1.5 * US)  # 6.0-6.5, 9.0-10.0
    assert gaps["between bench/run_fused[decode dec16 pre0x0 steps4] and bench/run_fused[mixed dec16 pre1x300 steps1]"] \
        == pytest.approx(1.0 * US)
    assert sum(gaps.values()) == pytest.approx(reduced["window_s"] - reduced["busy_s"])


@pytest.mark.parametrize("metric,want", [
    ("decode_step_device_ms", 3.0 * US / 4 * 1e3),                 # the burst's busy time over its 4 steps
    ("attn_share_of_busy.serve", 100 * 0.8 / 5.5),
    ("collective_exposed_share.train", 10.0),
])
def test_metric_readers_on_the_recorded_trace(reduced, metric, want):
    assert mf.metric_module(metric).read({"reduced": reduced}) == pytest.approx(want)


def test_a_reader_that_finds_nothing_returns_nothing(reduced):
    record = {"reduced": reduced, "published": mf.load_json(os.path.join(mf.BENCH, "configs", "olmo-1b.json")),
              "train": {"micro_batch": 2, "seq_len": 2048}, "device": {"kind": "TPU v5 lite", "count": 4}}
    assert mf.metric_module("flash_attention_roofline").read(record) is None  # no flash kernel in a serving trace
    assert trace.reduce_trace({"planes": []})["devices"] == {}


def test_flash_roofline_from_the_names_and_counts_a_v5e_trace_holds():
    """The training step's Mosaic kernels as the chip names them (my chip run,
    PR 24: ``%shard_map.1856 = (bf16[32,2048,128]..., f32[32,2048,128]...)
    custom-call(...), custom_call_target="tpu_custom_call"``), 3 calls a layer
    and step, and the step program's executions: 158 steps and 4.098 s of
    kernels on a device gave 32.3% there."""
    events, modules = [], []
    for step in range(4):
        t0 = step * 1000
        modules.append(["jit_fused_step(15379128562169708908)", t0, 990, {}])
        for k, typ in enumerate(["(bf16[32,2048,128]{2,1,0:T(8,128)(2,1)S(1)}, f32[32,2048,128]{2,1,0:T(8,128)})",
                                 "(f32[32,2048,128]{2,1,0:T(8,128)}, f32[32,2048,128]{2,1,0:T(8,128)})",
                                 "f32[32,2048,128]{2,1,0:T(8,128)}"]):
            events.append([f"%shard_map.{k} = {typ} custom-call(bf16[32,2048,128]{{2,1,0}} %bitcast.8884), "
                           f"custom_call_target=\"tpu_custom_call\"", t0 + 100 * k, 50, {}])
        events.append(["%custom-call.1588 = f32[512,16,128]{2,1,0:T(8,128)S(1)} custom-call(f32[128,16,128]{2,1,0} %s)",
                       t0 + 400, 10, {}])  # not a kernel: no tpu_custom_call target, another shape
    recorded = {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": modules},
                                                                {"name": "XLA Ops", "events": events}]}]}
    reduced = trace.reduce_trace(recorded, (0, 4000))
    published = dict(mf.load_json(os.path.join(mf.BENCH, "configs", "olmo-1b.json")), num_hidden_layers=1)
    record = {"reduced": reduced, "published": published, "train": {"micro_batch": 2, "seq_len": 2048},
              "device": {"kind": "TPU v5 lite", "count": 4}}
    share = mf.metric_module("flash_attention_roofline").read(record)
    least = (2 + 4) * 2 * 16 * 2048 * 2048 * 128 / 197e12      # one layer's forward and backward, causal
    assert share == pytest.approx(100 * 4 * least / (12 * 50e-9))
