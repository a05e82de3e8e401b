"""The trace reduction on a small recorded trace: busy union, idle
share, per-kernel and per-program time, idle gaps by host span."""

import pytest

from bench.harness import trace as TR

KERNELS = {"flash_fwd": ["^flash_attention_bhsd"],
           "paged_attn": ["^paged_attention_bkgd"]}


# device op events as a v5e trace prints them: the whole instruction
WHILE = ("%while.13 = (s32[]{:T(128)}, bf16[16,1,1536]{2,0,1:T(8,128)(2,1)}) "
         "while((s32[]{:T(128)}, bf16[16,1,1536]{2,0,1:T(8,128)(2,1)}) "
         "%tuple.5), condition=%region_1, body=%region_2")
FLASH = ("%flash_attention_bhsd.1 = bf16[2,12,1024,128]{3,2,1,0:T(8,128)(2,1)} "
         "custom-call(%bitcast.6, %copy_bitcast_fusion.1, %copy_bitcast_fusion), "
         "custom_call_target=\"tpu_custom_call\"")
PAGED = ("%paged_attention_bkgd.3 = bf16[8,2,6,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
         "custom-call(%broadcast_maximum_fusion, %copy-done, %kp.1, %vp.1), "
         "custom_call_target=\"tpu_custom_call\"")


def _op(text):
    return "%" + text + " = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop"


def _synthetic():
    ms = 1_000_000
    ops = [(_op("fusion.1"), 0 * ms, 2 * ms),
           (WHILE, 0, 4 * ms),                            # holds the next op
           (FLASH, 1 * ms, 3 * ms),                       # overlaps fusion.1
           (_op("fusion.2"), 6 * ms, 1 * ms),
           (PAGED, 9 * ms, 1 * ms),
           (_op("fusion.9"), 19 * ms, 4 * ms)]            # runs past the window
    ops = [(TR.device_op(t), s, d) for t, s, d in ops]
    modules = [("jit_prefill", 0, 7 * ms), ("jit_decode", 9 * ms, 1 * ms),
               ("jit_other", 19 * ms, 4 * ms)]
    host = [("bench.window", 0, 20 * ms), ("bench.step", 0, 7 * ms),
            ("bench.observe", 7 * ms, 2 * ms), ("bench.step", 9 * ms, 1 * ms),
            ("bench.wait", 10 * ms, 9 * ms), ("other", 0, 20 * ms)]
    return {"devices": [{"ops": ops, "modules": modules}],
            "host": [h for h in host if h[0].startswith("bench.")]}


def test_busy_union_idle_and_kernels():
    red = TR.reduce(_synthetic(), KERNELS)
    assert red["window_s"] == pytest.approx(0.020)
    # busy: [0,4) + [6,7) + [9,10) + [19,20) = 7 ms
    assert red["busy_s"] == pytest.approx(0.007)
    assert red["idle_share"] == pytest.approx(1 - 7 / 20)
    assert red["kernels"]["flash_fwd"] == {"seconds": pytest.approx(0.003),
                                           "calls": 1}
    assert red["kernels"]["paged_attn"]["seconds"] == pytest.approx(0.001)
    assert red["modules"]["jit_prefill"] == pytest.approx(0.007)
    assert red["modules"]["jit_other"] == pytest.approx(0.001)   # clipped
    assert red["ops"]["jit_prefill/flash_attention_bhsd.1"] == pytest.approx(0.003)
    assert not any("while" in k for k in red["ops"])   # containers skipped
    assert TR.module_seconds(red, "flash_fwd") == pytest.approx(0.007)
    assert TR.module_seconds(red, "paged_attn") == pytest.approx(0.001)


def test_idle_gaps_are_tagged_by_host_span():
    red = TR.reduce(_synthetic(), KERNELS)
    gaps = red["idle_gaps"]
    # [4,6) in step, [7,9) in observe, [10,19) in wait
    assert gaps["bench.step"] == pytest.approx(0.002)
    assert gaps["bench.observe"] == pytest.approx(0.002)
    assert gaps["bench.wait"] == pytest.approx(0.009)
    assert sum(gaps.values()) == pytest.approx(red["window_s"] - red["busy_s"])
    assert TR.top(gaps, 1) == [["bench.wait", pytest.approx(0.009)]]


def test_op_names():
    assert TR.device_op(WHILE) == "~while.13"
    assert TR.device_op(FLASH) == "flash_attention_bhsd.1"
    assert TR.device_op(_op("convert.28")) == "convert.28"


def test_union():
    assert TR.union_ns([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
