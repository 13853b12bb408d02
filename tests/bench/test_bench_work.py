"""Operation and byte counts against hand counts, and the kernel
roofline and step MFU readers on a hand-made trace."""

from types import SimpleNamespace as NS

import pytest

from bench import trace as tr
from bench.metrics import paged_decode_roofline, step_mfu
from bench.archs.dense_decoder import Dims
from bench.work import Step, decode_attention, head_flops, least_time, \
    step_flops

DIMS = Dims(layers=2, d=8, heads=4, kv_heads=2, head_dim=2, ff=16,
            vocab=10, tied=True, rope_theta=1e4, eps=1e-5)
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_decode_attention_by_hand():
    flops, byts = decode_attention(DIMS, 0, [3, 5])
    assert flops == 4 * 4 * 2 * 8               # 4 H D per key, 8 keys
    assert byts == 2 * 2 * 2 * 2 * 8 + 2 * 4 * 2 * 2 * 2
    assert least_time(flops, byts, PEAKS) == pytest.approx(19.2)


def test_step_flops_by_hand():
    # 4 tokens through 576 matmul weights a layer; attention over the
    # chunk's causal contexts 1 + 2 + 3 and one decode lane of 5
    assert DIMS.layer_matmul_params(0) == 8 * 2 * 12 + 3 * 8 * 16
    assert step_flops(DIMS, Step([(0, 3)], [5])) == 2 * (
        2 * 576 * 4 + 4 * 4 * 2 * 11)
    # a chunk starting at 10 sees 11, 12 keys
    assert step_flops(DIMS, Step([(10, 2)], [])) == 2 * (
        2 * 576 * 2 + 4 * 4 * 2 * 23)
    assert head_flops(DIMS, 3) == 2 * 8 * 10 * 3


def _run(ops, modules, steps, emitted=0):
    t = tr.from_events((0.0, 1000.0),
                       {"/device:TPU:0": {"ops": ops, "modules": modules}},
                       [])
    return NS(trace=t, steps=steps, dims=DIMS, peaks=PEAKS,
              emitted_traced=emitted)


def test_roofline_share_counts_valid_lengths_only():
    steps = [Step([], [3, 5]), Step([(0, 3)], [])]
    kernel = ("%_decode_paged_impl.63 = (f32[4,5,32,3,64]{4,3,2,1,0:T(4,128)"
              "S(1)}, f32[4,5,32,3,1]{4,3,2,1,0:T(4,128)S(1)}) custom-call("
              "s32[4,32]{1,0} %copy-done.31)")
    ops = [(kernel, 0.0, 40.0), (kernel, 50.0, 86.8),
           ("%custom-call.142 = bf16[1033,128,5,64]{3,2,1,0} custom-call("
            "bf16[8] %x)", 87.0, 89.0),
           ("fusion", 90.0, 200.0)]
    # least time: 2 layers x 19.2 s against 76.8 s in the kernel
    r = _run(ops, [], steps)
    assert paged_decode_roofline.read(r) == pytest.approx(50.0)
    assert paged_decode_roofline.read(_run([], [], steps)) is None


def test_step_mfu_by_hand():
    steps = [Step([(0, 3)], [5])]
    mods = [("jit__mixed_paged_impl(3)", 0.0, 104.0),
            ("jit__decode_paged_impl(4)", 200.0, 304.0),
            ("jit_copy_page(5)", 400.0, 900.0)]
    # (9920 + 480) FLOPs over 208 s x 100 FLOP/s
    assert step_mfu.read(_run([], mods, steps, emitted=3)) == \
        pytest.approx(50.0)
    assert step_mfu.read(_run([], [], steps, emitted=3)) is None
