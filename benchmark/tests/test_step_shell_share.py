"""``step.shell_share`` on small synthetic ``ProfileData``-shaped traces:
the multistep loop's ops less its kernel, copies and collectives."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import tracing  # noqa: E402
from test_tracing import WINDOW, ctx, ev, kernel, metric, profile  # noqa: E402


def test_shell_share_counts_the_loops_small_ops_in_the_window():
    t = tracing.from_profile(profile([[
        kernel(4, 100, 300),  # [100, 400): the bulk kernel
        ev("%collective-permute-start.1 = f32[2] collective-permute-start("
           "f32[2] %a)", 150, 100),
        # a shell fusion whose operand is a collective's output: counted
        ev("%and_select_fusion.13 = f32[2] fusion(f32[2] "
           "%collective-permute-done.1)", 400, 100),  # [400, 500)
        ev("%dynamic_update_slice.92 = f32[8] dynamic-update-slice("
           "f32[8] %heat3d.stencil.4, f32[2] %b)", 450, 100),  # [450, 550)
        ev("%copy.46 = f32[8] copy(f32[8] %c)", 550, 100),  # a copy
        ev("%fusion.322 = f32[2] fusion(f32[8] %d)", 1050, 100),  # clipped
        ev("%fusion.9 = f32[8] fusion(f32[8] %e)", 700, 100),  # bench copy
    ], [
        kernel(4, 100, 300),
        ev("%fusion.322 = f32[2] fusion(f32[8] %d)", 400, 50),
    ]], WINDOW, modules=[ev("jit_runk(7)", 90, 560),
                         ev("jit_copy(8)", 690, 120),
                         ev("jit_runk(7)", 1000, 200)]))
    # device 0: [400, 550) and [1050, 1100) of the 1000 ns window
    got = metric("step.shell_share").read(ctx(t))
    assert got == pytest.approx(100.0 * 200 / 1000)


def test_no_loop_program_gives_no_shell_share():
    t = tracing.from_profile(profile(
        [[ev("%fusion.1 = f32[8] fusion(f32[8] %a)", 100, 100)]], WINDOW,
        modules=[ev("jit_wrapper(1)", 90, 200)]))
    assert metric("step.shell_share").read(ctx(t)) is None
    assert metric("step.shell_share").read(ctx(None)) is None
