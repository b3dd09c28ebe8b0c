"""The trace reduction on small synthetic ``ProfileData``-shaped traces."""

import importlib.util
import os
import sys
from types import SimpleNamespace as NS

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import tracing  # noqa: E402


def metric(name):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    mod_name = "m_" + name.replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def kernel(n, start, dur):
    return ev(f"%heat3d.stencil.{n} = f32[8]{{0}} custom-call(f32[8]{{0}} %a), "
              'custom_call_target="tpu_custom_call"', start, dur)


def profile(devices, host, modules=()):
    planes = [NS(name=f"/device:TPU:{i}",
                 lines=[NS(name="XLA Ops", events=evs),
                        NS(name="XLA Modules", events=list(modules))])
              for i, evs in enumerate(devices)]
    planes.append(NS(name="/host:CPU",
                     lines=[NS(name="python", events=host),
                            NS(name="other", events=[ev("not.bench", 0, 5)])]))
    return NS(planes=planes)


WINDOW = [ev("bench.window", 100, 1000)]


def ctx(trace, **kw):
    base = dict(trace=trace, itemsize=4, local_cells=1000,
                peaks={"hbm_bytes_per_s": 1e9})
    base.update(kw)
    return NS(**base)


def test_idle_share_takes_the_union_not_the_sum():
    # two overlapping ops cover [100, 700); a sum would give 1000 ns busy;
    # the while op around them is a container, not device work
    t = tracing.from_profile(profile(
        [[ev("%while.3 = (s32[], f32[8]) while((s32[], f32[8]) %t)", 0, 2000),
          ev("fusion.1", 100, 500), ev("fusion.2", 200, 500)]], WINDOW))
    assert tracing.busy_s(t) == pytest.approx(600e-9)
    share = metric("device.idle_share").read(ctx(t))
    assert share == pytest.approx(40.0)


def test_idle_share_clips_to_the_window_and_takes_the_worst_device():
    t = tracing.from_profile(profile(
        [[ev("a", 0, 2000)], [ev("a", 100, 250), ev("b", 900, 500)]],
        WINDOW))
    # device 0 busy all window; device 1 busy 250 + 200 of 1000
    assert metric("device.idle_share").read(ctx(t)) == pytest.approx(55.0)


def test_kernel_events_are_picked_by_the_names_in_the_metric_file():
    roof = metric("stencil_roofline")
    t = tracing.from_profile(profile([[
        kernel(16, 100, 100),
        kernel(17, 300, 100),
        ev("%heat3d.stencil.2 = f32[8]{0} fusion(f32[8]{0} %a)", 450, 50),
        ev("%custom-call.1 = f32[8] custom-call(), "
           'custom_call_target="tpu_custom_call"', 500, 100),
        ev("fusion.9", 600, 300),  # not a kernel: ignored
    ]], WINDOW))
    # 2 sweeps x 2 x 4 B x 1000 cells = 16000 B in 200 ns, over 1e9 B/s
    assert roof.read(ctx(t)) == pytest.approx(100.0 * 16000 / 200e-9 / 1e9)


def test_no_kernel_event_gives_no_roofline():
    t = tracing.from_profile(profile([[ev("fusion.9", 500, 400)]], WINDOW))
    assert metric("stencil_roofline").read(ctx(t)) is None
    assert metric("stencil_roofline").read(ctx(None)) is None


def test_exposed_halo_counts_only_time_with_no_kernel_running():
    t = tracing.from_profile(profile([[
        kernel(1, 100, 400),  # [100, 500)
        ev("collective-permute-start.1", 300, 400),  # [300, 700): 200 exposed
        ev("collective-permute-done.1", 650, 100),  # [650, 750): 50 more
        ev("fusion", 800, 100),
    ]], WINDOW))
    assert metric("halo.exposed_share").read(ctx(t)) == pytest.approx(25.0)


def test_no_halo_op_gives_no_exposed_share():
    t = tracing.from_profile(profile([[ev("fusion", 100, 100)]], WINDOW))
    assert metric("halo.exposed_share").read(ctx(t)) is None


def test_residual_share_counts_the_residual_program_less_its_kernel():
    t = tracing.from_profile(profile([[
        kernel(16, 100, 200),  # the multistep loop's kernel
        kernel(1, 300, 300),  # the residual step's kernel
        ev("%multiply_reduce_fusion = f32[] fusion(f32[8] %a)", 600, 100),
        ev("%copy.1 = f32[8] copy(f32[8] %heat3d.stencil.1)", 700, 100),
        ev("%copy.26 = f32[8] copy(f32[8] %b)", 850, 50),  # loop's copy
    ]], WINDOW, modules=[ev("jit_runk(123)", 90, 210),
                         ev("jit_wrapper(456)", 300, 510),
                         ev("jit_runk(123)", 840, 70)]))
    # reduction + copy = 200 ns of 750 ns busy
    got = metric("solver.residual_share").read(ctx(t))
    assert got == pytest.approx(100.0 * 200 / 750)


def test_breakdown_names_idle_gaps_by_host_phase():
    host = WINDOW + [ev("bench.dispatch", 100, 300),
                     ev("bench.block", 400, 500),
                     ev("bench.readback", 900, 200)]
    t = tracing.from_profile(profile(
        [[ev("op.a", 150, 100), ev("op.b", 600, 200)]], host))
    gaps = dict(tracing.idle_gaps(t))
    # idle: [100,150) dispatch, [250,600) block mid 425, [800,1100) mid 950
    assert gaps == pytest.approx({"bench.dispatch": 50e-9,
                                  "bench.block": 350e-9,
                                  "bench.readback": 300e-9})
    ops = dict(tracing.top_ops(t))
    assert ops == pytest.approx({"op.b": 200e-9, "op.a": 100e-9})


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        tracing.from_profile(profile([[ev("a", 0, 1)]], []))


def test_interval_arithmetic():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert tracing.minus([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tracing.length(tracing.clip([(0, 10), (20, 30)], (5, 25))) == 10
