"""True multi-process distributed tests — the mpirun world, reborn.

Two OS processes (4 CPU devices each) rendezvous via
jax.distributed.initialize on localhost and run the full CLI over an
8-device (2,2,2) mesh: cross-process collectives, per-process sharded
init, multi-host checkpoint write/resume, coordinator-only output, and
the golden check through a process_allgather. This is the closest this
box gets to the reference's `mpirun -np P ./heat3d` launch path
(SURVEY.md §1 L5, §3.1) — real process boundaries, not simulated ones.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _summary(stdout: str) -> dict:
    """Last JSON object line in stdout (Gloo logs its peer-connection info
    to stdout around the summary)."""
    lines = [ln for ln in stdout.splitlines() if ln.startswith("{")]
    assert lines, f"no JSON summary in stdout:\n{stdout}"
    return json.loads(lines[-1])


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _cpu_env(n_devices_per_proc: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices_per_proc}"
    ).strip()
    env["PYTHONPATH"] = os.pathsep.join([REPO, env.get("PYTHONPATH", "")])
    return env


def _launch(args, port, pid, env, out_f, err_f):
    return subprocess.Popen(
        [
            sys.executable, "-m", "heat3d_tpu",
            "--coordinator", f"localhost:{port}",
            "--num-processes", "2",
            "--process-id", str(pid),
            *args,
        ],
        env=env,
        stdout=out_f,
        stderr=err_f,
        cwd=REPO,
    )


def _run_pair(args, timeout=300):
    # File-backed capture: a chatty process can never block on a full pipe
    # while its peer waits in a collective (which would turn real failures
    # into opaque timeouts).
    import tempfile

    port = _free_port()
    env = _cpu_env(4)
    with tempfile.TemporaryDirectory() as td:
        files, procs = [], []
        for pid in (0, 1):
            out_f = open(os.path.join(td, f"out{pid}"), "w+")
            err_f = open(os.path.join(td, f"err{pid}"), "w+")
            files.append((out_f, err_f))
            procs.append(_launch(args, port, pid, env, out_f, err_f))
        outs = []
        try:
            for p, (out_f, err_f) in zip(procs, files):
                try:
                    p.wait(timeout=timeout)
                except subprocess.TimeoutExpired:
                    for q in procs:
                        q.kill()
                    dumps = []
                    for qid, (of, ef) in enumerate(files):
                        of.seek(0)
                        ef.seek(0)
                        dumps.append(
                            f"--- proc {qid} stdout ---\n{of.read()}\n"
                            f"--- proc {qid} stderr ---\n{ef.read()}"
                        )
                    raise AssertionError(
                        "multihost pair timed out; captured output:\n"
                        + "\n".join(dumps)
                    ) from None
                out_f.seek(0)
                err_f.seek(0)
                outs.append((p.returncode, out_f.read(), err_f.read()))
        finally:
            for out_f, err_f in files:
                out_f.close()
                err_f.close()
    for rc, out, err in outs:
        assert rc == 0, f"multihost process failed\nstdout:\n{out}\nstderr:\n{err}"
    return outs


def test_two_process_cli_golden_and_checkpoint(tmp_path):
    ck = str(tmp_path / "ck")
    plane = str(tmp_path / "plane.npy")
    outs = _run_pair(
        ["--grid", "16", "--steps", "5", "--mesh", "2", "2", "2",
         "--golden-check", "--checkpoint", ck,
         "--dump-slice", "z", "9", plane]
    )
    # coordinator prints the one JSON summary; the other process stays quiet
    summary = _summary(outs[0][1])
    assert summary["golden_pass"] is True
    assert summary["mesh"] == [2, 2, 2]
    # the slice dump crossed real process boundaries: only the coordinator
    # writes it, and its VALUES match the golden model's z=9 plane
    import numpy as np

    from heat3d_tpu.core import golden
    from heat3d_tpu.core.config import GridConfig, SolverConfig, StencilConfig

    assert summary["slice_path"] == plane
    got_plane = np.load(plane)
    assert got_plane.shape == (16, 16)
    want = golden.run(
        golden.make_init("hot-cube", (16, 16, 16)),
        SolverConfig(grid=GridConfig.cube(16)).grid, StencilConfig(), 5,
    )[:, :, 9]
    np.testing.assert_allclose(
        got_plane.astype(np.float64), want, rtol=1e-5, atol=1e-6
    )
    # non-coordinator emits no JSON summary (Gloo may chat on stdout)
    assert not [
        ln for ln in outs[1][1].splitlines() if ln.startswith("{")
    ]
    # both processes wrote their shards; proc 0 wrote the manifest
    manifest = json.load(open(os.path.join(ck, "manifest.json")))
    assert manifest["step"] == 5
    shards = [f for f in os.listdir(ck) if f.startswith("shard_")]
    assert len(shards) == 8  # (2,2,2) mesh = 8 blocks

    # resume across the same 2-process world and finish at 8 total steps
    outs2 = _run_pair(
        ["--grid", "16", "--steps", "3", "--mesh", "2", "2", "2",
         "--golden-check", "--checkpoint", ck, "--resume"]
    )
    summary2 = _summary(outs2[0][1])
    assert summary2["golden_pass"] is True
    manifest2 = json.load(open(os.path.join(ck, "manifest.json")))
    assert manifest2["step"] == 8


@pytest.mark.parametrize(
    ("extra", "direct"),
    [
        pytest.param([], False, id="exchange"),
        pytest.param(["--time-blocking", "2"], False, id="exchange-tb2"),
        # faces-direct paths (interpret-mode kernels) across real process
        # boundaries: step and fused tb=2 superstep
        pytest.param([], True, id="faces-direct"),
        pytest.param(["--time-blocking", "2"], True, id="faces-direct-tb2"),
    ],
)
def test_two_process_matches_single_process(extra, direct, monkeypatch, tmp_path):
    """Same run, 1 process vs 2 rendezvoused processes: identical residual
    (the '-np 1 vs -np P' oracle across real process boundaries)."""
    if direct:
        monkeypatch.setenv("HEAT3D_DIRECT_INTERPRET", "1")
    else:
        # pin the exchange path even if the var is set ambiently
        monkeypatch.delenv("HEAT3D_DIRECT_INTERPRET", raising=False)
    outs = _run_pair(
        ["--grid", "16", "--steps", "4", "--mesh", "2", "2", "2", *extra]
    )
    two = _summary(outs[0][1])

    env = _cpu_env(8)
    env.pop("HEAT3D_DIRECT_INTERPRET", None)  # baseline = exchange path
    single = subprocess.run(
        [sys.executable, "-m", "heat3d_tpu", "--grid", "16", "--steps", "4",
         "--mesh", "2", "2", "2", *extra],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert single.returncode == 0, single.stderr
    one = _summary(single.stdout)
    assert two["residual_l2"] == pytest.approx(one["residual_l2"], rel=1e-6)
