"""The one general traffic driver: warm-up, then the measured window.

A traffic mix is a JSON file of parameters (``benchmark/traffic/``):

- ``chunk_steps`` N > 0: each dispatch is ``solver.run(u, N)``, the
  compiled multistep loop, followed by ``block_until_ready``;
- ``residual_every`` K > 0: each dispatch is the CLI's ``--residual-every
  K`` group, ``solver.run(u, K - 1)`` then ``solver.step_with_residual``,
  with the residual read back to the host.

The window runs from the first dispatch to the return of the block (or
readback) that ends the dispatch after which ``seconds`` have passed. It
counts every step completed. Before each dispatch that may be the last,
the driver copies the field on the device, so that the reference can
redo the window's last dispatch from its input: a dispatch is judged
possibly last when the time so far plus 1.5 times the last dispatch's
time reaches ``seconds`` (and before the first). The window only ends
after a dispatch whose input was kept, so it can overrun ``seconds`` by
one dispatch in the rare case the guess was wrong. The copy is inside the
window: about 10 ms for a 4 GiB field, one or two per window.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class WindowResult:
    u: jax.Array  # the field after the last dispatch
    kept: jax.Array  # the last dispatch's input
    last_steps: int  # steps the last dispatch made
    last_residual: Optional[float]  # sum of squares it read back, if any
    steps: int
    dispatches: int
    seconds: float
    durations: List[float]  # seconds of each dispatch, copy included


class Driver:
    def __init__(self, solver, traffic: dict,
                 annotate: Callable = lambda name: contextlib.nullcontext()):
        self.solver = solver
        self.annotate = annotate
        self.residual_every = int(traffic.get("residual_every", 0))
        self.chunk_steps = int(traffic.get("chunk_steps", 0))
        if (self.residual_every > 0) == (self.chunk_steps > 0):
            raise ValueError(f"traffic needs exactly one of chunk_steps and "
                             f"residual_every above 0: {traffic}")
        self.steps_per_dispatch = self.residual_every or self.chunk_steps
        self._copy = jax.jit(jnp.copy)

    def dispatch(self, u):
        """One dispatch of the mix; returns (u, residual or None) once the
        device has finished it."""
        if self.residual_every:
            with self.annotate("bench.dispatch"):
                if self.residual_every > 1:
                    u = self.solver.run(u, self.residual_every - 1)
                u, r2 = self.solver.step_with_residual(u)
            with self.annotate("bench.readback"):
                return u, float(r2)
        with self.annotate("bench.dispatch"):
            u = self.solver.run(u, self.chunk_steps)
        with self.annotate("bench.block"):
            jax.block_until_ready(u)
        return u, None

    def copy(self, u):
        with self.annotate("bench.copy"):
            return self._copy(u)

    def warm_up(self, u):
        """One dispatch of every program the window uses."""
        kept = self.copy(u)
        u, _ = self.dispatch(u)
        jax.block_until_ready(kept)
        del kept
        return u

    def run(self, u, seconds: float) -> WindowResult:
        steps = dispatches = 0
        durations: List[float] = []
        kept = None
        last = None
        r = None
        t0 = time.perf_counter()
        elapsed = 0.0
        while True:
            kept = None  # free the last copy before the next is made
            if last is None or elapsed + 1.5 * last >= seconds:
                kept = self.copy(u)
            u, r = self.dispatch(u)
            steps += self.steps_per_dispatch
            dispatches += 1
            now = time.perf_counter() - t0
            last, elapsed = now - elapsed, now
            durations.append(last)
            if elapsed >= seconds and kept is not None:
                break
        return WindowResult(
            u=u, kept=kept, last_steps=self.steps_per_dispatch,
            last_residual=r, steps=steps, dispatches=dispatches,
            seconds=elapsed, durations=durations,
        )
