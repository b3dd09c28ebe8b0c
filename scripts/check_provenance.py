#!/usr/bin/env python
"""Provenance lint for bench result records — thin wrapper over the
promoted data-lint core (heat3d_tpu.analysis.provenance), keeping the
established flags; the analysis subsystem owns the rules and shares its
finding/report format with ``heat3d lint`` (docs/ANALYSIS.md).

Round 5 shipped 21 live on-chip rows whose ``ts`` field was null — the
timestamp stamping landed AFTER the healthy window that measured the rows
it was built to provenance (VERDICT.md weak item 2). This lint makes that
class of gap loud at measurement time instead of at judging time: it FAILS
(rc 1) when any row in a results file

- has ``ts`` missing, null, or empty (a row that cannot prove which
  session measured it), or
- is a throughput row missing its route-provenance fields (``platform``,
  ``direct_path``, ``mehrstellen_route``, ``streamk_path``,
  ``streamk_emulated``, ``halo_plan``, ``chain_ops`` — ``chain_ops:
  null`` is legal only for ``backend: conv``, where a tap-chain op count
  does not exist), or
- is a ``time_blocking > 1`` throughput row missing a numeric
  ``cost_redundant_flops_frac`` (deep-tb recompute honesty), or
- is a halo row missing ``platform``, or
- is a ``weak_scaling`` row missing ``platform``, the judged
  ``gcell_per_sec_per_chip``, or its ``post_heal`` elastic provenance, or
- is a ``soak`` row (serve/loadgen.py verdict rows) missing
  ``platform``/``duration_s``/``seed``, violating the conservation law
  ``admitted + shed == submitted``, or missing the judged
  ``sustained_member_gcell_per_s``, ``degraded_s`` chaos provenance, or
  the ``slo`` verdict that judged it, or
- is a throughput/halo row missing a numeric ``sync_rtt_s``.

Wired into the bench report path (scripts/run_bench_suite.sh runs it after
regenerating BASELINE.md, and its rc is the suite's rc), so a session
cannot complete "green" while writing unprovenanced rows. APPEND-mode
sessions scope the lint with ``--start-line N`` to the rows THEY wrote —
a bare run over a whole legacy file still fails on legacy rows by design:
the fix is re-landing the suite in a healthy window, not weakening the
lint.

Usage: scripts/check_provenance.py [--start-line N] RESULTS.jsonl [...]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from heat3d_tpu.analysis.provenance import (  # noqa: E402,F401
    MAX_REPORT,
    ROUTE_FIELDS,
    check_file,
    check_row,
    main,
)

if __name__ == "__main__":
    sys.exit(main())
