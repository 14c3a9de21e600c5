"""Readings of the numbers `correct` compares, for the program and for the
control, at a cell's own size, in one process on the chip.

    python bench/control.py --workload <cell> --seeds 12 --control-seeds 3

For each seed: as many jobs of the cell as a run keeps for its check,
through the timed path (after a warm-up job), then the same check a run
makes on them.  For the first ``--control-seeds`` seeds also the control
on the same lanes: the reference computed in bfloat16 in the program's
place.  One JSON line per seed, then a summary
line with the largest program reading and the smallest control reading of
each number.  The limits in ``run.LIMITS`` are set between the two
(PERF.md, section 2).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import cells  # noqa: E402
from bench import run as brun  # noqa: E402

NUMBERS = ("leaves_differing", "summary_gap")


def jobs_of(plan, seed: int, mesh) -> list:
    """As many jobs as a run keeps for its check, seeded as a run seeds
    its window, with their states on the host."""
    import jax
    import numpy as np
    out = []
    for j in range(int(plan.traffic["check"]["jobs"])):
        job = brun.run_job(plan, j, cells.derive(seed, j), mesh)
        job.calls = [(c, ln, cfg, jax.tree.map(np.asarray, st), sums)
                     for c, ln, cfg, st, sums in job.calls]
        out.append(job)
    return out


def readings(plan, jobs, seed: int, device, control: bool) -> dict:
    got = brun.check(plan, jobs, cells.derive(seed, 1 << 22), device,
                     control)
    return dict(got, seed=seed, control=control)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 101)
    args = ap.parse_args(argv)
    import jax
    devs = jax.devices()
    if devs[0].platform != brun.PLATFORM:
        print(f"control: no {brun.PLATFORM} found", file=sys.stderr)
        return 3
    brun.enable_cache()
    plan = cells.plan_for(args.workload, ROOT)
    mesh = None
    if plan.chips > 1:
        from repro.launch.mesh import make_sweep_mesh
        mesh = make_sweep_mesh(plan.chips)
    brun.run_job(plan, -1, cells.derive(args.first_seed, 1 << 20), mesh)
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + i
        jobs = jobs_of(plan, seed, mesh)
        for control in (False, True)[:1 + (i < args.control_seeds)]:
            rows.append(readings(plan, jobs, seed, devs[0], control))
            print(json.dumps(rows[-1]), flush=True)
    summary = {"workload": args.workload}
    for k in NUMBERS:
        prog = [r[k] for r in rows if not r["control"]]
        ctl = [r[k] for r in rows if r["control"]]
        summary[k] = {"program_max": max(prog) if prog else None,
                      "control_min": min(ctl) if ctl else None}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
