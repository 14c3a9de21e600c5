"""The chip benchmark of the lock simulator: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``BENCHMARK.json``: a configuration
(``bench/configs/``) under a traffic mix (``bench/traffic/``).  A job is
what a user waits for: every ``simlock.sweep`` call of the cell's grid,
each followed by ``simlock.sweep_summaries`` (or one ``simlock.run``
followed by ``simlock.summarize``), until the statistics are on the host.

The run refuses anything but a TPU, warms up with one whole job (set-up),
then runs a closed loop of jobs, one client, for ``--seconds``; the window
ends with the last whole job.  ``--trace 1`` traces the first jobs of the
window with the profiler and reports the per-layer metrics instead of the
end-to-end ones.  Afterwards a sample of the window's lanes, drawn from the
seed, is compared with the plain reference (``bench/reference.py``).  The
last line of standard output is one JSON object; the numbers compared, each
with its limit, end standard error and the line's ``check`` key.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
PLATFORM = "tpu"
CACHE_DIR = BENCH / ".cache" / "xla"
# Limits of the numbers compared with the reference (PERF.md, section 2,
# gives the readings they were set from).
LIMITS = {"leaves_differing": 0, "summary_gap": 1e-9}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else the fixed ``bench/.cache/xla`` of this checkout.
    Every program is cached, however short its compile."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


# --------------------------------------------------------------------------
# One job
# --------------------------------------------------------------------------

class Job:
    """What one job produced: per call the lanes, the device state and the
    host summaries; events retired in all."""

    def __init__(self, index, seed):
        self.index, self.seed = index, seed
        self.calls = []            # (call, lanes, cfg, state, summaries)
        self.lane_events = []      # (chunk, events per lane) per call
        self.events = 0
        self.wall = 0.0


def run_job(plan, index: int, seed: int, mesh=None, sl=None) -> Job:
    import jax
    if sl is None:
        from repro.core import simlock as sl
    job = Job(index, seed)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("job"):
        for call in plan.calls:
            lanes = plan.lanes(call, seed)
            cfg = plan.sim_config(call)
            slo = lanes[0]["slo_us"]
            if plan.kind == "run":
                with jax.profiler.TraceAnnotation("inputs"):
                    st = sl.run(cfg, slo, seed)
                with jax.profiler.TraceAnnotation("summaries"):
                    sums = [sl.summarize(cfg, st, slo_us=slo)]
            else:
                with jax.profiler.TraceAnnotation("inputs"):
                    st, grid = sl.sweep(cfg, plan.axes(call, lanes),
                                        slo_us=slo, seed=seed,
                                        product=False, mesh=mesh)
                with jax.profiler.TraceAnnotation("summaries"):
                    sums = sl.sweep_summaries(cfg, st, grid, slo_us=slo)
            job.calls.append((call, lanes, cfg, st, sums))
            job.lane_events.append(
                (cfg.chunk, [int(s["events"]) for s in sums]))
            job.events += sum(int(s["events"]) for s in sums)
    job.wall = time.perf_counter() - t0
    return job


def host_leaves(st, lane: int, single: bool) -> dict:
    """Every state leaf of one lane, on the host; pol slots as ``pol.*``."""
    import numpy as np
    out = {}
    for name, x in st._asdict().items():
        items = [("pol." + k, v) for k, v in x.items()] \
            if isinstance(x, dict) else [(name, x)]
        for k, v in items:
            v = np.asarray(v)
            out[k] = v if single else v[lane]
    return out


# --------------------------------------------------------------------------
# The sample the check compares, drawn from the seed
# --------------------------------------------------------------------------

class Sample:
    """A reservoir of ``k`` window jobs, uniform over the window and drawn
    from the seed, plus the job that retired the most events."""

    def __init__(self, k: int, seed: int):
        self.k, self.rng = k, random.Random(seed)
        self.kept, self.seen, self.longest = [], 0, None

    def offer(self, job: Job) -> None:
        self.seen += 1
        if len(self.kept) < self.k:
            self.kept.append(job)
        else:
            i = self.rng.randrange(self.seen)
            if i < self.k:
                self.kept[i] = job
        if self.longest is None or job.events > self.longest.events:
            self.longest = job

    def jobs(self) -> list:
        out = list(self.kept)
        if self.longest is not None and all(j is not self.longest
                                            for j in out):
            out.append(self.longest)
        return out


def check(plan, jobs: list, seed: int, device=None,
          control: bool = False) -> dict:
    """Compare the sampled lanes of ``jobs`` with the reference: every
    state leaf bit for bit, and every number of the host summary.  With
    ``control`` the control (the reference in bfloat16) stands in the
    program's place on the same lanes."""
    from bench import reference as ref
    rng = random.Random(seed)
    per_call = int(plan.traffic["check"]["lanes_per_call"])
    n_leaves, gap, lanes_done, ref_events = 0, 0.0, 0, 0
    worst = None
    for job in jobs:
        # the longest lane of the job, then lanes drawn from the seed
        longest = max(((ci, li) for ci, c in enumerate(job.calls)
                       for li in range(len(c[1]))),
                      key=lambda p: job.calls[p[0]][4][p[1]]["events"])
        picks = {longest}
        for ci, c in enumerate(job.calls):
            n = len(c[1])
            picks.update((ci, li) for li in rng.sample(range(n),
                                                       min(per_call, n)))
        for ci, li in sorted(picks):
            call, lanes, cfg, st, sums = job.calls[ci]
            want = ref.simulate(lanes[li], device=device)
            if control:
                ctl = ref.simulate(lanes[li], ref.CONTROL_DTYPE, device)
                got, got_sum = ctl["leaves"], ctl["summary"]
            else:
                got = host_leaves(st, li, plan.kind == "run")
                got_sum = sums[li]
            bad = ref.leaves_differing(got, want["leaves"])
            g = ref.summary_gap(got_sum, want["summary"])
            if bad and worst is None:
                worst = (job.index, call.policies, li, bad)
            n_leaves += len(bad)
            gap = max(gap, g)
            lanes_done += 1
            ref_events += int(want["leaves"]["events"])
    if worst is not None:
        log(f"check: job {worst[0]} call {worst[1]} lane {worst[2]} "
            f"differs in {worst[3]}")
    return {"leaves_differing": n_leaves, "summary_gap": gap,
            "lanes_compared": lanes_done, "reference_events": ref_events}


# --------------------------------------------------------------------------
# The run
# --------------------------------------------------------------------------

def device_info(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def load_metric(name: str):
    """The reader of per-layer metric ``name``: ``bench/metrics/<name>.py``,
    whose ``read(ctx)`` returns a number, or None when it finds nothing."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def measure(plan, name: str, seed: int, seconds: float, trace: bool,
            devs, manifest: dict, sl=None) -> dict:
    """Set-up, the window and the check of one run; returns the result
    line as a dict (``check`` last)."""
    import jax
    import numpy as np
    from bench import cells
    if sl is None:
        from repro.core import simlock as sl
    mesh = None
    if plan.chips > 1:
        from repro.launch.mesh import make_sweep_mesh
        mesh = make_sweep_mesh(plan.chips)
    used = devs[:plan.chips]

    warm = run_job(plan, -1, cells.derive(seed, 1 << 20), mesh, sl)
    setup_s = time.perf_counter() - T_START
    n_exec = sl.n_batch_executables() + sl._run_single._cache_size()
    log(f"setup_s={setup_s:.3f} warm-up job wall={warm.wall:.3f}s "
        f"events={warm.events} executables={n_exec}")
    del warm

    chk = plan.traffic["check"]
    sample = Sample(int(chk["jobs"]), cells.derive(seed, 1 << 21))
    n_traced = int(plan.traffic.get("trace_jobs", 1)) if trace else 0
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    traced = []
    walls, events, jobs = [], 0, 0
    t0 = time.perf_counter()
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    while jobs == 0 or time.perf_counter() - t0 < seconds:
        if jobs < n_traced:
            with jax.profiler.TraceAnnotation("window"):
                job = run_job(plan, jobs, cells.derive(seed, jobs), mesh, sl)
            traced.append(job.lane_events)
            if jobs == n_traced - 1:
                t_stop = time.perf_counter()
                jax.profiler.stop_trace()
                log(f"trace of {n_traced} jobs written in "
                    f"{time.perf_counter() - t_stop:.3f}s")
        else:
            job = run_job(plan, jobs, cells.derive(seed, jobs), mesh, sl)
        walls.append(job.wall)
        events += job.events
        sample.offer(job)
        jobs += 1
    window_s = time.perf_counter() - t0
    if trace and jobs < n_traced:
        jax.profiler.stop_trace()
    peak = memory_peak(used)
    log(f"window_s={window_s:.3f} jobs={jobs} events={events}")

    # The program's state of jobs outside the sample is gone; bring the
    # sample to the host, free the device, then run the reference.
    for job in sample.jobs():
        job.calls = [(c, ln, cfg, jax.tree.map(np.asarray, st), sums)
                     for c, ln, cfg, st, sums in job.calls]
    t_ref = time.perf_counter()
    got = check(plan, sample.jobs(), cells.derive(seed, 1 << 22),
                device=used[0])
    log(f"check: {got['lanes_compared']} lanes, "
        f"{got['reference_events']} reference events in "
        f"{time.perf_counter() - t_ref:.3f}s")

    out = {"correct": False, "attempted": jobs, "failed": 0, "metrics": {},
           "device": dict(device_info(used), memory_peak_bytes=peak)}
    if trace:
        from bench import trace_reduce
        t_red = time.perf_counter()
        red = trace_reduce.reduce_dir(trace_dir, len(used))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t_red:.3f}s")
        ctx = {"plan": plan, "lane_events": traced, "trace": red,
               "executables": n_exec, "chips": plan.chips}
        out["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
        for m in manifest["per_layer"]:
            if name in m.get("workloads", [name]):
                v = load_metric(m["name"])(ctx)
                if v is not None:
                    out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = red["breakdown"]
    else:
        e2e = {"events_per_s": events / window_s,
               "job_p95_ms": _p95(walls) * 1e3, "setup_s": setup_s}
        for m in manifest["end_to_end"]:
            if name in m.get("workloads", [name]):
                out["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                             "unit": m["unit"]}
    limits_ok = all(got[k] <= lim for k, lim in LIMITS.items())
    out["correct"] = bool(limits_ok and got["lanes_compared"] > 0)
    out["check"] = {k: {"value": got[k], "limit": lim}
                    for k, lim in LIMITS.items()}
    out["check"]["lanes_compared"] = {"value": got["lanes_compared"],
                                      "at_least": 1}
    return out


def _p95(walls) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(walls, float), 95))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"bench: no program under {ROOT / 'src'}; nothing was run")
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import cells
    manifest = cells.manifest(ROOT)
    w = cells.workload(args.workload, ROOT)

    import jax
    devs = jax.devices()
    if devs[0].platform != PLATFORM:
        log(f"bench: JAX found no {PLATFORM} (first device: "
            f"{devs[0].platform}); nothing was measured")
        return 3
    if len(devs) < int(w["chips"]):
        log(f"bench: the cell needs {w['chips']} chips, JAX found "
            f"{len(devs)}; nothing was measured")
        return 3
    log(f"device {device_info(devs)} jax={jax.__version__} "
        f"cache={enable_cache()}")
    plan = cells.plan_for(args.workload, ROOT)
    out = measure(plan, args.workload, args.seed, args.seconds,
                  bool(args.trace), devs, manifest)
    for k, v in out["check"].items():
        lim = v.get("limit", v.get("at_least"))
        log(f"check {k} = {v['value']} (limit {lim})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
