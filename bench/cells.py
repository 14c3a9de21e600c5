"""Cells of the benchmark: a configuration file and a traffic mix, found by
the names ``BENCHMARK.json`` gives them, turned into the calls a job makes.

A configuration (``configs/<name>.json``) states the machine, the epoch
program and the lock policies' knobs.  The generator takes closed-loop
epochs, keyed or not, and refuses open-loop arrivals (no reference models
them yet).  Without a ``keys`` block every epoch contends the locks of its
static segment program (``epoch.seg_lock``).  With ``"keys": {"n_keys": K,
"zipf_theta": theta}`` each epoch of each core draws a key from YCSB's
Zipf(K, theta) and contends the bucket lock ``key % epoch.n_locks``, and
``ks_crew`` draws the epoch's read/write class besides; the block is
refused where ``K < n_locks``, where theta is negative or not finite, and
beside open-loop arrivals.  A traffic mix
(``traffic/<name>.json``) states the grid: which policies, the grid axes,
the horizon, how many seed replicas per grid point, whether a job is
``sweep`` calls or one ``run``, and how much of each job the correctness
check samples.  One general generator (``Plan``) reads both; adding a cell
adds files, not code.

Every lane is described twice from the same numbers: as the program's
``SimConfig`` and sweep axes, and as a plain dict for the reference
(``bench/reference.py``).
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json
import math
from pathlib import Path

ROOT = Path(__file__).resolve().parent
REPO = ROOT.parent
POLICY_KNOBS = ("shfl_bound", "race_bound", "erew_bound", "crew_bound",
                "crew_wfrac", "jbsq_k")
LANE_KNOBS = ("slo_us", "w_big", "prop_n")
KNOB_DEFAULTS = {"shfl_bound": 4, "race_bound": 8, "erew_bound": 4,
                 "crew_bound": 4, "crew_wfrac": 0.5, "jbsq_k": 4}


def derive(*ints) -> int:
    """A seed in [0, 2**31) that is a pure function of ``ints``."""
    h = hashlib.blake2b(repr(tuple(int(i) for i in ints)).encode(),
                        digest_size=8)
    return int.from_bytes(h.digest(), "little") & 0x7FFFFFFF


def manifest(root: Path = REPO) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_json(kind: str, name: str, root: Path = ROOT) -> dict:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def workload(name: str, root: Path = REPO) -> dict:
    for w in manifest(root)["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


@dataclasses.dataclass
class Call:
    """One program call of a job: a sweep (or run) over ``points``."""
    policies: tuple            # the executable's policy set
    points: list               # one dict per grid point (before seeds)
    merged: bool


class Plan:
    """The jobs of one cell: ``calls`` in order; seeds drawn per job."""

    def __init__(self, config: dict, traffic: dict, chips: int = 1,
                 sim_time_us: float | None = None):
        self.config, self.traffic, self.chips = config, traffic, chips
        if config.get("arrivals", {}).get("open_loop"):
            raise ValueError(f"config {config['name']!r} asks for open-loop "
                             "arrivals; the generator and the reference "
                             "model closed-loop epochs only")
        self.keys = _keys(config)
        self.kind = traffic["job"]
        if self.kind not in ("sweep", "run"):
            raise ValueError(f"job kind {self.kind!r} is not sweep or run")
        self.sim_time_us = float(sim_time_us or traffic["sim_time_us"])
        self.replicas = int(traffic.get("seed_replicas", 0))
        axes = traffic["axes"]
        names = list(axes)
        grid = [dict(zip(names, vals))
                for vals in itertools.product(*(axes[k] for k in names))]
        pols = traffic["policies"]
        for p in pols:
            if p not in config["policies"]:
                raise ValueError(f"policy {p!r} has no entry in config "
                                 f"{config['name']!r}")
        if traffic.get("merge_policies"):
            pts = [dict(g, policy=p) for p in pols for g in grid]
            self.calls = [Call(tuple(pols), pts, True)]
        else:
            self.calls = [Call((p,), [dict(g, policy=p) for g in grid], False)
                          for p in pols]
        if self.kind == "run" and (len(self.calls) != 1 or
                                   len(self.calls[0].points) != 1):
            raise ValueError("a run job is one policy at one grid point")

    # -- one lane, described for the reference ------------------------------
    def knob(self, policy: str, name: str):
        pk = self.config["policies"][policy]
        if name in pk:
            return pk[name]
        if name in self.config["lock"]:
            return self.config["lock"][name]
        return KNOB_DEFAULTS[name]

    def lanes(self, call: Call, job_seed: int) -> list:
        """Reference lane dicts of ``call`` for the job seeded ``job_seed``
        (seed replicas innermost, as the program's axes list them)."""
        cfg, out = self.config, []
        m, e, lk = cfg["machine"], cfg["epoch"], cfg["lock"]
        reps = range(self.replicas) if self.replicas else [None]
        for pt in call.points:
            pol = pt["policy"]
            for r in reps:
                lane = dict(
                    policy=pol, policies=list(call.policies),
                    n=m["n_cores"], n_active=int(pt.get("n_cores",
                                                        m["n_cores"])),
                    big=m["big"], speed_cs=m["speed_cs"],
                    speed_nc=m["speed_nc"],
                    seg_noncrit_us=e["seg_noncrit_us"],
                    seg_cs_us=e["seg_cs_us"], seg_lock=e["seg_lock"],
                    inter_epoch_us=e["inter_epoch_us"],
                    n_locks=e["n_locks"], sim_time_us=self.sim_time_us,
                    seed=job_seed if r is None else derive(job_seed, r),
                    default_window_us=lk["default_window_us"],
                    max_window_us=lk["max_window_us"], pct=lk["pct"],
                    epcap=lk["epcap"], max_events=lk["max_events"])
                for k in LANE_KNOBS + POLICY_KNOBS:
                    lane[k] = self.knob(pol, k)
                lane.update(self.keys)
                out.append(lane)
        return out

    # -- the same lanes, as the program's configuration and axes ------------
    def sim_config(self, call: Call):
        from repro.core import simlock as sl
        cfg, lk = self.config, self.config["lock"]
        m, e = cfg["machine"], cfg["epoch"]
        first = call.policies[0]
        kw = {}
        for p in call.policies:
            for k in POLICY_KNOBS:
                if k in cfg["policies"][p] or k in _OWNER.get(p, ()):
                    kw[k] = self.knob(p, k)
        return sl.SimConfig(
            policy=first, n_cores=m["n_cores"], big=tuple(m["big"]),
            speed_cs=tuple(m["speed_cs"]), speed_nc=tuple(m["speed_nc"]),
            seg_noncrit_us=tuple(e["seg_noncrit_us"]),
            seg_cs_us=tuple(e["seg_cs_us"]), seg_lock=tuple(e["seg_lock"]),
            inter_epoch_us=e["inter_epoch_us"], n_locks=e["n_locks"],
            w_big=self.knob(first, "w_big"),
            prop_n=self.knob(first, "prop_n"),
            default_window_us=lk["default_window_us"],
            max_window_us=lk["max_window_us"], pct=lk["pct"],
            epcap=lk["epcap"], max_events=lk["max_events"],
            sim_time_us=self.sim_time_us,
            policy_kw=tuple(sorted(kw.items())), **self.keys)

    def axes(self, call: Call, lanes: list) -> dict:
        """Zipped sweep axes, one entry per lane."""
        ax = {}
        if call.merged:
            ax["policy"] = [ln["policy"] for ln in lanes]
        if "n_cores" in self.traffic["axes"]:
            ax["n_cores"] = [ln["n_active"] for ln in lanes]
        if call.merged:
            for k in LANE_KNOBS:
                ax[k] = [ln[k] for ln in lanes]
        if self.replicas:
            ax["seed"] = [ln["seed"] for ln in lanes]
        return ax


def _keys(config: dict) -> dict:
    """The configuration's ``keys`` block as ``n_keys`` and ``zipf_theta``
    for the program and the reference; empty for a key-off configuration.
    Refuses a block no run could honour."""
    keys = config.get("keys")
    if keys is None:
        return {}
    name = config["name"]
    if set(keys) != {"n_keys", "zipf_theta"}:
        raise ValueError(f"config {name!r}: the keys block holds exactly "
                         f"n_keys and zipf_theta, not {sorted(keys)}")
    n_keys, theta = keys["n_keys"], keys["zipf_theta"]
    n_locks = config["epoch"]["n_locks"]
    if isinstance(n_keys, bool) or not isinstance(n_keys, int) \
            or n_keys < n_locks:
        raise ValueError(f"config {name!r}: n_keys {n_keys!r} is not a "
                         f"whole number of at least n_locks ({n_locks}); "
                         "every bucket lock needs a key")
    if isinstance(theta, bool) or not isinstance(theta, (int, float)) \
            or not math.isfinite(theta) or theta < 0:
        raise ValueError(f"config {name!r}: zipf_theta {theta!r} is not a "
                         "finite number of at least 0")
    return {"n_keys": n_keys, "zipf_theta": float(theta)}


_OWNER = {"shfl": ("shfl_bound",), "dvfs_race": ("race_bound",),
          "ks_erew": ("erew_bound",), "ks_crew": ("crew_bound", "crew_wfrac"),
          "ks_jbsq": ("jbsq_k",)}


def plan_for(name: str, root: Path = REPO, sim_time_us=None) -> Plan:
    """The plan of workload ``name`` in ``root``'s BENCHMARK.json: its
    configuration file as the manifest names it, its traffic mix by name."""
    man = manifest(root)
    w = workload(name, root)
    cfg_entry = next(c for c in man["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    return Plan(config, load_json("traffic", w["traffic"], root / "bench"),
                chips=int(w["chips"]), sim_time_us=sim_time_us)
