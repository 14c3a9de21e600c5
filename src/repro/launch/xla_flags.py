"""Pre-jax-import ``XLA_FLAGS`` composition, shared by every entry point
(benchmark drivers, the dry-run CLI, the test session).

Never imports jax — these knobs are only effective when set *before* the
first jax import.  Flags are **prepended**: XLA's flag parser stops at
the first token without a ``--`` prefix, so anything appended after a
caller's bare token (e.g. a stray ``intra_op_parallelism_threads=1``)
would be silently dropped.
"""

from __future__ import annotations

import os

HOST_DEVICE_FLAG = "xla_force_host_platform_device_count"


def cpu_platform() -> bool:
    """Whether JAX is pinned to the CPU (``JAX_PLATFORMS=cpu``) — the
    only case in which the CPU threading flags and virtual host devices
    apply.  Read from the environment because it must be known before
    jax is imported; on a TPU host the chips are the devices."""
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    return first.strip().lower() == "cpu"


def prepend(*flags: str) -> None:
    """Add ``flags`` to XLA_FLAGS, skipping any whose name (the part
    before ``=``) the caller already set — the environment wins.  The
    result is reordered so every ``--``-prefixed flag precedes any bare
    token (ours or the caller's): the parser would silently drop flags
    after the first bare token otherwise."""
    cur = os.environ.get("XLA_FLAGS", "").split()
    names = {t.split("=", 1)[0].lstrip("-") for t in cur}
    toks = [f for f in flags
            if f.split("=", 1)[0].lstrip("-") not in names] + cur
    os.environ["XLA_FLAGS"] = " ".join(
        [t for t in toks if t.startswith("--")] +
        [t for t in toks if not t.startswith("--")])


def ensure_host_devices(n) -> None:
    """Virtualize ``n`` host-platform devices (CPU containers standing in
    for a real mesh).  No-op when the caller already pinned a count."""
    prepend(f"--{HOST_DEVICE_FLAG}={n}")


def argv_device_count(argv, default):
    """Read ``--devices N`` / ``--devices=N`` from raw ``argv`` — needed
    before argparse can run because jax must not be imported yet."""
    for i, a in enumerate(argv):
        if a == "--devices" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--devices="):
            return a.split("=", 1)[1]
    return default
