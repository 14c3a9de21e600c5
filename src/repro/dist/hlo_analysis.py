"""Analytic FLOP/byte models + HLO collective accounting for dry-runs.

Two independent estimates that the dry-run / roofline compare:

* *analytic* — closed-form transformer arithmetic from the config (the
  6ND rule plus attention terms), independent of XLA;
* *measured* — XLA's ``cost_analysis`` and the collective schedule parsed
  out of the compiled HLO text (:func:`collective_stats`).
"""

from __future__ import annotations

import re

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4,
                "u32": 4, "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# `%x = f32[256,1024]{1,0} all-reduce(...)` — shape of the collective result.
_OP_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")

# Group size out of either HLO spelling: iota `[n_groups,size]<=[...]` or
# explicit `{{0,1,...},{...}}` (size = elements of the first group).
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[\d+,(\d+)\]")
_EXPLICIT_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]*)\}")


def _ring_link_bytes(op: str, result_bytes: float, n: int) -> float:
    """Per-device link traffic of one collective under the standard ring
    algorithms, from the HLO *result* shape (all-reduce/all-gather results
    are full-size, reduce-scatter results are the per-device shard)."""
    if op == "collective-permute":
        return result_bytes                      # one hop per device
    if n <= 1:
        return 0.0
    if op == "all-reduce":
        return result_bytes * 2.0 * (n - 1) / n  # reduce-scatter + gather
    if op == "reduce-scatter":
        return result_bytes * (n - 1)            # input is n shards
    # all-gather / all-to-all: each device ships (n-1)/n of the result
    return result_bytes * (n - 1) / n


def _param_count(cfg) -> float:
    d, dh = cfg.d_model, cfg.head_dim
    attn = d * dh * (cfg.n_heads * 2 + cfg.n_kv_heads * 2)
    n_mats = 3 if cfg.activation in ("swiglu", "geglu") else 2
    mlp = n_mats * d * cfg.d_ff
    if cfg.n_experts:
        mlp = cfg.n_experts * mlp + d * cfg.n_experts   # experts + router
    per_layer = attn + mlp + 2 * d
    embed = cfg.vocab * d * (1 if cfg.tie_embeddings else 2)
    return cfg.n_layers * per_layer + embed + d


def _active_param_count(cfg) -> float:
    """Params touched per token (MoE: top_k of n_experts)."""
    if not cfg.n_experts:
        return _param_count(cfg)
    dense = _param_count(cfg)
    n_mats = 3 if cfg.activation in ("swiglu", "geglu") else 2
    expert = n_mats * cfg.d_model * cfg.d_ff
    inactive = cfg.n_layers * (cfg.n_experts - cfg.top_k) * expert
    return dense - inactive


def analytic_model_flops(cfg, shape) -> float:
    """Estimated *model* FLOPs for one global step/call of ``shape``.

    train: 6·N_active·tokens (fwd+bwd) + attention scores;
    prefill: 2·N·tokens + attention; decode: 2·N·batch (one token each).
    """
    b, s = shape.global_batch, shape.seq_len
    n_act = _active_param_count(cfg)
    attn_layers = sum(1 for k in cfg.blocks() if k in ("attn", "local_attn"))
    if shape.kind == "decode":
        tokens = b                       # one token per sequence
        attn = 4.0 * tokens * s * cfg.attn_q_dim * attn_layers
        return 2.0 * n_act * tokens + attn
    tokens = float(b) * s
    attn = 4.0 * tokens * s * cfg.attn_q_dim * attn_layers
    if cfg.causal:
        attn *= 0.5
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n_act * tokens + (3.0 if shape.kind == "train" else 1.0) \
        * attn


def analytic_hbm_bytes(cfg, shape, rules=None) -> float:
    """Minimum HBM traffic per call: parameters once + KV-cache sweep
    (decode) or activations (train/prefill, one residual stream pass).

    Global bytes by default; pass ``rules`` (a ``dist.sharding.Rules``)
    to divide each component by its actual shard count on that mesh —
    per-device traffic, the roofline's memory term.  Weight reads use
    the FULL parameter count, not the top_k-active count: every
    standard shape carries >= n_experts tokens per step, so each device
    streams its whole resident expert shard (gating thins compute, not
    the weight bytes read).
    """
    pbytes = {"float32": 4, "bfloat16": 2, "float16": 2}.get(
        cfg.param_dtype, 4)
    abytes = {"float32": 4, "bfloat16": 2, "float16": 2}.get(cfg.dtype, 2)
    b, s = shape.global_batch, shape.seq_len
    w_sh = c_sh = a_sh = 1
    if rules is not None:
        w_sh = rules.num_shards("ff")            # tensor-parallel weights
        c_sh = (rules.num_shards("cache_batch")
                * rules.num_shards("kv_heads"))  # KV: batch x heads
        a_sh = rules.num_shards("batch")         # activations: data-par
    params = _param_count(cfg) * pbytes / w_sh
    attn_layers = sum(1 for k in cfg.blocks() if k in ("attn", "local_attn"))
    if shape.kind == "decode":
        kv = 2.0 * b * s * cfg.n_kv_heads * cfg.head_dim * abytes \
            * attn_layers / c_sh
        return params + kv + b * cfg.d_model * abytes * cfg.n_layers / a_sh
    acts = float(b) * s * cfg.d_model * abytes * cfg.n_layers / a_sh
    return params * (3 if shape.kind == "train" else 1) + acts


def xla_cost(compiled) -> dict:
    """XLA ``cost_analysis`` of a compiled executable as a plain dict."""
    return dict(compiled.cost_analysis() or {})


def collective_stats(hlo_text: str, n_devices: int | None = None) -> dict:
    """Parse the compiled HLO: per-collective op counts and result bytes.

    Returns ``{op: {"count", "bytes", "link_bytes"}}`` per collective kind
    plus flat ``"total_bytes"``/``"total_count"`` rollups and a nested
    ``"total"`` rollup.  ``bytes`` is raw result-shape bytes;
    ``link_bytes`` applies the ring-algorithm factors (2(n-1)/n etc.) with
    the group size parsed from each op's ``replica_groups`` — per-device
    link traffic, the roofline's collective term.  ``n_devices`` is the
    group-size fallback for ops with no parsable ``replica_groups``.
    """
    out = {op: {"count": 0, "bytes": 0.0, "link_bytes": 0.0}
           for op in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        dtype, dims, op = m.group(1), m.group(2), m.group(3)
        # -start/-done pairs describe one collective; count starts only.
        if m.group(0).rstrip("(").endswith("-done"):
            continue
        nelem = 1
        for d in dims.split(","):
            if d:
                nelem *= int(d)
        rbytes = nelem * _DTYPE_BYTES.get(dtype, 4)
        line = hlo_text[m.start():hlo_text.find("\n", m.end())]
        gm = _IOTA_GROUPS_RE.search(line)
        if gm:
            gsize = int(gm.group(1))
        else:
            gm = _EXPLICIT_GROUPS_RE.search(line)
            gsize = (len(gm.group(1).split(",")) if gm and gm.group(1)
                     else (n_devices or 1))
        out[op]["count"] += 1
        out[op]["bytes"] += rbytes
        out[op]["link_bytes"] += _ring_link_bytes(op, rbytes, gsize)
    out["total_count"] = sum(out[op]["count"] for op in _COLLECTIVES)
    out["total_bytes"] = sum(out[op]["bytes"] for op in _COLLECTIVES)
    out["total"] = {
        "count": out["total_count"], "bytes": out["total_bytes"],
        "link_bytes": sum(out[op]["link_bytes"] for op in _COLLECTIVES)}
    out["ops"] = {op: out[op] for op in _COLLECTIVES if out[op]["count"]}
    return out
