"""Profiled execution: XLA cost analysis + jax.profiler capture.

The engine's compiled/chunked/fused programs are single fused XLA
blobs — there is no per-operator boundary at runtime (the
observe/stats.py design note).  Attribution inside them therefore
comes from the COMPILER, not the interpreter:

- `executable_cost` pulls XLA's cost analysis (FLOPs, bytes accessed)
  off a compiled program — the per-fragment numbers EXPLAIN ANALYZE
  attaches next to the measured wall in compiled/chunked/cluster
  modes, with a roofline-model estimated wall
  (`estimate_wall_ms`) so estimated-vs-measured gaps surface
  scheduling/transfer overheads;
- `maybe_profile` wraps a query in `jax.profiler.trace` when
  `PRESTO_TPU_PROFILE=<dir>` (or the `profile_query` session property)
  is set — the captured xplane maps back to plan node names through
  the `jax.named_scope` annotations the executor emits at every
  operator-lowering site (exec/executor.py).
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Optional


#: roofline peaks for the estimated-wall model: device_kind ->
#: (peak FLOP/s, memory GB/s), each with its source.  A kind that is not
#: here is an error in estimate_wall_ms, never a default; the env vars
#: PRESTO_TPU_PEAK_FLOPS / PRESTO_TPU_HBM_GBPS pin both for a part the
#: table does not know.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    "TPU v5 lite": (197e12, 819.0),
    # the XLA CPU backend: an order of magnitude for one host socket,
    # only so EXPLAIN ANALYZE prints an estimate in CPU tests
    "cpu": (100e9, 20.0),
}


def _normalize(raw) -> Optional[dict]:
    """XLA cost_analysis payload (a dict on the installed jax) ->
    {"flops": float, "bytes_accessed": float, ...extras}."""
    if not isinstance(raw, dict):
        return None
    out = {}
    for k, v in raw.items():
        if not isinstance(v, (int, float)):
            continue
        key = str(k).replace(" ", "_")
        out[key] = float(v)
    if "flops" not in out and "bytes_accessed" not in out:
        return None
    return out


def executable_cost(executable, args=None) -> Optional[dict]:
    """Cost analysis of a compile_cache.Executable (or a bare jitted
    callable).  AOT-compiled executables answer directly; a live-jit
    wrapper needs `args` to lower against (EXPLAIN ANALYZE only — the
    lower+compile there is a diagnostic cost, never on the hot path).
    Returns None when the backend can't answer; never raises."""
    try:
        compiled = getattr(executable, "_compiled", None)
        if compiled is not None:
            return _normalize(compiled.cost_analysis())
        if args is not None:
            lower = getattr(executable, "lower", None)
            if lower is not None:
                return _normalize(lower(*args).compile().cost_analysis())
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        return None
    return None


def merge_costs(costs) -> Optional[dict]:
    """Sum cost dicts across a fragment's program family (chunk loop +
    fold + compact programs all bill the same fragment)."""
    total: dict = {}
    seen = False
    for c in costs:
        if not c:
            continue
        seen = True
        for k, v in c.items():
            total[k] = total.get(k, 0.0) + float(v)
    return total if seen else None


def platform() -> str:
    import jax

    return jax.devices()[0].platform


def device_peaks() -> tuple:
    """(peak FLOP/s, memory bytes/s) of the first device: the env
    overrides, else DEVICE_PEAKS by device_kind; an unknown kind
    raises."""
    import jax

    kind = jax.devices()[0].device_kind
    known = DEVICE_PEAKS.get(kind)
    flops = os.environ.get("PRESTO_TPU_PEAK_FLOPS")
    gbps = os.environ.get("PRESTO_TPU_HBM_GBPS")
    if known is None and (flops is None or gbps is None):
        raise LookupError(
            f"no roofline peaks for device_kind {kind!r}: add it to "
            "observe/profile.DEVICE_PEAKS with its source, or set "
            "PRESTO_TPU_PEAK_FLOPS and PRESTO_TPU_HBM_GBPS")
    return (float(flops if flops is not None else known[0]),
            float(gbps if gbps is not None else known[1]) * 1e9)


def estimate_wall_ms(cost: Optional[dict]) -> Optional[float]:
    """Roofline estimate: max(compute, memory) time for the program's
    FLOPs / bytes at the device's peak rates (device_peaks)."""
    if not cost:
        return None
    peak, bw = device_peaks()
    t_flops = cost.get("flops", 0.0) / max(peak, 1.0)
    t_bytes = cost.get("bytes_accessed", 0.0) / max(bw, 1.0)
    return max(t_flops, t_bytes) * 1e3


def cost_line(cost: Optional[dict], wall_ms: Optional[float] = None,
              note: str = "") -> str:
    """One EXPLAIN ANALYZE attribution line: measured wall + XLA cost
    analysis + roofline estimate."""
    parts = []
    if wall_ms is not None:
        parts.append(f"wall={wall_ms:.2f}ms")
    if cost:
        if "flops" in cost:
            parts.append(f"xla_flops={cost['flops']:,.0f}")
        if "bytes_accessed" in cost:
            parts.append(f"hbm_bytes={cost['bytes_accessed']:,.0f}")
        est = estimate_wall_ms(cost)
        if est is not None:
            parts.append(f"est_wall={est:.2f}ms")
    else:
        parts.append("xla_cost=unavailable"
                     + (f" ({note})" if note else ""))
    return " ".join(parts)


# ---------------------------------------------------------------------------
# jax.profiler capture
# ---------------------------------------------------------------------------


def profile_dir(session=None) -> Optional[str]:
    """Where to write a jax.profiler capture: the `profile_query`
    session property (a directory path; "" / falsy = off) or the
    PRESTO_TPU_PROFILE env var."""
    d = None
    if session is not None:
        try:
            d = session.properties.get("profile_query") or None
        except Exception:
            d = None
    if d is None:
        d = os.environ.get("PRESTO_TPU_PROFILE") or None
    if d in ("0", "off", "false", None):
        return None
    return str(d)


@contextmanager
def maybe_profile(session=None):
    """Wrap a query in jax.profiler.trace when profiling is requested;
    capture failures (unsupported backend, busy profiler) never fail
    the query."""
    d = profile_dir(session)
    if d is None:
        yield None
        return
    try:
        import jax

        os.makedirs(d, exist_ok=True)
        ctx = jax.profiler.trace(d)
        ctx.__enter__()
    except Exception:  # noqa: BLE001 — profiling is best-effort
        yield None
        return
    try:
        yield d
    finally:
        try:
            ctx.__exit__(None, None, None)
        except Exception:  # noqa: BLE001
            pass
