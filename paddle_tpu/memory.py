"""Device-memory observability (VERDICT r2 #10; ref capability:
``memory/allocation/allocator_facade.h`` stats +
``platform/flags.cc:370-391`` memory-fraction flags +
``memory/allocation/retry_allocator.h`` OOM handling).

On TPU, HBM allocation belongs to XLA — the framework can't (and
shouldn't) re-implement the arena.  What the reference's allocator stack
actually gives users is *observability*: what is resident, how big, and
what was live when an OOM hit.  This module provides that:

- ``summary(scope)``     — per-var device bytes of live scope arrays,
  plus anonymous (non-scope) live arrays, sorted by size
- ``device_memory_stats()`` — the runtime allocator's own counters
  (bytes_in_use, peak_bytes_in_use, bytes_limit) where the backend
  exposes them (TPU does; CPU returns {})
- the executor appends ``summary()`` to RESOURCE_EXHAUSTED errors, so an
  on-chip OOM names the tensors that were resident (executor.py).
"""

from __future__ import annotations

from typing import Optional

__all__ = ["summary", "device_memory_stats", "live_bytes"]


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:8.2f} {unit}"
        n /= 1024
    return f"{n:.2f} TiB"


def _live_device_arrays():
    import jax
    out = []
    for a in jax.live_arrays():
        try:
            if a.is_deleted():
                continue
            out.append(a)
        except Exception:
            continue
    return out


def live_bytes() -> int:
    """Total bytes of all live device arrays in the process."""
    return sum(a.nbytes for a in _live_device_arrays())


def device_memory_stats(device=None) -> dict:
    """The backend allocator's counters for one device (TPU exposes
    bytes_in_use / peak_bytes_in_use / bytes_limit; CPU gives {})."""
    import jax
    dev = device if device is not None else jax.devices()[0]
    try:
        return dict(dev.memory_stats() or {})
    except Exception:
        return {}


def summary(scope: Optional[object] = None, max_rows: int = 40) -> str:
    """Human-readable residency report: scope vars (named) first, then
    anonymous live arrays (jit temporaries, donated-buffer survivors),
    largest first, with totals and allocator counters."""
    from .framework.scope import global_scope
    scope = scope if scope is not None else global_scope()

    live = _live_device_arrays()
    by_id = {id(a): a for a in live}
    named = []
    seen = set()
    for name, val in scope.items():
        if id(val) in by_id:
            named.append((name, val))
            seen.add(id(val))
    anon = [a for a in live if id(a) not in seen]

    named.sort(key=lambda kv: -kv[1].nbytes)
    anon.sort(key=lambda a: -a.nbytes)

    lines = ["=== paddle_tpu device memory summary ==="]
    total_named = sum(v.nbytes for _, v in named)
    total_anon = sum(a.nbytes for a in anon)
    lines.append(f"scope vars: {len(named)}  ({_fmt_bytes(total_named).strip()})"
                 f"   anonymous arrays: {len(anon)}  "
                 f"({_fmt_bytes(total_anon).strip()})")
    for name, v in named[:max_rows]:
        dev = next(iter(v.devices())) if hasattr(v, "devices") else "?"
        lines.append(f"  {_fmt_bytes(v.nbytes)}  {str(v.dtype):>9s} "
                     f"{str(v.shape):>20s}  {name}  [{dev}]")
    if len(named) > max_rows:
        rest = sum(v.nbytes for _, v in named[max_rows:])
        lines.append(f"  {_fmt_bytes(rest)}  … {len(named) - max_rows} "
                     "more scope vars")
    for a in anon[:8]:
        lines.append(f"  {_fmt_bytes(a.nbytes)}  {str(a.dtype):>9s} "
                     f"{str(a.shape):>20s}  <anonymous>")
    if len(anon) > 8:
        rest = sum(a.nbytes for a in anon[8:])
        lines.append(f"  {_fmt_bytes(rest)}  … {len(anon) - 8} more "
                     "anonymous arrays")
    stats = device_memory_stats()
    if stats:
        parts = []
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if k in stats:
                parts.append(f"{k}={_fmt_bytes(stats[k]).strip()}")
        if parts:
            lines.append("allocator: " + "  ".join(parts))
    for tag, plan in hbm_plans().items():
        lines.append(
            f"hbm plan [{tag[:48]}]: peak "
            f"{_fmt_bytes(plan['peak_bytes']).strip()} "
            f"(args {_fmt_bytes(plan['argument_bytes']).strip()}, temps "
            f"{_fmt_bytes(plan['temp_bytes']).strip()}, out "
            f"{_fmt_bytes(plan['output_bytes']).strip()}, aliased "
            f"-{_fmt_bytes(plan['alias_bytes']).strip()})")
    lines.append(f"total live device bytes: "
                 f"{_fmt_bytes(total_named + total_anon).strip()}")
    return "\n".join(lines)


# --- compiled-executable HBM plans (ref allocator_facade.h stats) ----------
# device.memory_stats() counts the whole chip; the per-executable footprint
# comes from the XLA buffer assignment of each compiled step: the executor
# records memory_analysis() here when
# PADDLE_TPU_RECORD_HBM=1 (framework/executor.py _CompiledBlock.__call__).

_HBM_PLANS: dict = {}


def record_hbm_plan(tag: str, ma) -> str:
    """Store one executable's memory_analysis; returns the tag the plan
    was stored under (suffixed on collision — callers reading the entry
    back must use the RETURNED tag, not the one they passed)."""
    # distinct compiled blocks can share a fetch list (startup programs
    # all tag '<block>') — suffix instead of silently overwriting
    if tag in _HBM_PLANS:
        n = 2
        while f"{tag}#{n}" in _HBM_PLANS:
            n += 1
        tag = f"{tag}#{n}"
    arg = int(getattr(ma, "argument_size_in_bytes", 0))
    out = int(getattr(ma, "output_size_in_bytes", 0))
    tmp = int(getattr(ma, "temp_size_in_bytes", 0))
    alias = int(getattr(ma, "alias_size_in_bytes", 0))
    code = int(getattr(ma, "generated_code_size_in_bytes", 0))
    _HBM_PLANS[tag] = {
        "argument_bytes": arg, "output_bytes": out, "temp_bytes": tmp,
        "alias_bytes": alias, "generated_code_bytes": code,
        # donated (aliased) outputs reuse their argument buffers
        "peak_bytes": arg + out + tmp + code - alias,
    }
    return tag


def hbm_plans() -> dict:
    return dict(_HBM_PLANS)


def _is_oom_error(e: BaseException) -> bool:
    s = str(e)
    return ("RESOURCE_EXHAUSTED" in s or "Out of memory" in s
            or "out of memory" in s or "OOM" in s)
