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

import collections
import threading
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
    plans = list(hbm_plans().items())
    if len(plans) > SUMMARY_PLANS:
        lines.append(f"hbm plans: {len(plans)} recorded, the newest "
                     f"{SUMMARY_PLANS} follow")
    for tag, plan in plans[-SUMMARY_PLANS:]:
        lines.append(f"hbm plan [{plan.get('block', 'other')}] "
                     f"[{tag[:48]}]: " + format_plan(plan))
    lines.append(f"total live device bytes: "
                 f"{_fmt_bytes(total_named + total_anon).strip()}")
    return "\n".join(lines)


# --- compiled-executable HBM plans (ref allocator_facade.h stats) ----------
# device.memory_stats() counts the whole chip; the per-executable footprint
# comes from the XLA buffer assignment of each compiled step: the executor
# records memory_analysis() of every block where it compiles
# (framework/executor.py ``_record_block_plan`` -> hbm.record_xla_plan).

#: the newest plans, oldest first; bounded, so a process that compiles
#: without end (a server re-bucketing, a test session) holds a window
_HBM_PLANS: "collections.OrderedDict[str, dict]" = collections.OrderedDict()
_PLANS_LOCK = threading.Lock()
MAX_HBM_PLANS = 256
#: a tag is a metric label value: cut to this many characters
MAX_TAG_CHARS = 64
#: how many of them the residency summary prints
SUMMARY_PLANS = 8


def plan_parts(ma) -> dict:
    """The five regions of one executable's ``memory_analysis()`` (bytes
    on ONE device) and the peak they add up to.

    ``temp_bytes`` is the temporaries AT THE EXECUTABLE'S PEAK where the
    backend reports one (``peak_memory_in_bytes``, which the TPU compiler
    gives: arguments + unaliased outputs + the temporaries live at the
    fullest instant), else ``temp_size_in_bytes``.  On the TPU the two
    differ in steps compiled to the chip's brim: ``temp_size_in_bytes``
    read 8.60 GB for Trinity-Mini's step where the same compile's
    buffer assignment reserves 7.25 GB and arguments + that 8.60 would not
    fit the chip the step runs on (PERF.md section 6, PR 51); it stays in
    the entry as ``xla_temp_bytes``."""
    arg = int(getattr(ma, "argument_size_in_bytes", 0))
    out = int(getattr(ma, "output_size_in_bytes", 0))
    tmp = xla_tmp = int(getattr(ma, "temp_size_in_bytes", 0))
    alias = int(getattr(ma, "alias_size_in_bytes", 0))
    code = int(getattr(ma, "generated_code_size_in_bytes", 0))
    xla_peak = int(getattr(ma, "peak_memory_in_bytes", 0) or 0)
    # the CPU backend's "peak" is arguments + outputs to the byte and says
    # nothing of temporaries: taken only where it is something else
    if xla_peak != arg + out and 0 < xla_peak - arg - (out - alias) < tmp:
        tmp = xla_peak - arg - (out - alias)
    return {
        "argument_bytes": arg, "output_bytes": out, "temp_bytes": tmp,
        "alias_bytes": alias, "generated_code_bytes": code,
        # donated (aliased) outputs reuse their argument buffers
        "peak_bytes": arg + out + tmp + code - alias,
        "xla_temp_bytes": xla_tmp, "xla_peak_bytes": xla_peak,
    }


def format_plan(plan: dict) -> str:
    """One line for one plan entry: the regions as the per-layer metrics
    name them (arguments, temporaries, outputs - aliased, code) and the
    arguments by class where the entry has them."""
    text = (f"peak {_fmt_bytes(plan['peak_bytes']).strip()} = arguments "
            f"{_fmt_bytes(plan['argument_bytes']).strip()} + temporaries "
            f"{_fmt_bytes(plan['temp_bytes']).strip()} + unaliased outputs "
            f"{_fmt_bytes(plan['output_bytes'] - plan['alias_bytes']).strip()}"
            f" (outputs {_fmt_bytes(plan['output_bytes']).strip()} - aliased "
            f"{_fmt_bytes(plan['alias_bytes']).strip()}) + code "
            f"{_fmt_bytes(plan['generated_code_bytes']).strip()}")
    classes = plan.get("argument_classes")
    if classes:
        text += "; arguments by class: " + ", ".join(
            f"{c} {_fmt_bytes(n).strip()}" for c, n in classes.items())
    return text


def record_hbm_plan(tag: str, ma, **facts):
    """Store one executable's memory_analysis with what the caller knows
    of it (``block``, ``compiled_at``, ``argument_classes``, ``hook_ms``).
    Returns ``(tag, entry, evicted)``: the tag the plan was stored under
    (cut to ``MAX_TAG_CHARS`` and suffixed on collision: read the entry
    back by the RETURNED tag), the stored entry, and the ``(tag, entry)``
    pairs that fell off the window's old end."""
    # distinct compiled blocks can share a fetch list (startup programs
    # all tag '<block>', a re-trace compiles its block again) — suffix
    # instead of silently overwriting
    base = tag = tag[:MAX_TAG_CHARS]
    evicted = []
    with _PLANS_LOCK:
        n = 1
        while tag in _HBM_PLANS:
            n += 1
            tag = f"{base}#{n}"
        entry = _HBM_PLANS[tag] = dict(plan_parts(ma), **facts)
        while len(_HBM_PLANS) > MAX_HBM_PLANS:
            evicted.append(_HBM_PLANS.popitem(last=False))
    return tag, entry, evicted


#: plans nobody has asked for yet: block key -> the call that records it
#: (hbm.record_compiled_plan defers a block whose every ``.compile()``
#: costs seconds); a block's newer compile replaces its older one
_DEFERRED_PLANS: dict = {}


def defer_hbm_plan(key, record) -> None:
    with _PLANS_LOCK:
        _DEFERRED_PLANS[key] = record


def hbm_plans() -> dict:
    """The recorded plans by tag, oldest first; the deferred ones are
    recorded now."""
    with _PLANS_LOCK:
        deferred = list(_DEFERRED_PLANS.values())
        _DEFERRED_PLANS.clear()
    for record in deferred:
        record()
    with _PLANS_LOCK:
        return dict(_HBM_PLANS)


def _is_oom_error(e: BaseException) -> bool:
    s = str(e)
    return ("RESOURCE_EXHAUSTED" in s or "Out of memory" in s
            or "out of memory" in s or "OOM" in s)
