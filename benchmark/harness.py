"""The data-driven core of the benchmark.

``BENCHMARK.json`` is the index.  A cell names a configuration and a traffic
mix; everything that belongs to one of them is a file found by that name:

    configs/<config>.json          the sizes as run, source, reduced, assumed
    models/<config>.py             adapter: builds it through public entry points
    reference/<config>.py          plain float32 jax.numpy reference
    traffic/<traffic>.json         parameters of the mix (or "same_as": another
                                   mix under a second name); its "kind" names
    generators/<kind>.py           the one general loop for that kind of mix
    layer_metrics/<metric>.py      one reader: read(inputs) -> number or None

Nothing here lists them, so a later PR adds files and appends entries to
``BENCHMARK.json`` without editing a file that is there.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import List, Optional

from . import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: seconds of the measured window that the profiler records in a traced run
TRACE_SECONDS = 3.0
#: the profiler's output, inside the checkout and listed in .gitignore
TRACE_DIR = os.path.join(ROOT, ".cache", "bench_trace")


def log(msg: str) -> None:
    """An earlier output line (never the last one)."""
    print(f"bench: {msg}", flush=True)


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def load_traffic(name: str) -> dict:
    """``traffic/<name>.json``; a file that says ``same_as`` is that other
    mix under this name, with its own keys laid over it."""
    t = load_json(os.path.join("benchmark", "traffic", name + ".json"))
    if "same_as" in t:
        t = dict(load_traffic(t["same_as"]), **t)
    return t


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"benchmark/{kind}/{name}.py does not exist")
    modname = "benchmark.%s.%s" % (kind, name.replace(".", "_"))
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"{what} {name!r} is not in BENCHMARK.json "
                   f"(known: {[e['name'] for e in entries]})")


def metrics_of_cell(spec: dict, section: str, cell: str) -> List[dict]:
    """The metrics of ``section`` that ``cell`` reports: those without a
    ``workloads`` key and those that list it."""
    return [m for m in spec[section]
            if "workloads" not in m or cell in m["workloads"]]


def np_seed(seed: int) -> int:
    """``--seed`` may exceed 32 signed bits; numpy takes 32 unsigned."""
    return int(seed) % (2 ** 32)


def exe_seed(seed: int) -> int:
    return int(seed) % (2 ** 31 - 1) + 1


# -- device -------------------------------------------------------------------

def memory_peak_bytes() -> int:
    """Peak device memory of the fullest local chip, as its allocator reports
    it now.  The TPU allocator counts two regions apart: the heap of live
    buffers (``peak_bytes_in_use``: weights, optimizer state, KV pools, the
    ring) and the region reserved for the loaded programs' temporaries
    (``peak_bytes_reserved``: activations, scratch).  What has to fit is their
    sum (bytes_limit - that sum is the largest block still free), so the sum
    is what is reported; a backend without the second key reports the
    first."""
    import jax
    peak = 0
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    return peak


def device_block(peak: Optional[int] = None) -> dict:
    """The device as JAX reports it; ``peak`` is the generator's reading at
    window close where it took one (what is checked against the reference
    after the window is not the system's memory)."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": memory_peak_bytes() if peak is None
            else int(peak)}


def require_tpu(chips: int) -> None:
    """Exit non-zero, printing no result, unless JAX's default backend is a
    TPU with at least ``chips`` chips."""
    import jax
    try:
        backend = jax.default_backend()
        n = len(jax.devices())
    except Exception as e:                     # no backend at all
        sys.exit(f"bench: JAX found no accelerator: {e}")
    if backend != "tpu":
        sys.exit(f"bench: no TPU (JAX's default backend is {backend!r}); "
                 "a device metric is never taken from another backend")
    if n < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX found {n}")


# -- set-up clock -------------------------------------------------------------

class SetupClock:
    """Splits set-up (process start to window open) into named phases."""

    def __init__(self, t_process_start: float):
        self.t0 = t_process_start
        self._last = t_process_start
        self.phases: List[list] = []

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.phases.append([name, now - self._last])
        self._last = now

    def report(self) -> str:
        return ", ".join(f"{n} {s:.2f}s" for n, s in self.phases)


class CompileMeter:
    """Backend compiles and persistent-cache hits, from ``jax.monitoring``
    (copied from chip_smoke.py): a window in which ``compiles`` rises
    compiled something."""

    def __init__(self):
        import jax.monitoring as m
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        m.register_event_duration_secs_listener(self._on_duration)
        m.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# -- program spans ------------------------------------------------------------

class SpanWindow:
    """The program's own tracer ring (``monitor.TRACER``) over one window,
    on the ``perf_counter`` clock.  ``open`` clears the ring and writes a
    mark that ties the ring's clock to ``perf_counter``; ``close`` returns
    the spans as ``(name, t0, t1, args)``."""

    MARK = "bench.window_open"

    def __init__(self):
        from paddle_tpu import monitor
        self._tracer = monitor.TRACER
        self._t_mark = None

    def open(self) -> None:
        self._tracer.clear()
        self._t_mark = time.perf_counter()
        self._tracer.add_complete(self.MARK, "bench", self._t_mark,
                                  self._t_mark)

    def close(self) -> List[tuple]:
        evs = self._tracer.chrome_events()
        mark = next((e for e in evs if e.get("name") == self.MARK), None)
        if mark is None:
            return []
        off = self._t_mark - mark["ts"] / 1e6
        out = []
        for e in evs:
            if e.get("ph") != "X" or e["name"] == self.MARK:
                continue
            t0 = e["ts"] / 1e6 + off
            out.append((e["name"], t0, t0 + e.get("dur", 0.0) / 1e6,
                        e.get("args") or {}))
        return out


# -- profiler -----------------------------------------------------------------

class DeviceTrace:
    """One JAX profiler recording with the benchmark's marks inside."""

    def __init__(self, cell: str):
        self.dir = os.path.join(TRACE_DIR, cell)
        self.t_a = self.t_b = None

    @staticmethod
    def mark(tag: str) -> float:
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.MARK, t_perf=repr(t),
                                          tag=tag):
            pass
        return t

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        kw = {}
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # no per-call python events
            opts.host_tracer_level = 1        # TraceMe marks only
            kw["profiler_options"] = opts
        except Exception:
            pass
        jax.profiler.start_trace(self.dir, **kw)
        self.t_a = self.mark("a")

    def stop(self) -> None:
        import jax
        self.t_b = self.mark("b")
        jax.profiler.stop_trace()

    def reduce(self) -> Optional[dict]:
        """The reduced trace of the window between the two marks, with the
        raw events' clock offset (``offset_ns``), or None when the profiler
        left no file."""
        paths = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths or self.t_b is None:
            return None
        events = trace_reduce.load_xplane(paths[-1])
        off = trace_reduce.clock_offset_ns(events)
        if off is None:
            log("trace holds no bench_mark: window taken from the device "
                "events alone")
            red = trace_reduce.reduce_events(events)
            red["offset_ns"] = None
            return red
        window = (int(self.t_a * 1e9 + off), int(self.t_b * 1e9 + off))
        red = trace_reduce.reduce_events(events, window)
        red["offset_ns"] = off
        red["path"] = paths[-1]
        return red


def breakdown(red: dict, spans: List[tuple]) -> dict:
    """The ten device operations with most time, and the idle gaps of the
    first device by the program span that covered them."""
    out = {"device_ops": trace_reduce.top(red["ops"]), "idle_gaps": []}
    off = red.get("offset_ns")
    if red["devices"] and off is not None:
        first = sorted(red["devices"])[0]
        host = [(n, int(t0 * 1e9 + off), int(t1 * 1e9 + off))
                for n, t0, t1, _ in spans]
        out["idle_gaps"] = trace_reduce.top(trace_reduce.attribute_gaps(
            red["devices"][first]["gaps"], host))
    return out


# -- one run ------------------------------------------------------------------

class Context:
    """What a generator gets: the cell's data and the harness's tools."""

    def __init__(self, cell, config, traffic, seed, seconds, trace, clock,
                 on_chip):
        self.cell: dict = cell
        self.config: dict = config
        self.traffic: dict = traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.clock: SetupClock = clock
        self.on_chip = bool(on_chip)
        self.meter = CompileMeter()
        self.model = load_module("models", cell["config"])
        self.reference = load_module("reference", cell["config"])
        self.spans = SpanWindow()
        self.device_trace = DeviceTrace(cell["name"]) if trace else None
        self.trace_seconds = min(TRACE_SECONDS, self.seconds / 2.0)


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_process_start: Optional[float] = None, on_chip: bool = True,
             config: Optional[dict] = None, traffic: Optional[dict] = None,
             spec: Optional[dict] = None) -> dict:
    """Run one cell and return the contract's result object.  ``config`` /
    ``traffic`` replace the cell's files (the CPU rehearsals pass toy sizes);
    ``on_chip=False`` skips what only a TPU has."""
    spec = spec or load_spec()
    cell = find(spec["workloads"], cell_name, "workload")
    cfg_entry = find(spec["configs"], cell["config"], "config")
    config = config or load_json(cfg_entry["file"])
    traffic = traffic or load_traffic(cell["traffic"])
    clock = SetupClock(t_process_start if t_process_start is not None
                       else time.perf_counter())
    ctx = Context(cell, config, traffic, seed, seconds, trace, clock, on_chip)
    clock.mark("import")
    gen = load_module("generators", traffic["kind"])
    out = gen.run(ctx)
    log(f"set-up split: {clock.report()} (total {out['setup_s']:.2f}s); "
        f"compiles {ctx.meter.compiles} ({ctx.meter.compile_s:.1f}s), "
        f"cache hits {ctx.meter.hits}, misses {ctx.meter.misses}")

    peaks = None
    device = device_block(out.get("memory_peak_bytes"))
    import jax
    log("memory_stats of device 0 at exit: " + json.dumps(
        {k: int(v) for k, v in
         (jax.local_devices()[0].memory_stats() or {}).items()}))
    if on_chip:
        from . import flops
        peaks = flops.load_peaks(device["kind"])
    # "compared": what decided ``correct``, each number beside its limit;
    # run.py repeats it as the last lines of standard error
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": {}, "device": device,
              "compared": list(out.get("compared", []))}
    e2e = dict(out["e2e"], setup_s=out["setup_s"])
    if not trace:
        for m in metrics_of_cell(spec, "end_to_end", cell_name):
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
        return result

    red = ctx.device_trace.reduce() if ctx.device_trace else None
    spans = out.get("spans", [])
    inputs = {"spans": spans, "counters": out.get("counters", {}),
              "facts": out.get("facts", {}), "e2e": e2e, "trace": red,
              "trace_window": (ctx.device_trace.t_a, ctx.device_trace.t_b)
              if ctx.device_trace else None,
              "config": config, "traffic": traffic, "peaks": peaks,
              "chips": cell["chips"]}
    for m in metrics_of_cell(spec, "per_layer", cell_name):
        value = load_module("layer_metrics", m["name"]).read(inputs)
        if value is not None:
            result["metrics"][m["name"]] = {"value": float(value),
                                            "unit": m["unit"]}
    if red is not None and red["n_devices"]:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = breakdown(red, spans)
    return result
