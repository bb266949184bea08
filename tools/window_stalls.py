"""Where a training cell's window stalls: host or device (PERF.md section 7,
rows 24 and 29).

Runs one cell of ``BENCHMARK.json`` through ``benchmark.harness.run_cell`` as
``benchmark/run.py`` does, without the reference checks after the window, and
keeps what the result line drops: the window's spans.  Beside them it records
every pass of Python's garbage collector (``gc.callbacks``) and, from a
sampling thread at 20 Hz, the process's CPU time against the wall clock.  For
every dispatch interval over 1.5 times the median it prints the spans and
collector passes that overlap it and the CPU seconds the process used in it:

* a long ``executor.throttle_wait`` and no CPU time: the host waited for the
  device, the device (or the runtime under it) stalled;
* a long ``executor.dispatch`` or a gap under no span, with CPU time used:
  the host was busy (a collector pass names itself);
* a gap with neither: the process was not scheduled.

    chiprun -- python3 tools/window_stalls.py --workload trinity_mini_lm_s8192 --seed 7

``--threads 1`` adds, from a second sampling thread at 2 Hz, what the
kernel says of the process while it waits (``kernel`` in each stall): how
many of its ticks fell into the stall (none: nothing of the process ran, the
machine itself stood still), the process's threads by name, state and the
kernel function each sleeps in (``/proc/self/task/*/stat`` and ``wchan``:
a thread in an ``ioctl`` of the accelerator's driver waits for the device, one
in ``futex_wait`` for another thread), each a tick inside the stall against a
tick outside, and the counters of ``/proc/stat`` and ``/proc/vmstat`` that
ran five times faster inside (steal and iowait, compaction, reclaim, a
balloon).

``--trace 1`` records the window's last seconds as a traced run of the
benchmark does (the harness's own ``DeviceTrace``, the host's tracer at
``--host_tracer_level``) and adds ``device_gaps``: every interval over 0.2 s
in which the first device ran no operation, where it lies between the
profiler's marks, and what every other line of the trace (the host's
threads, the device's lines that are not operations) holds over it.

One JSON line per run on standard output and in
``chiprun_out/window_stalls.jsonl``.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def kernel_tick():
    """One look at ``/proc``: the process's threads counted by ``(name,
    state, kernel function it sleeps in)``, and the machine's counters."""
    threads = {}
    for tid in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{tid}/stat") as f:
                stat = f.read()
            with open(f"/proc/self/task/{tid}/wchan") as f:
                wchan = f.read().strip()
        except OSError:                          # the thread ended meanwhile
            continue
        name = stat[stat.index("(") + 1:stat.rindex(")")]
        key = (name, stat[stat.rindex(")") + 2], wchan)
        threads[key] = threads.get(key, 0) + 1
    counters = {}
    with open("/proc/stat") as f:
        cpu = f.readline().split()
    for name, value in zip(("user", "nice", "system", "idle", "iowait", "irq",
                            "softirq", "steal"), cpu[1:]):
        counters["cpu." + name] = int(value)
    try:
        with open("/proc/vmstat") as f:
            for line in f:
                name, value = line.split()
                counters[name] = int(value)
    except OSError:
        pass
    return time.perf_counter(), threads, counters


def kernel_over(ticks, a, b):
    """What :func:`kernel_tick` saw inside ``[a, b]`` against outside."""
    inside = [t for t in ticks if a <= t[0] <= b]
    outside = [t for t in ticks if not a <= t[0] <= b]
    out = {"ticks_inside": len(inside), "ticks_outside": len(outside)}
    if not inside or len(outside) < 2:
        return out

    def per_tick(some):
        total = {}
        for _, threads, _ in some:
            for key, n in threads.items():
                total[key] = total.get(key, 0) + n
        return {k: n / len(some) for k, n in total.items()}
    now, usual = per_tick(inside), per_tick(outside)
    by_change = sorted(set(now) | set(usual),
                       key=lambda k: usual.get(k, 0) - now.get(k, 0))
    out["threads_inside_vs_outside"] = [
        [" ".join(k), round(now.get(k, 0), 2), round(usual.get(k, 0), 2)]
        for k in by_change[:10] + by_change[-5:]
        if abs(now.get(k, 0) - usual.get(k, 0)) >= 0.2]
    out["threads_inside_not_in_futex"] = [
        [" ".join(k), round(n, 2), round(usual.get(k, 0), 2)]
        for k, n in sorted(now.items(), key=lambda kv: -kv[1])
        if not k[2].startswith("futex")][:14]
    # counters: the rate over the stall (the ticks around it) against the
    # rate over the rest of the window
    before = [t for t in ticks if t[0] < a]
    after = [t for t in ticks if t[0] > b]
    if before and after and len(ticks) > 3:
        (t0, _, c0), (t1, _, c1) = before[-1], after[0]
        (s0, _, d0), (s1, _, d1) = ticks[0], ticks[-1]
        faster = []
        for name in c0:
            stall = (c1.get(name, 0) - c0[name]) / (t1 - t0)
            rest = ((d1.get(name, 0) - d0[name]) - (c1.get(name, 0)
                                                    - c0[name])) \
                / max((s1 - s0) - (t1 - t0), 1e-9)
            if stall > 5 * max(rest, 0.2):
                faster.append([name, round(stall, 1), round(rest, 1)])
        out["counters_per_s_inside_vs_rest"] = sorted(
            faster, key=lambda r: -r[1])[:16]
    return out


def stalls(spans, passes, samples, factor=1.5, ticks=()):
    """The dispatch intervals over ``factor`` times their median, each with
    what overlapped it: ``spans`` as the harness keeps them ``(name, start,
    end, args)``, ``passes`` ``(start, end, generation)`` of the
    collector, ``samples`` ``(wall, cpu)`` of the process, ``ticks`` of
    :func:`kernel_tick`."""
    starts = sorted(s[1] for s in spans if s[0] == "executor.dispatch")
    if len(starts) < 3:
        return {"steps": len(starts), "stalls": []}
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    median = sorted(gaps)[len(gaps) // 2]
    found = []
    for i, gap in enumerate(gaps):
        if gap <= factor * median:
            continue
        a, b = starts[i], starts[i + 1]
        inside = [(s[0], round(max(s[1], a) - a, 4),
                   round(min(s[2], b) - max(s[1], a), 4))
                  for s in spans if s[1] < b and s[2] > a]
        inside = [s for s in inside if s[2] >= 0.01]
        cpu = [c for w, c in samples if a <= w <= b]
        found.append({
            "after_step": i + 1, "seconds": round(gap, 4),
            "spans": sorted(inside, key=lambda s: -s[2])[:6],
            "gc": [(g, round(min(e, b) - max(s, a), 4))
                   for s, e, g in passes if s < b and e > a],
            "cpu_s": round(cpu[-1] - cpu[0], 4) if len(cpu) > 1 else None,
            "samples_inside": len(cpu)})
        if ticks:
            found[-1]["kernel"] = kernel_over(ticks, a, b)
    def mid(xs):
        return round(sorted(xs)[len(xs) // 2] * 1e3, 3)

    return {"steps": len(starts), "median_ms": round(median * 1e3, 3),
            "intervals_ms": [round(g * 1e3, 2) for g in gaps],
            "median_first_10_ms": mid(gaps[:10]),
            "median_last_10_ms": mid(gaps[-10:]), "stalls": found}


def device_gaps(red, least_s=0.2, event_s=0.02):
    """The idle intervals over ``least_s`` of the first device inside the
    reduced trace ``red`` (``harness.DeviceTrace.reduce``): seconds after the
    opening mark, length, and the events of every line that is not a device's
    operation line lying over it for ``event_s`` or more, longest first."""
    import jax
    from benchmark import trace_reduce
    if not red or not red.get("devices") or not red.get("path"):
        return None
    first = sorted(red["devices"])[0]
    gaps = [(a, b) for a, b in red["devices"][first]["gaps"]
            if b - a >= least_s * 1e9]
    w0 = min((a for a, _ in red["devices"][first]["gaps"]), default=0)
    out = [{"after_mark_s": round((a - w0) / 1e9, 4),
            "seconds": round((b - a) / 1e9, 4), "over_it": []}
           for a, b in gaps]
    if not gaps:
        return out
    for plane in jax.profiler.ProfileData.from_file(red["path"]).planes:
        on_device = trace_reduce.is_device_plane(plane.name)
        for line in plane.lines:
            if on_device and line.name in trace_reduce.OP_LINES:
                continue
            for ev in line.events:
                t0 = int(ev.start_ns)
                t1 = t0 + int(ev.duration_ns)
                for (a, b), found in zip(gaps, out):
                    over = min(t1, b) - max(t0, a)
                    if over >= event_s * 1e9:
                        found["over_it"].append(
                            [round(over / 1e9, 4), plane.name, line.name,
                             ev.name[:80]])
    for found in out:
        found["over_it"] = sorted(found["over_it"], reverse=True)[:24]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--config", default="",
                    help="key=int[,key=int...] over the cell's configuration "
                    "file: another footprint of the same program, no "
                    "reading of the cell")
    ap.add_argument("--recompute", type=int, choices=(0, 1), default=0,
                    help="1: the traffic's recompute fallback (a smaller "
                    "footprint and a longer step), where the adapter has one")
    ap.add_argument("--threads", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--host_tracer_level", type=int, default=2)
    args = ap.parse_args(argv)

    from benchmark import harness
    spec = harness.load_spec()
    cell = harness.find(spec["workloads"], args.workload, "workload")
    import paddle_tpu  # noqa: F401
    import jax
    on_chip = jax.default_backend() == "tpu"

    passes, samples, open_pass = [], [], {}

    def on_gc(phase, info):
        if phase == "start":
            open_pass[info["generation"]] = time.perf_counter()
        else:
            passes.append((open_pass.pop(info["generation"], 0.0),
                           time.perf_counter(), info["generation"]))

    stop = threading.Event()

    def sample():
        while not stop.wait(0.05):
            samples.append((time.perf_counter(), time.process_time()))

    ticks = []

    def look():
        while not stop.wait(0.5):
            ticks.append(kernel_tick())

    kept = {}
    gen = harness.load_module("generators", "train_ring")
    run = gen.run

    def keeping(ctx):
        # the window only: the checks after it are another tool's subject
        model = ctx.model
        ctx.model = type("WindowOnly", (), {
            k: staticmethod(getattr(model, k))
            for k in ("build_train", "check_before_window")})
        kept.update(run(ctx), ctx=ctx)
        return kept

    gen.run = keeping
    start_trace = jax.profiler.start_trace

    def start_with_host_level(log_dir, profiler_options=None, **kw):
        # the harness asks for the marks alone (level 1)
        if profiler_options is not None:
            profiler_options.host_tracer_level = args.host_tracer_level
        return start_trace(log_dir, profiler_options=profiler_options, **kw)

    jax.profiler.start_trace = start_with_host_level
    gc.callbacks.append(on_gc)
    threading.Thread(target=sample, daemon=True).start()
    if args.threads:
        threading.Thread(target=look, daemon=True).start()
    kw = {}
    if not on_chip:                    # a rehearsal of the tool, no reading
        sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
        import test_trinity_cell
        kw["config"], kw["traffic"] = test_trinity_cell.toy_trinity()
    if args.config:
        kw.setdefault("config", harness.load_json(harness.find(
            spec["configs"], cell["config"], "config")["file"]))
        kw["config"] = dict(kw["config"], **{
            k: int(v) for k, v in (kv.split("=")
                                   for kv in args.config.split(","))})
    if args.recompute:
        kw.setdefault("traffic", harness.load_traffic(cell["traffic"]))
        kw["traffic"] = dict(kw["traffic"], recompute=True)
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace),
                                  t_process_start=T_PROCESS_START,
                                  on_chip=on_chip, spec=spec, **kw)
    finally:
        stop.set()
        gc.callbacks.remove(on_gc)
        gen.run = run
        jax.profiler.start_trace = start_trace
    out = {"workload": cell["name"], "seed": args.seed,
           "recompute": args.recompute, "config": args.config,
           "memory_peak_bytes": result["device"].get("memory_peak_bytes"),
           "device": result["device"].get("kind"),
           "train_samples_per_s": kept["e2e"]["train_samples_per_s"],
           "gc_passes_in_run": len(passes),
           "gc_longest_s": round(max((e - s for s, e, _ in passes),
                                     default=0.0), 4)}
    window = [s[1] for s in kept.get("spans", [])
              if s[0] == "executor.dispatch"]
    ticks = [t for t in ticks if window and
             window[0] <= t[0] <= window[-1] + 1.0]
    out.update(stalls(kept.get("spans", []), passes, samples, ticks=ticks))
    if args.trace:
        red = kept["ctx"].device_trace.reduce() or {}
        try:
            gaps = device_gaps(red)
        except Exception as e:                   # noqa: BLE001
            gaps = f"{type(e).__name__}: {e}"
        out.update(device_gaps=gaps,
                   traced_window_s=red.get("window_s"),
                   traced_busy_s=red.get("busy_s"),
                   first_step_backend_s=result["metrics"].get(
                       "first_step_backend_s", {}).get("value"))
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "window_stalls.jsonl"),
              "a") as f:
        f.write(json.dumps(out) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
