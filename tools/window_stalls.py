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


def stalls(spans, passes, samples, factor=1.5):
    """The dispatch intervals over ``factor`` times their median, each with
    what overlapped it: ``spans`` as the harness keeps them ``(name, start,
    end, args)``, ``passes`` ``(start, end, generation)`` of the
    collector, ``samples`` ``(wall, cpu)`` of the process."""
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
            "cpu_s": round(cpu[-1] - cpu[0], 4) if len(cpu) > 1 else None})
    def mid(xs):
        return round(sorted(xs)[len(xs) // 2] * 1e3, 3)

    return {"steps": len(starts), "median_ms": round(median * 1e3, 3),
            "intervals_ms": [round(g * 1e3, 2) for g in gaps],
            "median_first_10_ms": mid(gaps[:10]),
            "median_last_10_ms": mid(gaps[-10:]), "stalls": found}


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

    kept = {}
    gen = harness.load_module("generators", "train_ring")
    run = gen.run

    def keeping(ctx):
        # the window only: the checks after it are another tool's subject
        model = ctx.model
        ctx.model = type("WindowOnly", (), {
            k: staticmethod(getattr(model, k))
            for k in ("build_train", "check_before_window")})
        kept.update(run(ctx))
        return kept

    gen.run = keeping
    gc.callbacks.append(on_gc)
    threading.Thread(target=sample, daemon=True).start()
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
                                  False, t_process_start=T_PROCESS_START,
                                  on_chip=on_chip, spec=spec, **kw)
    finally:
        stop.set()
        gc.callbacks.remove(on_gc)
        gen.run = run
    out = {"workload": cell["name"], "seed": args.seed,
           "recompute": args.recompute, "config": args.config,
           "memory_peak_bytes": result["device"].get("memory_peak_bytes"),
           "device": result["device"].get("kind"),
           "train_samples_per_s":
           result["metrics"]["train_samples_per_s"]["value"],
           "gc_passes_in_run": len(passes),
           "gc_longest_s": round(max((e - s for s, e, _ in passes),
                                     default=0.0), 4)}
    out.update(stalls(kept.get("spans", []), passes, samples))
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "window_stalls.jsonl"),
              "a") as f:
        f.write(json.dumps(out) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
