"""``tools/window_stalls.py``: which spans, collector passes and CPU seconds
lie under a dispatch interval that is too long (pure Python; the tool's run
of a cell is rehearsed by hand, ``JAX_PLATFORMS=cpu python3
tools/window_stalls.py --workload trinity_mini_lm_s8192 --seconds 1``)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import window_stalls  # noqa: E402


def _dispatches(stall_after=None, seconds=0.0):
    out, t = [], 0.0
    for i in range(10):
        out.append(("executor.dispatch", t, t + 0.002, {}))
        t += 0.3 + (seconds if i == stall_after else 0.0)
    return out


def test_a_steady_window_has_no_stall():
    got = window_stalls.stalls(_dispatches(), [], [])
    assert got["stalls"] == [] and got["steps"] == 10
    assert got["median_ms"] == got["median_first_10_ms"] == 300.0


def test_a_stall_is_laid_to_what_overlapped_it():
    """Two seconds after step 5: the device's (the host waits inside
    ``executor.throttle_wait`` and uses no CPU) and the host's (a collector
    pass, CPU busy) read differently."""
    spans = _dispatches(4, 2.0)
    samples = [(0.1 * i, 0.001 * i) for i in range(60)]
    device = window_stalls.stalls(
        spans + [("executor.throttle_wait", 1.25, 3.19, {})], [], samples)
    stall, = device["stalls"]
    assert stall["after_step"] == 5 and stall["seconds"] == 2.3
    assert stall["spans"][0] == ("executor.throttle_wait", 0.05, 1.94)
    assert stall["gc"] == [] and stall["cpu_s"] < 0.05
    busy = [(w, w if 1.2 <= w <= 3.5 else 0.0) for w, _ in samples]
    host = window_stalls.stalls(spans, [(1.3, 3.2, 2)], busy)
    stall, = host["stalls"]
    assert stall["gc"] == [(2, 1.9)] and stall["cpu_s"] > 1.9
    assert all(s[0] == "executor.dispatch" for s in stall["spans"]) \
        or stall["spans"] == []


def test_too_few_steps_to_say():
    assert window_stalls.stalls(_dispatches()[:2], [], []) == \
        {"steps": 2, "stalls": []}


def _tick(t, threads, **counters):
    return (t, threads, dict({"cpu.steal": 0, "pgfault": 0}, **counters))


def test_the_kernels_view_of_a_stall():
    """``--threads 1``: a runtime thread that sleeps in the driver's ioctl
    through the stall and not outside it is named, with the counters that ran
    faster inside; ticks that did not fall are counted as such."""
    busy = {("python3", "S", "futex_wait_queue"): 3,
            ("tpu_worker", "S", "futex_wait_queue"): 1}
    stuck = {("python3", "S", "futex_wait_queue"): 3,
             ("tpu_worker", "D", "accel_ioctl"): 1}
    inside = [1.3 < 0.5 * i < 3.6 for i in range(12)]
    steal = [sum(4000 if on else 100 for on in inside[:i + 1])
             for i in range(12)]
    ticks = [_tick(0.5 * i, stuck if inside[i] else busy,
                   **{"cpu.steal": steal[i]}) for i in range(12)]
    got = window_stalls.stalls(
        _dispatches(4, 2.0) + [("executor.throttle_wait", 1.25, 3.19, {})],
        [], [], ticks=ticks)
    kernel = got["stalls"][0]["kernel"]
    assert kernel["ticks_inside"] == 5 and kernel["ticks_outside"] == 7
    assert ["tpu_worker D accel_ioctl", 1.0, 0.0] in \
        kernel["threads_inside_vs_outside"]
    assert kernel["threads_inside_not_in_futex"] == \
        [["tpu_worker D accel_ioctl", 1.0, 0.0]]
    assert [r[0] for r in kernel["counters_per_s_inside_vs_rest"]] == \
        ["cpu.steal"]
    none = window_stalls.kernel_over([t for t in ticks
                                      if not 1.3 < t[0] < 3.6], 1.2, 3.5)
    assert none == {"ticks_inside": 0, "ticks_outside": 7}


def test_one_look_at_proc_names_this_thread():
    t, threads, counters = window_stalls.kernel_tick()
    assert sum(threads.values()) >= 1 and counters["cpu.user"] >= 0
    assert all(len(key) == 3 for key in threads)


def test_device_gaps_need_a_trace():
    assert window_stalls.device_gaps(None) is None
    assert window_stalls.device_gaps({"devices": {}, "path": "x"}) is None
