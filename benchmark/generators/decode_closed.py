"""Closed-loop decode traffic: one ordered dispatcher over a written-out
length table.

Everything that shapes the load is data in the traffic file:

    engine        slots, max_seq, page_len (page count: the engine's default)
    table         the (prompt, answer) pairs; the request list is rounds of
                  it, in table order, under every seed
    reserve       requests kept queued beyond the slots (outstanding =
                  slots + reserve)
    start_fractions   phase at which each slot's first request starts, so that
                  slots leave prefill and finish at spread-out iterations
    open_after_completions   the window opens when this many requests ended
    watch_slots   slots whose logits are recorded during the start and
                  compared with the reference

``--seed`` draws the token ids, nothing else: every seed sends the same
lengths in the same order.

The dispatcher is one loop on the main thread.  It polls the outstanding
futures and, for each one that resolved, submits the next list entry, in list
order.  With a few requests always queued (FIFO) the i-th freed slot takes the
i-th list entry, so the schedule is a function of the list in iteration space,
not of thread timing.
"""

from __future__ import annotations

import math
import time
from typing import Dict, Iterator, List, Sequence, Tuple

import numpy as np

from .. import harness
from ..stats import percentile

TOKENS_COUNTER = "paddle_tpu_serving_generated_tokens_total"


# -- the request list (pure; rehearsed on the CPU) ------------------------------

def rounds(traffic: dict) -> Iterator[Tuple[int, int]]:
    """The endless list of (prompt_len, answer_len): rounds of the table."""
    table = [tuple(p) for p in traffic["table"]]
    while True:
        yield from table


def cut_to_phase(prompt: int, answer: int, fraction: float) -> Tuple[int, int]:
    """A request that has already ridden ``fraction`` of its iterations
    (prompt + answer - 1): what is left of it, as a prompt and an answer.
    Left inside the prompt: the rest of the prompt and the whole answer; left
    inside the answer: a one-token prompt and the rest of the answer."""
    total = prompt + answer - 1
    left = max(1, int(math.ceil((1.0 - fraction) * total)))
    if left > answer:
        return left - answer + 1, answer
    return 1, left


def start_batch(traffic: dict) -> List[Tuple[int, int]]:
    """The first ``slots`` requests: table entries cut to the written-out
    phases."""
    table = traffic["table"]
    return [cut_to_phase(*table[i % len(table)], f)
            for i, f in enumerate(traffic["start_fractions"])]


def simulate(traffic: dict, n_iterations: int) -> dict:
    """The schedule in iteration space, as the engine runs it: each active
    slot consumes one token per iteration, a request of (P, A) rides P + A - 1
    iterations and makes a token in each of its last A, a freed slot takes the
    next queued request at the top of the next iteration.  Returns per
    iteration the generated-token count and the admissions, and per request
    its admission and completion iteration."""
    slots = traffic["engine"]["slots"]
    todo = list(start_batch(traffic))
    it = rounds(traffic)
    state: List[list] = [None] * slots          # [req_id, left, prompt_left]
    reqs: List[dict] = []
    gen_per_iter, admit_order = [], []
    for n in range(n_iterations):
        for s in range(slots):
            if state[s] is None:
                p, a = todo.pop(0) if todo else next(it)
                reqs.append({"id": len(reqs), "prompt": p, "answer": a,
                             "admit": n, "done": None, "slot": s})
                admit_order.append((n, s, p, a))
                state[s] = [len(reqs) - 1, p + a - 1, p - 1]
        g = 0
        for s in range(slots):
            rid, left, pl = state[s]
            if pl > 0:
                state[s][2] -= 1
            else:
                g += 1
            state[s][1] -= 1
            if state[s][1] == 0:
                reqs[rid]["done"] = n
                state[s] = None
        gen_per_iter.append(g)
    return {"generated": gen_per_iter, "admissions": admit_order,
            "requests": reqs}


# -- the run -------------------------------------------------------------------

def _counter(name: str) -> float:
    from paddle_tpu import monitor
    fam = monitor.REGISTRY.get(name)
    return float(sum(c.get() for _, c in fam.series())) if fam else 0.0


class _LogitsTap:
    """Wraps ``engine.run_iteration`` from the benchmark's side while the
    start batch runs: keeps, for the watched slots, the tokens fed and the
    logits that came back, position by position, as long as the slot's first
    request lives.  Taken off before the window opens."""

    def __init__(self, engine, slots: Sequence[int], limit: int):
        self.engine = engine
        self.limit = limit
        self.rows: Dict[int, list] = {s: [] for s in slots}
        self._live = {s: True for s in slots}
        self._orig = engine.run_iteration
        engine.run_iteration = self._call

    def _call(self, ids, pos, active):
        logits = self._orig(ids, pos, active)
        for s, rows in self.rows.items():
            if not self._live[s] or (not rows and not active[s]):
                continue                      # over, or not started yet
            if active[s] and int(pos[s]) == len(rows) < self.limit:
                rows.append((int(ids[s]), np.array(logits[s], np.float32)))
            else:                             # the slot's next request
                self._live[s] = False
        return logits

    def remove(self) -> None:
        if self.engine.__dict__.get("run_iteration") == self._call:
            del self.engine.run_iteration


class _Aborted(RuntimeError):
    """Raised inside the decode step after the window: fails the in-flight
    requests so that the scheduler thread ends at once (no drain)."""


def _abort_inflight(engine) -> None:
    def _raise(ids, pos, active):
        raise _Aborted("benchmark window closed")
    engine.run_iteration = _raise


def run(ctx) -> dict:
    t = ctx.traffic
    eng_cfg = t["engine"]
    slots = int(eng_cfg["slots"])
    reserve = int(t["reserve"])

    built = ctx.model.build_server(ctx.config, t, ctx.seed, ctx.on_chip)
    engine, server, vocab = built["engine"], built["server"], built["vocab"]
    ctx.clock.mark("build+startup")

    rng = np.random.RandomState(harness.np_seed(ctx.seed))
    lengths = rounds(t)
    first = start_batch(t)

    outstanding: List[dict] = []
    n_submitted = 0

    def submit(prompt_len: int, answer_len: int, measured: bool) -> None:
        nonlocal n_submitted
        prompt = rng.randint(1, vocab, size=prompt_len).astype(np.int64)
        t_sub = time.perf_counter()
        fut = server.submit("bench", prompt, max_new_tokens=answer_len,
                            eos_id=None)
        outstanding.append({"i": n_submitted, "fut": fut, "t_sub": t_sub,
                            "prompt": prompt, "answer": answer_len,
                            "measured": measured})
        n_submitted += 1

    # the start batch and the reserve are queued before the decode thread
    # exists, so slot s takes the s-th of them
    tap = _LogitsTap(engine, t.get("watch_slots", []),
                     int(t.get("watch_limit", 160)))
    for p, a in first:
        submit(p, a, measured=False)
    for _ in range(reserve):
        submit(*next(lengths), measured=True)
    server.start()

    done: List[dict] = []
    pages_peak = 0
    open_after = int(t["open_after_completions"])
    t_open = t_deadline = t_close = None
    tok_open = tok_close = None
    trace_started = False
    first_done_logged = False

    def poll() -> None:
        nonlocal pages_peak
        now = time.perf_counter()
        for r in [r for r in outstanding if r["fut"].done()]:
            outstanding.remove(r)
            r["t_done"] = now
            done.append(r)
            if t_deadline is None or now < t_deadline:
                submit(*next(lengths), measured=True)
        pages_peak = max(pages_peak, engine.cache.pages_in_use())

    while True:
        poll()
        now = time.perf_counter()
        if done and not first_done_logged:
            first_done_logged = True
            ctx.clock.mark("compile+first completion")
        if t_open is None:
            if len(done) >= open_after:
                tap.remove()
                ctx.clock.mark("de-phasing start")
                compiles_at_open = ctx.meter.compiles
                traces_at_open = engine.trace_count
                ctx.spans.open()
                pages_peak = engine.cache.pages_in_use()
                tok_open = _counter(TOKENS_COUNTER)
                t_open = time.perf_counter()
                t_deadline = t_open + ctx.seconds
                setup_s = t_open - ctx.clock.t0
        else:
            if ctx.trace and not trace_started and \
                    now >= t_deadline - ctx.trace_seconds:
                trace_started = True
                ctx.device_trace.start()
            if now >= t_deadline:
                # close on the next iteration boundary: tokens and seconds
                # then cover the same whole iterations
                tok = _counter(TOKENS_COUNTER)
                while time.perf_counter() < t_deadline + 1.0 \
                        and _counter(TOKENS_COUNTER) == tok:
                    time.sleep(0.0005)
                tok_close = _counter(TOKENS_COUNTER)
                t_close = time.perf_counter()
                break
        time.sleep(0.002)
    poll()
    if trace_started:
        ctx.device_trace.stop()
    spans = ctx.spans.close()
    memory_peak = harness.memory_peak_bytes()     # before the reference runs
    compiled_in_window = ctx.meter.compiles - compiles_at_open
    traced_in_window = engine.trace_count - traces_at_open
    _abort_inflight(engine)
    server.stop()
    drained = server.drain(30.0)

    window_s = t_close - t_open
    in_window = [r for r in done if t_open <= r["t_done"] <= t_close]
    failed, lat, wrong_len, tokens_completed = 0, [], 0, 0
    for r in in_window:
        try:
            out = r["fut"].result(timeout=0)
        except Exception:
            failed += 1
            continue
        if len(out) != r["answer"]:
            wrong_len += 1
        tokens_completed += len(out)
        if r["measured"]:
            lat.append((r["t_done"] - r["t_sub"]) * 1e3 / r["answer"])
    tokens = tok_close - tok_open
    harness.log(
        f"window {window_s:.3f}s (asked {ctx.seconds}s, closed "
        f"{t_close - t_deadline:+.3f}s after the deadline); generated tokens "
        f"by the counter {tokens:.0f}, by completed answers "
        f"{tokens_completed}; completed {len(in_window)} "
        f"(latency samples {len(lat)}), failed {failed}; submitted "
        f"{n_submitted}; scheduler drained after abort: {drained}")

    iters = sorted((s[1], s[2]) for s in spans
                   if s[0] == "serving.decode_iter")
    if len(iters) > 1:
        gaps = [b[0] - a[1] for a, b in zip(iters, iters[1:])]
        harness.log(
            f"{len(iters)} iterations in the window: step p50 "
            f"{percentile([b - a for a, b in iters], 50) * 1e3:.3f} ms, host "
            f"between steps p50 {percentile(gaps, 50) * 1e3:.3f} ms")

    check = ctx.model.check_logits(ctx.config, built, tap.rows, ctx.reference)
    harness.log(f"logits against the reference: {check['detail']}")
    correct = (check["ok"] and failed == 0 and wrong_len == 0
               and engine.trace_count == 1 and compiled_in_window == 0
               and traced_in_window == 0 and len(lat) > 0)
    verdict = (f"{'correct' if correct else 'NOT correct'}: "
               f"logits_ok={check['ok']} failed={failed} (0) "
               f"wrong_len={wrong_len} (0) trace_count={engine.trace_count} "
               f"(1) compiled_in_window={compiled_in_window} (0) "
               f"traced_in_window={traced_in_window} (0) "
               f"latency_samples={len(lat)} (> 0)")
    if not correct:
        harness.log(verdict)

    n_beyond = len(lat) - int(math.ceil(0.9 * len(lat))) if lat else 0
    harness.log(f"norm_latency_p90_ms (per layer) over {len(lat)} requests "
                f"({n_beyond} beyond it): "
                f"{percentile(lat, 90) if lat else float('nan'):.3f} ms/token")
    return {
        "correct": correct,
        "compared": [check["detail"], verdict],
        "attempted": len(in_window),
        "failed": failed,
        "setup_s": setup_s,
        "memory_peak_bytes": memory_peak,
        "e2e": {"serve_out_tokens_per_s": tokens / window_s},
        "spans": spans,
        "counters": {"generated_tokens": tokens,
                     "kv_pages_peak": pages_peak},
        "facts": {"slots": slots, "n_pages": engine.cache.n_pages,
                  "window_s": window_s, "iterations": len(iters),
                  "norm_latency_ms": lat},
    }
