"""chip_smoke.py — the quickest proof that the system still starts on a chip.

One process, no arguments, normal entry points only.  It drives the main
path once at the full width of the repo's GPT-causal recipe (BERT-base
width, seq 2048, batch 8): a trainer that takes a few steps through
``Program -> Executor -> Pallas flash attention``, then a decode server
that answers four requests over the scope the trainer just produced, then
(only when the process sees >= 4 TPU devices) BERT-base under data
parallelism and under a dp x mp GSPMD mesh.  Weights are random, from a
seed.  Any phase that fails raises; nothing is caught and printed.

It never selects a platform.  It prints what JAX found first and exits
non-zero, printing no result, unless the default backend is a TPU.  A passing
run ends with two lines: ``chip_smoke: summary {...}`` (per-phase reports,
compile seconds, the compile cache in use, ``"claim": null`` — step and
request times in it are information, not benchmark metrics), and last of all
exactly ``{"ok": true, "device": {"platform": "tpu", "kind": "...",
"count": N}}`` with the device as JAX reports it.

    chiprun -- python3 chip_smoke.py        # first command of a chip session
"""

import json
import os
import sys
import tempfile
import time

import numpy as np

SEED = 1234
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 2048, 6
SERVE_PROMPT_LENS, SERVE_NEW_TOKENS = (16, 48, 96, 128), 16
MULTICHIP_MIN_DEVICES = 4


def check(cond, what):
    """A gate: raises (and so fails the run) when ``cond`` is false."""
    if not cond:
        raise AssertionError(f"chip_smoke gate failed: {what}")


class CompileMeter:
    """Seconds JAX spent producing executables (compiling, or loading from
    the persistent cache) and persistent-cache hits/writes, read from
    ``jax.monitoring`` — the same events whatever layer asked for the
    compile (the executor, the decode engine, a bare jit)."""

    def __init__(self):
        import jax.monitoring as m
        self.seconds = 0.0
        self.hits = 0
        self.writes = 0
        m.register_event_duration_secs_listener(self._on_duration)
        m.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def since(self, mark):
        return round(self.seconds - mark, 2)


def _tpu_devices(arr):
    """The devices holding ``arr`` — all of which must be TPU chips."""
    devs = sorted(arr.devices(), key=lambda d: d.id)
    check(all(d.platform == "tpu" for d in devs),
          f"array lives on {devs}, not on TPU devices")
    return devs


def lowered_kernel_names(dump_dir):
    """kernel_name of every Mosaic custom call in the StableHLO JAX dumped
    while lowering the jitted training step (``jit_step``)."""
    import re
    names = []
    for fn in sorted(os.listdir(dump_dir)):
        if "jit_step" not in fn:
            continue
        with open(os.path.join(dump_dir, fn)) as f:
            text = f.read()
        for call in re.finditer(r"@tpu_custom_call\(.*", text):
            m = re.search(r'kernel_name = "([^"]+)"', call.group(0))
            names.append(m.group(1) if m else "?")
    return names


def _attention_parity(on_chip):
    """The flash kernels against the O(T^2) reference on a small input:
    forward and the three input gradients."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.pallas import flash_attention, mha_reference

    rng = np.random.RandomState(SEED)
    shape = (1, 4, 1024, 64) if on_chip else (1, 2, 64, 8)
    q, k, v, w = (jnp.asarray(rng.randn(*shape).astype(np.float32)) * 0.5
                  for _ in range(4))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) * w)

    got_o = flash_attention(q, k, v, causal=True)
    got_g = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want_o = mha_reference(q, k, v, causal=True)
        want_g = jax.grad(loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
    errs = {}
    for name, got, want in zip(("o", "dq", "dk", "dv"),
                               (got_o,) + got_g, (want_o,) + want_g):
        err = float(jnp.max(jnp.abs(got - want)))
        check(np.isfinite(err) and err < 2e-2,
              f"flash {name} differs from mha_reference by {err}")
        errs[name] = round(err, 6)
    return errs


def phase_train(cfg, seq_len, batch, steps, place, on_chip, meter):
    """Startup, one compiling step, then ``steps`` lazy-fetch steps on one
    seeded batch with a closing sync.  Returns (report, scope)."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models import transformer as T

    mark = meter.seconds
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        _, _, loss = T.build_gpt_pretrain(
            cfg, seq_len, fused_head=True, attn_impl="auto", dropout=0.0)
        pt.amp.decorate(opt.AdamOptimizer(learning_rate=1e-4)).minimize(loss)
        exe = pt.Executor(place)
        exe.run(pt.default_startup_program(), scope=scope, seed=SEED)
        exe.reset_dispatch_stats()

        rng = np.random.RandomState(SEED)
        ids = rng.randint(1, cfg.vocab_size, (batch, seq_len)).astype(np.int32)
        labels = np.roll(ids, -1, axis=1)
        labels[:, -1] = 0
        feed = {"src_ids": jax.device_put(ids),
                "lm_label": jax.device_put(labels)}

        with tempfile.TemporaryDirectory() as dump_dir:
            jax.config.update("jax_dump_ir_to", dump_dir)
            t0 = time.perf_counter()
            first, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
            first_step_s = time.perf_counter() - t0
            jax.config.update("jax_dump_ir_to", None)
            kernels = lowered_kernel_names(dump_dir)

        t0 = time.perf_counter()
        handles = [exe.run(feed=feed, fetch_list=[loss.name], scope=scope,
                           return_numpy=False)[0] for _ in range(steps)]
        losses = [float(np.asarray(first))] + \
            [float(np.asarray(h)) for h in handles]      # closing sync
        step_s = (time.perf_counter() - t0) / steps
        stats = exe.dispatch_stats()

    check(all(np.isfinite(l) for l in losses), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(stats["traces"] == 1 and stats["cache_misses"] == 1,
          f"expected exactly one compile after startup, got {stats}")
    check(stats["steps_dispatched"] == steps + 1, f"steps: {stats}")
    report = {"ok": True, "losses": [round(l, 4) for l in losses],
              "compile_s": meter.since(mark),
              "first_step_s": round(first_step_s, 2),
              "step_ms": round(step_s * 1e3, 1)}
    if on_chip:
        report["fetch_device"] = str(_tpu_devices(handles[-1])[0])
        # one forward per layer at least: the grad op's generic vjp lowers
        # the forward kernel a second time (24 calls for 12 layers)
        check(kernels.count("flash_fwd") >= cfg.n_layer
              and kernels.count("flash_bwd_fused") == cfg.n_layer,
              "the lowered step must hold a Mosaic flash forward and a "
              f"fused backward kernel for every layer, found {kernels}")
        report["mosaic_kernels"] = {n: kernels.count(n)
                                    for n in sorted(set(kernels))}
    report["attention_max_err"] = _attention_parity(on_chip)
    return report, scope


def _reference_gaps(cfg, scope, tokens, n_prompt):
    """How far each generated token's logit sits below the row maximum of
    the full-context program (``build_gpt_serving`` through the Executor)
    run once over prompt + generation, in units of that row's std.  0 means
    the engine picked the reference argmax."""
    import paddle_tpu as pt
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models import transformer as T

    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        _, logits = T.build_gpt_serving(cfg, len(tokens), attn_impl="base")
    ref, = pt.Executor().run(
        prog, feed={"src_ids": np.asarray(tokens, np.int64)[None, :]},
        fetch_list=[logits.name], scope=scope)
    ref = np.asarray(ref, np.float32)[0]
    gaps = []
    for i in range(n_prompt, len(tokens)):
        row = ref[i - 1]
        gaps.append(float((row.max() - row[tokens[i]]) / row.std()))
    return gaps


def phase_serve(cfg, scope, prompt_lens, max_new, page_len, on_chip, meter):
    """A DecodeServer over the trained scope answers one request per
    prompt length across two tenants; the shortest is checked against the
    full-context program."""
    from paddle_tpu import serving

    mark = meter.seconds
    eng = serving.DecodeEngine(cfg, scope, max_slots=4, page_len=page_len)
    srv = serving.DecodeServer(eng).start()
    try:
        rng = np.random.RandomState(SEED + 1)
        prompts = [rng.randint(1, cfg.vocab_size, (n,)).astype(np.int64)
                   for n in prompt_lens]
        t0 = time.perf_counter()
        futs = [srv.submit("tenant_a" if i % 2 else "tenant_b", p,
                           max_new_tokens=max_new)
                for i, p in enumerate(prompts)]
        gens = [[int(t) for t in f.result(timeout=900)] for f in futs]
        wall_s = time.perf_counter() - t0
        check(srv.drain(60), "DecodeServer.drain timed out")
    finally:
        srv.stop()

    for p, g in zip(prompts, gens):
        check(len(g) == max_new and all(0 <= t < cfg.vocab_size for t in g),
              f"prompt of {len(p)}: expected {max_new} ids in "
              f"[0, {cfg.vocab_size}), got {g}")
    check(eng.trace_count == 1, f"decode step traced {eng.trace_count}x")
    check(eng.cache.pages_in_use() == 0,
          f"{eng.cache.pages_in_use()} KV pages leaked")
    gaps = _reference_gaps(cfg, scope, list(map(int, prompts[0])) + gens[0],
                           len(prompts[0]))
    check(max(gaps) < 0.25,
          "engine tokens are not the full-context program's (near-)argmax: "
          f"gaps in row-std units {gaps}")
    report = {"ok": True, "requests": len(gens), "tokens": max_new * len(gens),
              "trace_count": eng.trace_count, "pages_in_use": 0,
              "compile_s": meter.since(mark), "wall_s": round(wall_s, 2),
              "max_argmax_gap_std": round(max(gaps), 4)}
    if on_chip:
        report["pool_device"] = str(_tpu_devices(eng.cache.k)[0])
    return report


def phase_multichip(cfg, seq_len, n_devices, place, on_chip, meter, steps=4):
    """BERT pretrain on all ``n_devices`` chips of the host, one process:
    data parallel, then dp x mp GSPMD with ZeRO-1."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models import transformer as T

    batch = 16 * n_devices
    rng = np.random.RandomState(SEED + 2)
    feed = {"src_ids": rng.randint(1, cfg.vocab_size, (batch, seq_len)
                                   ).astype(np.int64),
            "pos_ids": np.tile(np.arange(seq_len), (batch, 1)
                               ).astype(np.int64),
            "lm_label": rng.randint(0, cfg.vocab_size, (batch, seq_len)
                                    ).astype(np.int64)}

    def run(name, compile_fn, expect_split):
        mark = meter.seconds
        scope, main = Scope(), Program()
        with scope_guard(scope), program_guard(main, Program()):
            _, logits, loss = T.build_bert_pretrain(cfg, seq_len)
            pt.amp.decorate(
                opt.AdamOptimizer(learning_rate=1e-4)).minimize(loss)
            compiled = compile_fn(main, loss)
            exe = pt.Executor(place)
            exe.run(pt.default_startup_program(), scope=scope, seed=SEED)
            losses = []
            for _ in range(steps):
                lv, lg = exe.run(compiled, feed=feed,
                                 fetch_list=[loss.name, logits.name],
                                 scope=scope, return_numpy=False)
                losses.append(float(np.asarray(lv)))
            params = [v.name for v in main.global_block().all_parameters()]
            spans, split = {}, []
            for n in params:
                arr = scope.find_var(n)
                spans[n] = len(arr.sharding.device_set)
                if arr.addressable_shards[0].data.shape != arr.shape:
                    split.append(n)
            fetch_span = len(lg.sharding.device_set)
        report = {}
        if on_chip:
            _tpu_devices(lg)
            in_use = [int(d.memory_stats()["bytes_in_use"])
                      for d in jax.devices()]
            check(all(b > 0 for b in in_use),
                  f"{name}: bytes_in_use per device {in_use}")
            report["bytes_in_use"] = in_use
        check(all(np.isfinite(l) for l in losses) and losses[-1] < losses[0],
              f"{name}: loss not finite and falling: {losses}")
        check(all(s == n_devices for s in spans.values()),
              f"{name}: parameters not on all {n_devices} chips: "
              f"{ {n: s for n, s in spans.items() if s != n_devices} }")
        check(bool(split) == expect_split,
              f"{name}: mp-split parameters expected={expect_split}, "
              f"found {len(split)}")
        check(fetch_span == n_devices,
              f"{name}: batch-sharded fetch spans {fetch_span} devices")
        return {"ok": True, "losses": [round(l, 4) for l in losses],
                "params": len(params), "params_split": len(split),
                "fetch_devices": fetch_span, "compile_s": meter.since(mark),
                **report}

    return {
        "ok": True,
        "data_parallel": run(
            "data_parallel",
            lambda main, loss: pt.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name), expect_split=False),
        "gspmd_dp2_mp2": run(
            "gspmd",
            lambda main, loss: pt.CompiledProgram(main).with_gspmd(
                axes={"dp": n_devices // 2, "mp": 2}, rules="mp_hidden",
                zero_stage=1), expect_split=True),
    }


def device_identity():
    """The device as JAX reports it."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def result_line(device):
    """The last line of a passing run: these two keys and no others (the
    driver's chip check parses it; details go on the summary line)."""
    return json.dumps({"ok": True, "device": device})


def main():
    import jax
    device = device_identity()
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']} count={device['count']}", flush=True)
    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's default backend is "
                 f"{jax.default_backend()!r}); this check only means "
                 "something on a chip — run it through chiprun")

    import paddle_tpu as pt
    from paddle_tpu import monitor
    from paddle_tpu.models import transformer as T

    meter = CompileMeter()
    cfg = T.BertConfig(max_pos=TRAIN_SEQ)
    phases = {}
    phases["train"], scope = phase_train(
        cfg, TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS, pt.TPUPlace(0), True, meter)
    print(f"chip_smoke: train {json.dumps(phases['train'])}", flush=True)
    phases["serve"] = phase_serve(
        cfg, scope, SERVE_PROMPT_LENS, SERVE_NEW_TOKENS, 64, True, meter)
    print(f"chip_smoke: serve {json.dumps(phases['serve'])}", flush=True)
    if device["count"] >= MULTICHIP_MIN_DEVICES:
        phases["multichip"] = phase_multichip(
            T.BertConfig(dropout=0.0), 128, device["count"], pt.TPUPlace(0),
            True, meter)
    else:
        phases["multichip"] = f"not run ({device['count']} device)"
    print(f"chip_smoke: multichip {json.dumps(phases['multichip'])}",
          flush=True)

    persist = monitor.REGISTRY.get("paddle_tpu_compile_total")
    print("chip_smoke: summary " + json.dumps({
        "ok": True,
        "device": device,
        "phases": phases,
        "compile_cache": {
            "dir": jax.config.jax_compilation_cache_dir,
            "hits": meter.hits, "writes": meter.writes,
            "executor_compiles": {k: int(persist.value(persist=k))
                                  for k in ("hit", "miss", "off")}},
        "memory_stats": {k: int(v) for k, v in
                         (jax.devices()[0].memory_stats() or {}).items()
                         if k in ("bytes_in_use", "peak_bytes_in_use",
                                  "bytes_limit")},
        "native_available": bool(pt.native.available()),
        "claim": None,
    }), flush=True)
    print(result_line(device), flush=True)


if __name__ == "__main__":
    main()
