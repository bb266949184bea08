"""Benchmark: flagship training steps on one chip vs the 35% MFU BASELINE
targets (BASELINE.md).  Prints one JSON line per benchmark:

  1. ResNet-50 ImageNet-shaped training (BASELINE target #1)
  2. BERT-base MLM training (BASELINE target #2, flagship — printed last)

Measurement notes:
- feeds are placed on device once (`jax.device_put`): per-step host feeds
  would time the host-to-device copy, not the step (a real input pipeline
  prefetches to device the same way)
- steps are chained via the executor's persistable-state round trip with
  ONE host sync at the end; a per-step sync would idle the device for the
  host's whole dispatch path
- one process holds the chip: a workload that raises is recorded as a
  `bench_error:` line, the remaining workloads still run, and the exit
  code is non-zero.  Children that run beside the chip holder pin
  themselves to `JAX_PLATFORMS=cpu`.
- ResNet-50 roofline (measured r2): XLA cost model reports 6.17 TFLOP +
  91 GB logical bytes accessed per step at batch 256; fwd and bwd both
  run at ~27% of bf16 peak — the small-channel stages (C_out/K = 64)
  underfill the 128-lane MXU, matching public RN50-on-TPU profiles.
"""

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

# Every metric line is also collected here so main() can print ONE compact
# all-metrics summary array as the FINAL stdout line: the driver records
# only the tail of the output, and in round 4 the verbose early lines
# (resnet50, long4k, long8k) scrolled off the capture window.
RESULTS = []


def emit(rec):
    print(json.dumps(rec))
    RESULTS.append(rec)


def _device_info():
    import jax
    from paddle_tpu.analysis import device_peak_flops
    from paddle_tpu.device import is_tpu
    dev = jax.devices()[0]
    on_tpu = is_tpu(dev)
    # peak dense bf16 FLOP/s per chip — SHARED with the executor's live
    # paddle_tpu_step_mfu gauge (analysis.cost.device_peak_flops), so
    # the mfu:<workload> cross-check below compares numerators only.
    # A CPU run has no MFU and gets no peak: the CPU smoke branches below
    # name their own nominal constant.
    peak = device_peak_flops(dev) if on_tpu else None
    return dev, on_tpu, peak


#: runtime-vs-offline MFU agreement band for the mfu:<workload> lines —
#: the two accountings share the peak denominator, so the ratio isolates
#: analytic-model flops (cost.py) against the hand formulas below plus
#: gauge-vs-best-rep timing noise; outside the band the line flags
#: diverged=true so the trajectory can never drift silently
_MFU_TOLERANCE = 2.0


def _emit_runtime_mfu(name, exe, offline_mfu):
    """mfu:<workload> line: the executor's LIVE paddle_tpu_step_mfu
    gauge (analytic flops/step over the median dispatch interval x chip peak)
    next to the workload's own offline MFU computation, with the
    tolerance gate.  Never breaks the bench."""
    try:
        from paddle_tpu import monitor
        fam = monitor.REGISTRY.get("paddle_tpu_step_mfu")
        live = fam.value(executor=str(exe._stats.serial)) if fam else 0.0
        ms_fam = monitor.REGISTRY.get("paddle_tpu_step_device_ms")
        step_ms = (ms_fam.value(executor=str(exe._stats.serial))
                   if ms_fam else 0.0)
        offline = float(offline_mfu)
        ratio = (live / offline) if (live > 0 and offline > 0) else 0.0
        ok = bool(ratio and 1.0 / _MFU_TOLERANCE <= ratio
                  <= _MFU_TOLERANCE)
        rec = {
            "metric": f"mfu:{name}",
            "value": round(live * 100, 2),
            "unit": "% MFU (live runtime gauge)",
            "vs_baseline": 0,
            "offline_pct": round(offline * 100, 2),
            "live_vs_offline": round(ratio, 3),
            "step_ms": round(step_ms, 2),
            "tolerance": _MFU_TOLERANCE,
        }
        if not ok:
            rec["diverged"] = True
        emit(rec)
    except Exception as e:   # the cross-check must never kill a line
        emit({"metric": f"mfu:{name}", "value": 0,
              "unit": "% MFU (live runtime gauge)", "vs_baseline": 0,
              "error": repr(e)[:200]})


def _fusion_counts(since=None):
    """Cumulative {(pattern, verdict): n} of the fusion decision counter
    (optionally as a delta against an earlier snapshot)."""
    try:
        from paddle_tpu import monitor
        fam = monitor.REGISTRY.get("paddle_tpu_fusion_candidates_total")
        now = {}
        for labels, cell in (fam.series() if fam else ()):
            k = (labels.get("pattern", "?"), labels.get("verdict", "?"))
            now[k] = now.get(k, 0) + cell.get()
        if since:
            now = {k: v - since.get(k, 0) for k, v in now.items()
                   if v - since.get(k, 0)}
        return now
    except Exception:
        return {}


def _emit_fusion_line(name, exe, scope, loss_name, feed, steps, dt_fused,
                      counts):
    """fusion:<workload> line: applied-rewrite counts (the graph-fusion
    decision counter deltas for THIS workload) next to a fused-vs-unfused
    steps/s comparison — the same program re-measured with
    FLAGS_graph_fusion off on the same executor (the fusion config token
    keys the dispatch plan, so the flip compiles the unfused block).
    The fused config is the product default; autotune's measured
    fallback is what keeps the ratio from regressing on real chips."""
    import paddle_tpu as pt
    try:
        from paddle_tpu.flags import get_flags as _gf
        prior = bool(_gf("FLAGS_graph_fusion")["FLAGS_graph_fusion"])
        pt.set_flags({"FLAGS_graph_fusion": False})
        try:
            lv, = exe.run(feed=feed, fetch_list=[loss_name], scope=scope)
            udts = []
            for _rep in range(2):
                t0 = time.perf_counter()
                for _ in range(steps):
                    lv, = exe.run(feed=feed, fetch_list=[loss_name],
                                  scope=scope, return_numpy=False)
                np.asarray(lv)
                udts.append((time.perf_counter() - t0) / steps)
            dt_unfused = min(udts)
        finally:
            pt.set_flags({"FLAGS_graph_fusion": prior})
        applied = {p: n for (p, v), n in counts.items() if v == "applied"}
        emit({
            "metric": f"fusion:{name}",
            "value": int(sum(applied.values())),
            "unit": "applied fusion rewrites",
            "vs_baseline": 0,
            "applied_by_pattern": applied,
            "decisions": {f"{p}:{v}": int(n)
                          for (p, v), n in sorted(counts.items())},
            "steps_per_s_fused": round(1.0 / dt_fused, 3),
            "steps_per_s_unfused": round(1.0 / dt_unfused, 3),
            "fused_vs_unfused": round(dt_unfused / dt_fused, 3),
        })
    except Exception as e:      # the comparison must never kill a line
        emit({"metric": f"fusion:{name}", "value": 0,
              "unit": "applied fusion rewrites", "vs_baseline": 0,
              "error": repr(e)[:200]})


def bench_resnet50(dev, on_tpu, peak, frozen_bn=False):
    """Batch-stat line (the honest from-scratch training config) plus a
    separately-labeled frozen-BN finetune line (`use_global_stats=True`,
    a legitimate reference mode — batch_norm's own flag): frozen BN drops
    the batch-stat reductions and their backward and measured −24% step
    time in RN50_ABLATION.md.  The batch-stat ceiling (~28% MFU at batch
    256) is a measured v5e ceiling, not an unexamined miss — five
    refuted levers + byte-model roofline in RN50_ABLATION.md."""
    if frozen_bn and not on_tpu:
        return                             # finetune line is a TPU metric
    import jax
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import Program, Scope, program_guard, \
        scope_guard
    from paddle_tpu.models.resnet import build_resnet_train

    scope = Scope()
    fusion_before = _fusion_counts()
    with scope_guard(scope), program_guard(Program(), Program()):
        if on_tpu:
            class_dim, image, batch, steps = 1000, (3, 224, 224), 256, 32
        else:
            class_dim, image, batch, steps = 10, (3, 32, 32), 4, 2
            peak = 1e12
        (img, label), pred, loss, accs = build_resnet_train(
            class_dim=class_dim, depth=50, image_shape=image,
            use_global_stats=frozen_bn)
        optimizer = pt.amp.decorate(
            opt.MomentumOptimizer(learning_rate=0.1, momentum=0.9))
        optimizer.minimize(loss)
        exe = pt.Executor()
        exe.run(pt.default_startup_program(), scope=scope)

        # analytic FLOPs from the program's inferred shapes (2·MAC)
        blk = pt.default_main_program().global_block()
        fl = 0
        for op_ in blk.ops:
            if op_.type == "conv2d":
                w = blk.var(op_.input("Filter")[0]).shape
                o = blk.var(op_.output("Output")[0]).shape
                fl += 2 * o[1] * o[2] * o[3] * w[1] * w[2] * w[3]
            elif op_.type in ("mul", "matmul"):
                x = blk.var(op_.input("X")[0]).shape
                y = blk.var(op_.input("Y")[0]).shape
                fl += 2 * int(np.prod([d for d in x[1:] if d > 0])) * y[-1]

        rng = np.random.RandomState(0)
        feed = {
            "image": jax.device_put(
                rng.rand(batch, *image).astype(np.float32)),
            "label": jax.device_put(
                rng.randint(0, class_dim, (batch, 1)).astype(np.int32)),
        }
        lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
        l0 = float(np.asarray(lv))
        # best of two timed passes: the first workload of a fresh process
        # can read ~10% slow (compile-cache warmup bleeding into the
        # pipeline) — a second pass measures the steady state
        dts = []
        for _rep in range(2):
            t0 = time.perf_counter()
            for _ in range(steps):
                lv, = exe.run(feed=feed, fetch_list=[loss.name],
                              scope=scope, return_numpy=False)
            lN = float(np.asarray(lv))        # one sync bounds the pipeline
            dts.append((time.perf_counter() - t0) / steps)
        dt = min(dts)
        mfu = 3 * fl * batch / dt / peak
        if frozen_bn:
            metric = "resnet50_frozen_bn_finetune_mfu"
            note = ("finetune config: use_global_stats=True (batch_norm's "
                    "own flag; not from-scratch training semantics) — "
                    "RN50_ABLATION.md")
        else:
            metric = ("resnet50_train_mfu" if on_tpu
                      else "resnet_tiny_train_smoke")
            note = ("batch-stat BN; ~28% is the measured v5e ceiling for "
                    "this config (5 refuted levers + byte roofline, "
                    "RN50_ABLATION.md)")
        rec = {
            "metric": metric,
            "value": round(mfu * 100, 2),
            "unit": "% MFU",
            "vs_baseline": round(mfu / 0.35, 4),
            "step_time_s": round(dt, 4),
            "images_per_s": round(batch / dt, 1),
            "device": str(dev), "batch": batch,
            "loss_first_last": [round(l0, 3), round(lN, 3)],
            "note": note,
        }
        if frozen_bn:
            # from random init the frozen-identity BN saturates the
            # softmax, so the loss pair is meaningless for this config —
            # the line measures the finetune step time/MFU only
            del rec["loss_first_last"]
        emit(rec)
        if not frozen_bn:
            _emit_runtime_mfu("resnet50", exe, mfu)
            _emit_fusion_line("resnet50", exe, scope, loss.name, feed,
                              steps, dt,
                              _fusion_counts(since=fusion_before))


def bench_bert(dev, on_tpu, peak):
    import jax
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import Program, Scope, program_guard, \
        scope_guard
    from paddle_tpu.models import transformer as T

    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        if on_tpu:
            cfg = T.BertConfig()           # BERT-base
            batch, seq_len, steps = 128, 128, 64
        else:                              # CPU smoke fallback
            cfg = T.BertConfig(vocab_size=1024, d_model=128, n_layer=2,
                               n_head=4, d_inner=256, max_pos=128)
            batch, seq_len, steps = 4, 64, 2
            peak = 1e12

        # fused chunked head: the [tokens, vocab] logits never hit HBM;
        # arange_pos: position embedding as a table slice (no scatter bwd)
        feeds, logits, loss = T.build_bert_pretrain(cfg, seq_len,
                                                    fused_head=True,
                                                    arange_pos=True)
        optimizer = pt.amp.decorate(opt.AdamOptimizer(learning_rate=1e-4))
        optimizer.minimize(loss)

        exe = pt.Executor()
        exe.run(pt.default_startup_program(), scope=scope)

        rng = np.random.RandomState(0)
        feed = {
            "src_ids": jax.device_put(rng.randint(
                1, cfg.vocab_size, (batch, seq_len)).astype(np.int32)),
            "lm_label": jax.device_put(rng.randint(
                0, cfg.vocab_size, (batch, seq_len)).astype(np.int32)),
        }

        lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
        float(np.asarray(lv))              # warmup / compile

        t0 = time.perf_counter()
        for _ in range(steps):
            lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope,
                          return_numpy=False)
        float(np.asarray(lv))              # sync
        dt = (time.perf_counter() - t0) / steps

        # matmul param count (excludes gather-only embeddings)
        d, L, F, V = cfg.d_model, cfg.n_layer, cfg.d_inner, cfg.vocab_size
        n_matmul = L * (4 * d * d + 2 * d * F) + V * d
        tokens = batch * seq_len
        flops = 6 * n_matmul * tokens + 12 * L * d * seq_len * tokens
        mfu = flops / dt / peak
        emit({
            "metric": "bert_base_train_mfu" if on_tpu
            else "bert_tiny_train_smoke",
            "value": round(mfu * 100, 2),
            "unit": "% MFU",
            "vs_baseline": round(mfu / 0.35, 4),
            "step_time_s": round(dt, 4),
            "tokens_per_s": round(tokens / dt, 1),
            "device": str(dev),
            "batch": batch, "seq_len": seq_len,
        })
        _emit_runtime_mfu("bert", exe, mfu)


def bench_bert_masked(dev, on_tpu, peak):
    """The LARK/BERT pretraining recipe proper: mask_pos gather before the
    LM head, so the [*, vocab] projection runs on 20 masked positions per
    sequence instead of all 128 (VERDICT r3 ask #2 — separate line; the
    dense-MLM line above stays the honest upper-bound config)."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import Program, Scope, program_guard, \
        scope_guard
    from paddle_tpu.models import transformer as T

    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        if on_tpu:
            cfg = T.BertConfig()
            batch, seq_len, n_mask, steps = 128, 128, 20, 64
        else:
            cfg = T.BertConfig(vocab_size=1024, d_model=128, n_layer=2,
                               n_head=4, d_inner=256, max_pos=128)
            batch, seq_len, n_mask, steps = 4, 64, 5, 2
            peak = 1e12
        feeds, logits, loss = T.build_bert_pretrain(
            cfg, seq_len, fused_head=True, arange_pos=True,
            masked_gather=n_mask)
        optimizer = pt.amp.decorate(opt.AdamOptimizer(learning_rate=1e-4))
        optimizer.minimize(loss)
        exe = pt.Executor()
        exe.run(pt.default_startup_program(), scope=scope)

        rng = np.random.RandomState(0)
        pos = np.stack([rng.choice(seq_len, n_mask, replace=False) + i * seq_len
                        for i in range(batch)]).astype(np.int32)
        feed = {
            "src_ids": jax.device_put(rng.randint(
                1, cfg.vocab_size, (batch, seq_len)).astype(np.int32)),
            "mask_pos": jax.device_put(pos),
            "lm_label": jax.device_put(rng.randint(
                1, cfg.vocab_size, (batch, n_mask)).astype(np.int32)),
        }
        lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
        l0 = float(np.asarray(lv))
        t0 = time.perf_counter()
        for _ in range(steps):
            lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope,
                          return_numpy=False)
        lN = float(np.asarray(lv))
        dt = (time.perf_counter() - t0) / steps

        d, L, F, V = cfg.d_model, cfg.n_layer, cfg.d_inner, cfg.vocab_size
        tokens = batch * seq_len
        flops = 6 * L * (4 * d * d + 2 * d * F) * tokens \
            + 6 * V * d * batch * n_mask \
            + 12 * L * d * seq_len * tokens
        mfu = flops / dt / peak
        emit({
            "metric": "bert_base_masked_mlm_train_mfu" if on_tpu
            else "bert_masked_tiny_train_smoke",
            "value": round(mfu * 100, 2),
            "unit": "% MFU",
            "vs_baseline": round(mfu / 0.35, 4),
            "step_time_s": round(dt, 4),
            "tokens_per_s": round(tokens / dt, 1),
            "device": str(dev), "batch": batch, "seq_len": seq_len,
            "masked_per_seq": n_mask,
            "loss_first_last": [round(l0, 3), round(lN, 3)],
        })
        _emit_runtime_mfu("bert_masked", exe, mfu)


def bench_gpt_causal(dev, on_tpu, peak):
    """Decoder-only causal LM (GPT recipe, BERT-base dims) at seq 2048:
    the causal flash kernel skips masked key blocks outright, so the
    quadratic attention term halves vs a masked dense chain — the
    decoder-family counterpart of the long-context lines.  FLOPs count
    the causal attention at T²/2."""
    if not on_tpu:
        return
    import jax
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import Program, Scope, program_guard, \
        scope_guard
    from paddle_tpu.models import transformer as T

    batch, seq_len, steps = 8, 2048, 24
    cfg = T.BertConfig(max_pos=seq_len)
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        feeds, logits, loss = T.build_gpt_pretrain(
            cfg, seq_len, fused_head=True, attn_impl="auto", dropout=0.0)
        optimizer = pt.amp.decorate(opt.AdamOptimizer(learning_rate=1e-4))
        optimizer.minimize(loss)
        exe = pt.Executor()
        exe.run(pt.default_startup_program(), scope=scope)
        rng = np.random.RandomState(0)
        ids = rng.randint(1, cfg.vocab_size,
                          (batch, seq_len)).astype(np.int32)
        labels = np.roll(ids, -1, axis=1)
        labels[:, -1] = 0
        feed = {"src_ids": jax.device_put(ids),
                "lm_label": jax.device_put(labels)}
        lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
        l0 = float(np.asarray(lv))
        t0 = time.perf_counter()
        for _ in range(steps):
            lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope,
                          return_numpy=False)
        lN = float(np.asarray(lv))
        dt = (time.perf_counter() - t0) / steps
        d, L, F, V = cfg.d_model, cfg.n_layer, cfg.d_inner, cfg.vocab_size
        tokens = batch * seq_len
        flops = 6 * (L * (4 * d * d + 2 * d * F) + V * d) * tokens \
            + 6 * L * d * seq_len * tokens          # causal: T^2/2
        mfu = flops / dt / peak
        emit({
            "metric": "gpt_causal2k_train_mfu",
            "value": round(mfu * 100, 2),
            "unit": "% MFU",
            "vs_baseline": round(mfu / 0.35, 4),
            "step_time_s": round(dt, 4),
            "tokens_per_s": round(tokens / dt, 1),
            "device": str(dev), "batch": batch, "seq_len": seq_len,
            "attn": "pallas flash causal (auto)",
            "loss_first_last": [round(l0, 3), round(lN, 3)],
            "note": ("residual vs 35% is the measured dh=64 shape "
                     "ceiling: softmax VPU tile cost scales as 1/d "
                     "(skeleton microbench, LONGCTX_ABLATION.md r5)"),
        })
        _emit_runtime_mfu("gpt_causal", exe, mfu)


def bench_bert_long(dev, on_tpu, peak):
    """Long-context line: BERT-base at seq 4096 where the Pallas flash
    kernel is the measured winner over XLA's O(T²) attention (v5e r4:
    flash 298 ms vs base 407 ms per step; beyond ~8k tokens the base
    path OOMs outright and flash is the only option — 11 ms fwd /
    45 ms f+b at [12,16384,64] attention-only, LONGCTX_ABLATION.md)."""
    if not on_tpu:
        return                             # pallas path is TPU-only
    import jax
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import Program, Scope, program_guard, \
        scope_guard
    from paddle_tpu.models import transformer as T

    batch, seq_len, steps = 4, 4096, 16
    cfg = T.BertConfig(max_pos=seq_len)
    results = {}
    for impl in ("auto", "base"):
        scope = Scope()
        with scope_guard(scope), program_guard(Program(), Program()):
            feeds, logits, loss = T.build_bert_pretrain(
                cfg, seq_len, fused_head=True, arange_pos=True,
                attn_impl=impl, dropout=0.0)
            optimizer = pt.amp.decorate(
                opt.AdamOptimizer(learning_rate=1e-4))
            optimizer.minimize(loss)
            exe = pt.Executor()
            exe.run(pt.default_startup_program(), scope=scope)
            rng = np.random.RandomState(0)
            feed = {
                "src_ids": jax.device_put(rng.randint(
                    1, cfg.vocab_size,
                    (batch, seq_len)).astype(np.int32)),
                "lm_label": jax.device_put(rng.randint(
                    0, cfg.vocab_size,
                    (batch, seq_len)).astype(np.int32)),
            }
            lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
            float(np.asarray(lv))
            t0 = time.perf_counter()
            for _ in range(steps):
                lv, = exe.run(feed=feed, fetch_list=[loss.name],
                              scope=scope, return_numpy=False)
            float(np.asarray(lv))
            results[impl] = (time.perf_counter() - t0) / steps
    dt = results["auto"]
    d, L, F, V = cfg.d_model, cfg.n_layer, cfg.d_inner, cfg.vocab_size
    tokens = batch * seq_len
    flops = 6 * (L * (4 * d * d + 2 * d * F) + V * d) * tokens \
        + 12 * L * d * seq_len * tokens
    mfu = flops / dt / peak
    emit({
        "metric": "bert_long4k_train_mfu",
        "value": round(mfu * 100, 2),
        "unit": "% MFU",
        "vs_baseline": round(mfu / 0.35, 4),
        "step_time_s": round(dt, 4),
        "xla_base_step_time_s": round(results["base"], 4),
        "flash_speedup_vs_xla": round(results["base"] / dt, 3),
        "device": str(dev), "batch": batch, "seq_len": seq_len,
        "attn": "pallas flash (auto)",
    })

    # 8k/16k: where the tuned flash blocks compound (the XLA base path
    # OOMs beyond ~8k — flash is the only option, so no "base" column)
    for seq_len, batch in ((8192, 2), (16384, 1)):
        cfg = T.BertConfig(max_pos=seq_len)
        scope = Scope()
        with scope_guard(scope), program_guard(Program(), Program()):
            feeds, logits, loss = T.build_bert_pretrain(
                cfg, seq_len, fused_head=True, arange_pos=True,
                attn_impl="auto", dropout=0.0)
            optimizer = pt.amp.decorate(
                opt.AdamOptimizer(learning_rate=1e-4))
            optimizer.minimize(loss)
            exe = pt.Executor()
            exe.run(pt.default_startup_program(), scope=scope)
            rng = np.random.RandomState(0)
            feed = {
                "src_ids": jax.device_put(rng.randint(
                    1, cfg.vocab_size,
                    (batch, seq_len)).astype(np.int32)),
                "lm_label": jax.device_put(rng.randint(
                    0, cfg.vocab_size,
                    (batch, seq_len)).astype(np.int32)),
            }
            lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
            float(np.asarray(lv))
            t0 = time.perf_counter()
            for _ in range(8):
                lv, = exe.run(feed=feed, fetch_list=[loss.name],
                              scope=scope, return_numpy=False)
            float(np.asarray(lv))
            dt = (time.perf_counter() - t0) / 8
        tokens = batch * seq_len
        flops = 6 * (L * (4 * d * d + 2 * d * F) + V * d) * tokens \
            + 12 * L * d * seq_len * tokens
        mfu = flops / dt / peak
        emit({
            "metric": f"bert_long{seq_len // 1024}k_train_mfu",
            "value": round(mfu * 100, 2),
            "unit": "% MFU",
            "vs_baseline": round(mfu / 0.35, 4),
            "step_time_s": round(dt, 4),
            "tokens_per_s": round(tokens / dt, 1),
            "device": str(dev), "batch": batch, "seq_len": seq_len,
            "attn": "pallas flash (auto)",
            "note": ("kernel measured within ~1.2-1.8x of its matmul-"
                     "only skeleton; residual = mandatory softmax VPU "
                     "work at dh=64 (LONGCTX_ABLATION.md r5)"),
        })


def bench_transformer_wmt(dev, on_tpu, peak):
    """Transformer-base WMT14 en-de (BASELINE target #4; ref recipe
    dist_transformer.py:958 transformer-base: d512/6L/8H/2048, shared
    37k BPE vocab).  Encoder-decoder training step, seq 256."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import Program, Scope, program_guard, \
        scope_guard
    from paddle_tpu.models import transformer as T

    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        if on_tpu:
            V, d, L, H, F = 37000, 512, 6, 8, 2048
            batch, seq_len, steps = 32, 256, 32
        else:
            V, d, L, H, F = 512, 64, 2, 2, 128
            batch, seq_len, steps = 2, 16, 2
            peak = 1e12
        # fused chunked head: the [tokens, 37k] logits never hit HBM
        # (measured r3: 44.8 ms vs 49.8 ms dense head = 37.7% vs 33.9% MFU)
        feeds, logits, loss = T.build_transformer_nmt(
            V, V, seq_len, d_model=d, n_layer=L, n_head=H, d_inner=F,
            fused_head=True)
        optimizer = pt.amp.decorate(opt.AdamOptimizer(learning_rate=1e-4))
        optimizer.minimize(loss)
        exe = pt.Executor()
        exe.run(pt.default_startup_program(), scope=scope)

        rng = np.random.RandomState(0)
        pos = np.tile(np.arange(seq_len), (batch, 1)).astype(np.int32)
        feed = {
            "src_ids": jax.device_put(rng.randint(
                1, V, (batch, seq_len)).astype(np.int32)),
            "src_pos": jax.device_put(pos),
            "trg_ids": jax.device_put(rng.randint(
                1, V, (batch, seq_len)).astype(np.int32)),
            "trg_pos": jax.device_put(pos),
            "label": jax.device_put(rng.randint(
                1, V, (batch, seq_len)).astype(np.int32)),
        }
        lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
        l0 = float(np.asarray(lv))
        t0 = time.perf_counter()
        for _ in range(steps):
            lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope,
                          return_numpy=False)
        lN = float(np.asarray(lv))
        dt = (time.perf_counter() - t0) / steps

        tokens = batch * seq_len
        enc_m = L * (4 * d * d + 2 * d * F)
        dec_m = L * (8 * d * d + 2 * d * F)
        head = V * d
        flops = 6 * (enc_m + dec_m + head) * tokens \
            + 12 * L * d * seq_len * tokens \
            + 24 * L * d * seq_len * tokens
        mfu = flops / dt / peak
        emit({
            "metric": "transformer_wmt14_train_mfu" if on_tpu
            else "transformer_tiny_train_smoke",
            "value": round(mfu * 100, 2),
            "unit": "% MFU",
            "vs_baseline": round(mfu / 0.35, 4),
            "step_time_s": round(dt, 4),
            "tokens_per_s": round(tokens / dt, 1),
            "device": str(dev), "batch": batch, "seq_len": seq_len,
            "loss_first_last": [round(l0, 3), round(lN, 3)],
        })
        _emit_runtime_mfu("transformer_wmt", exe, mfu)


def bench_deepfm_ps():
    """BASELINE workload #5: DeepFM distributed sparse training in PS
    mode — 1 native pserver + 2 trainer processes on the host CPU (the
    PS plane is the reference's CPU sparse path; it never touches the
    chip).  Delegates to tools/bench_deepfm_ps.py and passes its JSON
    lines through (sync, async, and geo-SGD modes — ref
    distribute_transpiler.py:131)."""
    import subprocess
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "bench_deepfm_ps.py")
    try:
        r = subprocess.run([sys.executable, tool], capture_output=True,
                           text=True, timeout=2900)
        out = r.stdout or ""
        err = r.stderr or ""
    except subprocess.TimeoutExpired as te:
        # salvage the modes that DID complete before the timeout
        out = (te.stdout or b"")
        out = out.decode() if isinstance(out, bytes) else out
        err = f"timeout after {te.timeout}s"
    lines = [l for l in out.splitlines() if l.startswith("{\"metric\"")]
    if not lines:
        raise RuntimeError("tools/bench_deepfm_ps.py emitted no metric "
                           f"line: {(err or out)[-300:]}")
    for line in lines:
        print(line)
        RESULTS.append(json.loads(line))


def bench_dispatch_overhead(dev, on_tpu, peak):
    """Dispatch-overhead line (host framework tax per steady-state step):
    50 lazy-fetch steps of a small MLP train step, measured by the
    executor's OWN dispatch counters (`dispatch_stats()`), so the number
    is host time inside `Executor.run` up to async-dispatch return —
    device compute and the closing sync excluded by construction.  Runs on
    CPU and TPU alike; tracked from this PR onward so hot-path regressions
    show in the BENCH trajectory."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.framework import Program, Scope, program_guard, \
        scope_guard

    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        x = layers.data("x", shape=[64], dtype="float32")
        h = layers.fc(x, size=64, act="relu")
        loss = layers.mean(layers.fc(h, size=64))
        pt.optimizer.SGD(0.01).minimize(loss)
        exe = pt.Executor()
        exe.run(pt.default_startup_program(), scope=scope)
        # feed staged once, like every other line: per-step H2D would
        # measure the copy, and a real input pipeline prefetches anyway
        feed = {"x": jax.device_put(np.ones((32, 64), np.float32))}
        lv, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
        float(np.asarray(lv))              # warmup: trace + compile

        steps = 50
        s0 = exe.dispatch_stats()
        t0 = time.perf_counter()
        for _ in range(steps):
            h_, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope,
                          return_numpy=False)
        h_.numpy()                         # ONE sync bounds the pipeline
        wall_us = (time.perf_counter() - t0) * 1e6 / steps
        s1 = exe.dispatch_stats()

        d = {k: s1[k] - s0[k] for k in
             ("time_to_dispatch_us", "host_block_us", "cache_hits",
              "traces", "steps_dispatched", "fetch_materializations")}
        emit({
            "metric": "dispatch_overhead_us_per_step",
            "value": round(d["time_to_dispatch_us"] / steps, 1),
            "unit": "us/step (lower is better)",
            "vs_baseline": 0,              # no BASELINE target: trajectory metric
            "wall_us_per_step": round(wall_us, 1),
            "host_block_us_per_step": round(d["host_block_us"] / steps, 1),
            "cache_hits": d["cache_hits"],
            "retraces": d["traces"],
            "fetch_materializations": d["fetch_materializations"],
            "steps": d["steps_dispatched"],
            "device": str(dev),
            "note": ("host time in Executor.run to async-dispatch return, "
                     "from executor dispatch counters; lazy fetches, "
                     "in-flight throttle=2; materializations happen only "
                     "at the final sync"),
        })


def bench_comms(dev, on_tpu, peak):
    """``comms:allreduce_mlp`` line: the collective-communication
    observability plane's trajectory metric — analytic vs measured
    collective bytes (MUST match exactly: the per-launch accounting is
    the static plan priced per dispatch), the analytic comm-time
    estimate and comm-vs-compute bound verdict, the measured bus
    bandwidth (algorithm bandwidth over link peak — the network MFU),
    and the wait fraction of the measured comm time.  This is the
    before/after gate the quantized-collectives arc inherits: a codec
    halving the wire bytes must move ``bytes_per_step`` and ``bus_bw``
    here, not in a one-off notebook.

    The collective shard_map path needs >= 2 local devices, so the run
    happens in a subprocess with a 2-virtual-device CPU mesh (the
    tools/comms_smoke.py single-process mode — one measurement path for
    CI and bench)."""
    import subprocess
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    for k in ("PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM",
              "PADDLE_GANG_COORD", "PADDLE_GANG_DIR",
              "FLAGS_fault_inject"):
        env.pop(k, None)
    r = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tools", "comms_smoke.py"), "--single-json"],
        env=env, capture_output=True, text=True, timeout=900)
    rec = None
    for line in (r.stdout or "").splitlines():
        if line.startswith("COMMS_SINGLE "):
            rec = json.loads(line[len("COMMS_SINGLE "):])
    if r.returncode != 0 or rec is None:
        raise RuntimeError(
            f"comms child failed rc={r.returncode}: "
            f"{(r.stderr or r.stdout or '')[-300:]}")
    plan = rec["plan"]
    exact = rec["measured_bytes"] == rec["expected_bytes"]
    comm_ms = rec["measured_comm_ms"]
    emit({
        "metric": "comms:allreduce_mlp",
        "value": round(rec["bus_bw"], 9),
        "unit": "measured bus bandwidth / link peak (network MFU)",
        "vs_baseline": 0,             # trajectory metric, no BASELINE
        "nranks": plan["nranks"],
        "collectives": plan["collectives"],
        "bytes_per_step": plan["payload_bytes"],
        "wire_bytes_per_step": plan["wire_bytes"],
        "measured_bytes": rec["measured_bytes"],
        "bytes_exact": exact,
        "analytic_comm_ms": round(plan["est_ms"], 6),
        "analytic_compute_ms": round(plan["compute_ms"], 6),
        "bound": plan["bound"],
        "measured_comm_ms": round(comm_ms, 3),
        "wait_frac": round(rec["measured_wait_ms"] / comm_ms, 4)
        if comm_ms > 0 else 0.0,
        "plan_fingerprint": plan["fingerprint"][:12],
        "note": ("2-virtual-device GradAllReduce MLP; bytes_exact gates "
                 "measured == static plan; the quantized-collectives "
                 "arc's before/after rides this line"),
    })
    if not exact:
        raise RuntimeError(
            f"measured collective bytes {rec['measured_bytes']} != "
            f"plan {rec['expected_bytes']}")


def bench_gspmd(dev, on_tpu, peak):
    """``gspmd:transformer`` line: the model-parallelism trajectory
    metric — a transformer whose single-chip static plan exceeds the
    budget trains on a dp:2 x mp:2 mesh under the planner-chosen rule
    table with loss parity, and ZeRO-1 + mp sharding shrink the
    runtime accountant's live ``opt_state`` bytes.  ``value`` is the
    per-device opt_state ratio (sharded/single-chip); the hard gate is
    ratio <= ~1/dp_degree + mp slack — a regression that silently
    re-replicates optimizer state fails the bench, not a notebook.

    The pjit path needs >= 2 local devices, so the run happens in a
    subprocess with a 4-virtual-device CPU mesh (the
    tools/gspmd_smoke.py single-process mode — one measurement path
    for CI and bench)."""
    import subprocess
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    for k in ("PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM",
              "PADDLE_GANG_COORD", "PADDLE_GANG_DIR",
              "FLAGS_fault_inject"):
        env.pop(k, None)
    r = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tools", "gspmd_smoke.py"), "--single-json"],
        env=env, capture_output=True, text=True, timeout=900)
    rec = None
    for line in (r.stdout or "").splitlines():
        if line.startswith("GSPMD_SINGLE "):
            rec = json.loads(line[len("GSPMD_SINGLE "):])
    if r.returncode != 0 or rec is None:
        raise RuntimeError(
            f"gspmd child failed rc={r.returncode}: "
            f"{(r.stderr or r.stdout or '')[-300:]}")
    dp = rec["mesh_axes"]["dp"]
    ratio = rec["opt_state_ratio"]
    emit({
        "metric": "gspmd:transformer",
        "value": round(ratio, 4),
        "unit": "sharded/single-chip opt_state live bytes "
                "(per-device accountant; ZeRO-1 target ~1/dp)",
        "vs_baseline": 0,             # trajectory metric, no BASELINE
        "mesh": rec["mesh_axes"],
        "chosen_rules": rec["chosen_rules"],
        "single_chip_peak_bytes": rec["single_chip_peak_bytes"],
        "per_shard_peak_bytes": rec["per_shard_peak_bytes"],
        "budget_bytes": rec["budget_bytes"],
        "sharded_params": rec["sharded_params"],
        "bound": rec["bound"],
        "max_rel_loss_diff": round(rec["max_rel_diff"], 8),
        "opt_state_bytes": {"single": rec["opt_state_bytes_single"],
                            "sharded": rec["opt_state_bytes_sharded"]},
        "steps_per_s": {
            "single": round(rec["steps_per_s_single"], 3),
            "sharded": round(rec["steps_per_s_sharded"], 3)},
        "headroom_bytes": rec["headroom_bytes"],
        "note": ("planner-chosen table on a 4-virtual-device CPU mesh; "
                 "single-chip static plan exceeds the budget, per-shard "
                 "plan fits; parity rtol 2e-4"),
    })
    if ratio > 1.0 / dp + 0.2:
        raise RuntimeError(
            f"ZeRO-1 opt_state shrink regressed: ratio {ratio:.3f} > "
            f"1/dp ({1.0 / dp:.2f}) + slack")
    if rec["max_rel_diff"] > 2e-4:
        raise RuntimeError(
            f"sharded loss parity broke: {rec['max_rel_diff']}")


def bench_xprof(dev, on_tpu, peak):
    """``xprof:mlp`` line: the measured-attribution pipeline end to end
    — capture a real profiler window over a small MLP train loop, let
    the post-close hook parse it into ``summary.json`` +
    ``paddle_tpu_step_mfu_measured``, and report measured MFU with the
    idle fraction and per-op-class measured device-time shares riding
    along.  The hard gate is the pipeline itself (a window must parse
    and publish); measured-vs-analytic MFU is reported as a ratio, not
    gated — on CPU the gap IS the finding (dispatch slack the analytic
    estimate cannot see)."""
    import tempfile
    import paddle_tpu as pt
    from paddle_tpu import layers, monitor, profiler
    from paddle_tpu.framework import Program, Scope, program_guard, \
        scope_guard
    from paddle_tpu.analysis import device_profile

    sdir = tempfile.mkdtemp(prefix="bench_xprof_")
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        x = layers.data("x", shape=[256], dtype="float32")
        h = layers.fc(x, size=512, act="relu")
        loss = layers.mean(layers.fc(h, size=128))
        pt.optimizer.SGD(0.01).minimize(loss)
        from paddle_tpu.framework import Executor
        from paddle_tpu.framework.executor import last_step_id
        exe = Executor()
        exe.run(pt.default_startup_program(), scope=scope)
        feed = {"x": np.random.rand(64, 256).astype(np.float32)}
        for _ in range(4):                       # warmup + compile
            exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
        profiler.SAMPLER.configure(0, 6, sdir, 2)
        profiler.SAMPLER.trigger_window(last_step_id(), trigger="bench")
        for _ in range(10):
            exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
        profiler.SAMPLER.close()
        profiler.SAMPLER.configure(0, 4, "", 8)   # leave it disarmed
    with open(os.path.join(sdir, "manifest.json")) as f:
        windows = json.load(f)["windows"]
    spath = os.path.join(windows[-1]["dir"], "summary.json")
    with open(spath) as f:
        s = json.load(f)
    measured = s["measured"]["mfu_measured"]
    analytic = monitor.REGISTRY.get("paddle_tpu_step_mfu").value(
        executor=str(exe._stats.serial))
    gauge = monitor.REGISTRY.get("paddle_tpu_step_mfu_measured").value()
    if not measured or gauge <= 0:
        raise RuntimeError(
            f"xprof pipeline produced no measured MFU: {s['measured']}")
    emit({
        "metric": "xprof:mlp",
        "value": round(measured * 100, 2),
        "unit": "% measured MFU (device-busy time per step)",
        "vs_baseline": 0,
        "analytic_pct": round(analytic * 100, 2),
        "measured_vs_analytic": round(measured / analytic, 3)
        if analytic > 0 else None,
        "idle_frac": s["idle_frac"],
        "n_steps": s["n_steps"],
        "per_class_share": s["per_class_share"],
        "note": ("captured window -> post-close summary.json -> "
                 "paddle_tpu_step_mfu_measured; idle_frac is "
                 "dispatch/host slack the analytic gauge folds into "
                 "its denominator"),
    })
    shutil.rmtree(sdir, ignore_errors=True)


def bench_numerics(dev, on_tpu, peak):
    """Cost-of-the-plane trajectory lines: steps/s of a small MLP train
    loop at FLAGS_numerics=off/sentinel/full — ``numerics:mlp`` carries
    the sentinel overhead % (the tier meant to stay on in production,
    budget < 5%) with the full-mode overhead riding along — plus
    ``numerics_loss_fp:mlp``, a sha1 fingerprint of the per-step loss
    trajectory under each mode.  The fingerprints MUST match: the stats
    are pure observers, and this line is the loss-parity gate the
    quantized-collectives arc will reuse (a codec change that perturbs
    the trajectory flips ``match`` to false in the bench record, not in
    a user's training run)."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.flags import get_flags, set_flags
    from paddle_tpu.framework import Program, Scope, program_guard, \
        scope_guard
    from paddle_tpu.analysis import numerics

    saved = get_flags("FLAGS_numerics")["FLAGS_numerics"]
    steps, warmup = 40, 3
    results = {}

    def one_mode(mode):
        set_flags({"FLAGS_numerics": mode})
        scope = Scope()
        with scope_guard(scope), program_guard(Program(), Program()):
            pt.default_main_program().random_seed = 7
            pt.default_startup_program().random_seed = 7
            # sized so per-element math dominates the step (~5-10 ms on
            # the CPU smoke): at micro-step scale the fixed per-step
            # cost (one 6-float D2H + frame decode) would read as tens
            # of percent and measure the harness, not the plane
            x = layers.data("x", shape=[256], dtype="float32")
            h = layers.fc(x, size=512, act="relu")
            h = layers.fc(h, size=512, act="relu")
            loss = layers.mean(layers.fc(h, size=256))
            pt.optimizer.SGD(0.01).minimize(loss)
            exe = pt.Executor()
            exe.run(pt.default_startup_program(), scope=scope)
            feed = {"x": jax.device_put(
                np.linspace(-1, 1, 256 * 256, dtype=np.float32)
                .reshape(256, 256))}
            handles = []
            for _ in range(warmup):
                exe.run(feed=feed, fetch_list=[loss.name], scope=scope,
                        return_numpy=False)
            exe.drain()
            t0 = time.perf_counter()
            for _ in range(steps):
                h_, = exe.run(feed=feed, fetch_list=[loss.name],
                              scope=scope, return_numpy=False)
                handles.append(h_)
            handles[-1].numpy()            # one sync bounds the pipeline
            dt = time.perf_counter() - t0
            losses = [float(h.numpy()) for h in handles]
            numerics.ENGINE.poll(force=True)
            return steps / dt, numerics.loss_fingerprint(losses)

    try:
        for mode in ("off", "sentinel", "full"):
            results[mode] = one_mode(mode)
    finally:
        set_flags({"FLAGS_numerics": saved})

    sps = {m: r[0] for m, r in results.items()}
    fps = {m: r[1] for m, r in results.items()}
    ovh = {m: round((sps["off"] / sps[m] - 1.0) * 100, 2)
           for m in ("sentinel", "full")}
    emit({
        "metric": "numerics:mlp",
        "value": ovh["sentinel"],
        "unit": "% steps/s overhead at FLAGS_numerics=sentinel "
                "(lower is better; budget < 5%)",
        "vs_baseline": 0,
        "steps_s_off": round(sps["off"], 1),
        "steps_s_sentinel": round(sps["sentinel"], 1),
        "steps_s_full": round(sps["full"], 1),
        "overhead_full_pct": ovh["full"],
        "device": str(dev),
    })
    emit({
        "metric": "numerics_loss_fp:mlp",
        "value": int(fps["off"] == fps["sentinel"] == fps["full"]),
        "unit": "loss-trajectory parity across numerics modes (1 = "
                "bit-identical — the quantized-collectives parity gate)",
        "vs_baseline": 0,
        "fp_off": fps["off"], "fp_sentinel": fps["sentinel"],
        "fp_full": fps["full"],
        "match": bool(fps["off"] == fps["sentinel"] == fps["full"]),
    })


def bench_memory(dev, on_tpu, peak):
    """Static HBM planner vs the runtime memory plane: for two
    workloads, run a few real steps, then pair the planner's
    step-boundary live-byte estimate
    (``analysis.plan_memory(...).steady_bytes`` at the true batch)
    against the measured live device bytes — read through
    ``hbm.measure_live_bytes``, the SAME reader the runtime accountant
    publishes its gauges from, so bench and the live plane can never
    disagree on what 'measured' means.  One ``memory:<workload>`` line
    each (`value` = estimate/measured, 1.0 = exact) plus an
    ``hbm:<workload>`` line pairing the accountant's live/peak/drift
    gauges against the plan — the plan-vs-measured gate the GSPMD
    sharding chooser's headroom signal rides on."""
    import gc

    import jax
    import paddle_tpu as pt
    from paddle_tpu import hbm, layers
    from paddle_tpu.analysis import plan_memory
    from paddle_tpu.framework import Program, Scope, program_guard, \
        scope_guard
    from paddle_tpu.monitor import REGISTRY

    def mlp_adam():
        x = layers.data("x", shape=[256], dtype="float32")
        h = layers.fc(x, size=1024, act="relu")
        h = layers.fc(h, size=1024, act="relu")
        loss = layers.mean(layers.fc(h, size=256))
        pt.optimizer.Adam(1e-3).minimize(loss)
        rng = np.random.RandomState(0)
        return {"x": rng.rand(64, 256).astype(np.float32)}, loss

    def wide_embedding():
        ids = layers.data("ids", shape=[1], dtype="int64")
        emb = layers.embedding(ids, size=[20000, 128])
        loss = layers.mean(layers.fc(emb, size=1))
        pt.optimizer.SGD(0.1).minimize(loss)
        rng = np.random.RandomState(0)
        return {"ids": rng.randint(0, 20000, (64, 1)).astype(np.int64)}, \
            loss

    for name, build in (("mlp_adam", mlp_adam),
                        ("wide_embedding", wide_embedding)):
        gc.collect()
        base = hbm.measure_live_bytes()
        scope = Scope()
        with scope_guard(scope), program_guard(Program(), Program()):
            feed_np, loss = build()
            prog = pt.default_main_program()
            cp = pt.CompiledProgram(prog)
            exe = pt.Executor()
            exe.run(pt.default_startup_program(), scope=scope)
            feed = {k: jax.device_put(v) for k, v in feed_np.items()}
            lv = None
            for _ in range(3):
                lv, = exe.run(cp, feed=feed, fetch_list=[loss.name],
                              scope=scope, return_numpy=False)
            lv.numpy()                       # sync the pipeline
            exe.drain()
            batch = next(iter(feed_np.values())).shape[0]
            plan = plan_memory(prog, (loss.name,), batch_size=batch)
            gc.collect()
            measured = hbm.measure_live_bytes() - base
            est = plan.steady_bytes
            emit({
                "metric": f"memory:{name}",
                "value": round(est / measured, 3) if measured else 0,
                "unit": "estimate/measured",
                "vs_baseline": 0,
                "estimate_bytes": int(est),
                "measured_bytes": int(measured),
                "static_peak_bytes": int(plan.peak_bytes),
                "resident_bytes": int(plan.resident_bytes),
                "peak_op": plan.peak_op,
                "batch": int(batch),
                "device": str(dev),
                "note": ("estimate = planner steady (step-boundary live "
                         "set: persistables counted once under donation "
                         "+ staged feeds + pinned fetches); measured = "
                         "live device bytes delta over the workload, via "
                         "hbm.measure_live_bytes — the accountant's "
                         "reader"),
            })
            # runtime plane: drain the off-thread accountant and pair
            # its gauges against the same plan.  `value` is the
            # delta-based plan-vs-measured ratio (the planner's
            # established 1.000-1.006 band); the raw drift gauge
            # (process live / plan steady) rides along — it includes
            # residual allocations from earlier workloads, so the gated
            # number is the delta form.
            hbm.ACCOUNTANT.drain(10.0)

            def _gauge(fam):
                g = REGISTRY.get(fam)
                cells = g.series() if g is not None else []
                return float(cells[-1][1].get()) if cells else 0.0
            emit({
                "metric": f"hbm:{name}",
                "value": round(measured / est, 3) if est else 0,
                "unit": "measured/plan (runtime accountant reader; "
                        "1.0 = plan exact)",
                "vs_baseline": 0,
                "plan_steady_bytes": int(est),
                "measured_bytes": int(measured),
                "live_gauge_bytes": int(_gauge("paddle_tpu_hbm_live_bytes")),
                "peak_gauge_bytes": int(_gauge("paddle_tpu_hbm_peak_bytes")),
                "drift_gauge": round(
                    _gauge("paddle_tpu_hbm_plan_drift"), 4),
                "samples": int(monitor_counter_total(
                    "paddle_tpu_hbm_samples_total")),
                "batch": int(batch),
                "device": str(dev),
            })
        del scope
        gc.collect()


def monitor_counter_total(fam: str) -> float:
    from paddle_tpu.monitor import counter_totals
    return counter_totals().get(fam, 0.0)


def _serving_latencies(futs, timeout_s=600.0):
    """Per-request latency ms in submit order: poll done() so each
    completion is timestamped when it happens (a sequential result()
    walk would bill early completions for their predecessors' waits)."""
    pending = {i: t0 for i, (t0, _f) in enumerate(futs)}
    lat = [0.0] * len(futs)
    deadline = time.monotonic() + timeout_s
    while pending:
        if time.monotonic() > deadline:
            raise TimeoutError(f"{len(pending)} serving futures pending")
        done = [i for i in pending if futs[i][1].done()]
        now = time.perf_counter()
        for i in done:
            lat[i] = (now - pending.pop(i)) * 1e3
        if not done:
            time.sleep(0.0005)
    for _, f in futs:
        f.result(0)            # surface any request failure
    return lat


def _pctl(sorted_vals, q):
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(q * (len(sorted_vals) - 1) + 0.5))]


def bench_serving(dev, on_tpu, peak):
    """serving:bert / serving:gpt_causal — the heavy-traffic half of the
    north star: p50/p99 request latency and sustained QPS of the
    continuous-batching multi-tenant server under a synthetic open-loop
    client (Poisson arrivals at ~70% of the measured single-batch
    capacity), plus mean batch occupancy and the compile-bucket count.
    CPU smoke uses a toy config; TPU uses BERT-base dims."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu import serving
    from paddle_tpu.framework import Program, Scope, program_guard, \
        scope_guard
    from paddle_tpu.models import transformer as T

    if on_tpu:
        cfg = T.BertConfig(max_pos=512, dropout=0.0)
        buckets, max_batch, n_requests = (128, 256, 512), 8, 48
        dec_slots, dec_new, dec_requests, dec_page = 8, 32, 16, 64
    else:
        cfg = T.BertConfig(vocab_size=64, d_model=16, n_layer=2, n_head=2,
                           d_inner=32, max_pos=64, dropout=0.0)
        buckets, max_batch, n_requests = (8, 16), 4, 24
        dec_slots, dec_new, dec_requests, dec_page = 2, 4, 6, 4

    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        T.build_gpt_serving(cfg, buckets[0], attn_impl="base")
        exe0 = pt.Executor()
        exe0.run(pt.default_startup_program(), scope=scope, seed=11)

    def factory(seq):
        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            _, logits = T.build_gpt_serving(
                cfg, seq, attn_impl="auto" if on_tpu else "base")
        return prog, ["src_ids"], [logits.name]

    srv = serving.InferenceServer(factory, scope, buckets=buckets,
                                  max_batch=max_batch, batch_wait_ms=2.0)
    srv.warmup()
    srv.start()
    rng = np.random.RandomState(0)
    # calibrate: one full batch through the mid bucket bounds capacity
    mid = buckets[len(buckets) // 2]
    tcal0 = time.perf_counter()
    calib = [srv.submit("calib", {"src_ids": rng.randint(
        1, cfg.vocab_size, (mid,)).astype(np.int64)})
        for _ in range(max_batch)]
    for f in calib:
        f.result(timeout=600)
    step_s = max(1e-4, time.perf_counter() - tcal0)
    rate = 0.7 * max_batch / step_s          # requests/s, open loop
    gaps = rng.exponential(1.0 / rate, n_requests)
    futs = []
    t_open0 = time.perf_counter()
    for i in range(n_requests):
        n = int(rng.randint(buckets[0] // 2, buckets[-1] + 1))
        ids = rng.randint(1, cfg.vocab_size, (n,)).astype(np.int64)
        t0 = time.perf_counter()
        futs.append((t0, srv.submit("bench_a" if i % 2 else "bench_b",
                                    {"src_ids": ids})))
        time.sleep(float(gaps[i]))
    lat = sorted(_serving_latencies(futs))
    wall = time.perf_counter() - t_open0
    from paddle_tpu import monitor
    tot = monitor.counter_totals()
    occ_n = tot.get("paddle_tpu_serving_batch_occupancy_count", 0)
    occ = (tot.get("paddle_tpu_serving_batch_occupancy_sum", 0.0)
           / occ_n) if occ_n else 0.0
    stats = srv.compile_stats()
    emit({
        "metric": "serving:bert",
        "value": round(n_requests / wall, 2),
        "unit": "req/s sustained",
        "vs_baseline": 0,
        "p50_ms": round(_pctl(lat, 0.50), 2),
        "p99_ms": round(_pctl(lat, 0.99), 2),
        "open_loop_rate": round(rate, 2),
        "occupancy_mean": round(occ, 2),
        "buckets": list(buckets),
        "compiles": stats["traces"],
        "max_batch": max_batch,
        "device": str(dev),
        "d_model": cfg.d_model, "layers": cfg.n_layer,
    })
    srv.drain(120)
    srv.stop()

    # -- decode serving: paged-KV continuous batching ------------------
    eng = serving.DecodeEngine(cfg, scope, max_slots=dec_slots,
                               page_len=dec_page,
                               max_seq=min(cfg.max_pos, 8 * dec_page))
    dsrv = serving.DecodeServer(eng)
    dsrv.start()
    dfuts = []
    t0_all = time.perf_counter()
    for i in range(dec_requests):
        p = rng.randint(1, cfg.vocab_size,
                        (int(rng.randint(4, 2 * dec_page)),))
        t0 = time.perf_counter()
        dfuts.append((t0, dsrv.submit(
            "bench_a" if i % 2 else "bench_b", p,
            max_new_tokens=dec_new)))
    dlat = sorted(_serving_latencies(dfuts))
    dwall = time.perf_counter() - t0_all
    emit({
        "metric": "serving:gpt_causal",
        "value": round(dec_requests / dwall, 2),
        "unit": "req/s sustained",
        "vs_baseline": 0,
        "p50_ms": round(_pctl(dlat, 0.50), 2),
        "p99_ms": round(_pctl(dlat, 0.99), 2),
        "tokens_per_s": round(dec_requests * dec_new / dwall, 1),
        "new_tokens_per_req": dec_new,
        "kv_slots": dec_slots, "kv_page_len": dec_page,
        "decode_traces": eng.trace_count,
        "device": str(dev),
    })
    dsrv.drain(120)
    dsrv.stop()


def bench_serving_fleet(dev, on_tpu, peak):
    """``serving_fleet`` line: the self-driving-fleet trajectory metric
    — a real router + subprocess-replica topology under the closed-loop
    autoscaler.  ``value`` is the aggregate 2-replica QPS; the ride-along
    keys are the tail the fleet controls: p99 while the autoscaler
    absorbs a 24-client spike (spawning the second replica), p99 under a
    replica SIGKILL (death repair + idempotent replay), and the
    calibrated SLO objective both are judged against.  A regression that
    makes scale-up slower or failover lossier moves these numbers — the
    assertion-level contract lives in the tools/fleet_smoke.py scale
    drill (tests/test_autoscaler.py runs it slow-marked).

    Subprocess like comms/gspmd: the replicas are real processes (the
    spawn/retire actuators need something to SIGTERM), one measurement
    path for CI and bench."""
    import subprocess
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    for k in ("PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM",
              "PADDLE_GANG_COORD", "PADDLE_GANG_DIR",
              "FLAGS_fault_inject"):
        env.pop(k, None)
    r = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tools", "fleet_smoke.py"), "--bench"],
        env=env, capture_output=True, text=True, timeout=900)
    rec = None
    for line in (r.stdout or "").splitlines():
        if line.startswith("FLEET BENCH "):
            rec = json.loads(line[len("FLEET BENCH "):])
    if r.returncode != 0 or rec is None:
        raise RuntimeError(
            f"fleet bench child failed rc={r.returncode}: "
            f"{(r.stderr or r.stdout or '')[-300:]}")
    emit({
        "metric": "serving_fleet",
        "value": rec["aggregate_qps"],
        "unit": "req/s aggregate",
        "vs_baseline": 0,             # trajectory metric, no BASELINE
        "p99_spike_ms": rec["p99_spike_ms"],
        "p99_kill_ms": rec["p99_kill_ms"],
        "slo_p99_ms": rec["slo_p99_ms"],
        "replicas": rec["replicas"],
        "device": str(dev),
        "note": ("2-subprocess-replica fleet under the autoscaler; "
                 "p99_spike is the tail while the controller spawns the "
                 "second replica, p99_kill the tail through a SIGKILL "
                 "death repair"),
    })


def _telemetry_block(name, tel0, wall_s):
    """Per-workload telemetry line: registry-total deltas over one bench
    (compile time, host-block split by cause, dispatch tax, dataloader
    occupancy, steps/s) — the ledger every later perf PR reports through.
    Registry totals (not the live-executor aggregate): the bench's
    executors are dead by the time this runs, and their series survive
    only in the registry."""
    from paddle_tpu import monitor
    tel1 = monitor.counter_totals()

    def d(key):
        return tel1.get(key, 0) - tel0.get(key, 0)

    steps = int(d("paddle_tpu_executor_steps_dispatched"))
    occ_n = d("paddle_tpu_dataloader_queue_occupancy_count")
    block = {
        "steps": steps,
        "steps_per_s": round(steps / wall_s, 2) if wall_s > 0 else 0,
        "compiles": int(d("paddle_tpu_compile_total")),
        "compile_ms": round(d("paddle_tpu_compile_ms_sum"), 1),
        "time_to_dispatch_us_per_step": round(
            d("paddle_tpu_executor_time_to_dispatch_us") / max(steps, 1),
            1),
        "host_block_ms": {
            "materialize": round(
                d("paddle_tpu_executor_materialize_block_us") / 1e3, 2),
            "throttle": round(
                d("paddle_tpu_executor_throttle_block_us") / 1e3, 2),
            "benchmark_sync": round(
                d("paddle_tpu_executor_benchmark_sync_us") / 1e3, 2),
        },
        "fetch_materializations": int(
            d("paddle_tpu_executor_fetch_materializations")),
        "queue_occupancy_mean": round(
            d("paddle_tpu_dataloader_queue_occupancy_sum") / occ_n, 2)
        if occ_n else None,
    }
    emit({"metric": f"telemetry:{name}", "value": block["steps_per_s"],
          "unit": "steps/s", "vs_baseline": 0, "telemetry": block})


def _run_one(name, b, monitor):
    """Run one workload.  A workload that raises is recorded as a
    ``bench_error:`` line and the run goes on to the next one; returns
    whether it passed so ``main`` can exit non-zero.  No retry in a child:
    this process holds the chip, so a child that needs it cannot start."""
    tel0 = monitor.counter_totals()
    t0 = time.perf_counter()
    ok = True
    try:
        b()
    except Exception as e:  # boundary: record it, run the rest, fail at exit
        import traceback
        traceback.print_exc()
        ok = False
        emit({"metric": f"bench_error:{name}", "value": 0,
              "unit": "error", "vs_baseline": 0, "error": repr(e)[:600]})
    _telemetry_block(name, tel0, time.perf_counter() - t0)
    return ok


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    only = None
    if "--only" in argv:
        idx = argv.index("--only")
        if idx + 1 >= len(argv):
            sys.exit("usage: bench.py [--only WORKLOAD]")
        only = argv[idx + 1]
    dev, on_tpu, peak = _device_info()
    # placed by paddle_tpu.device.place_compile_cache at import:
    # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.cache/xla_compile
    import jax
    emit({"metric": "xla_compile_cache", "value": 1, "unit": "enabled",
          "vs_baseline": 0, "dir": jax.config.jax_compilation_cache_dir})
    from paddle_tpu import monitor
    benches = [
        # cheap + always first: the hot-path trajectory line must never be
        # starved by a slow hardware bench ahead of it
        ("dispatch_overhead",
         lambda: bench_dispatch_overhead(dev, on_tpu, peak)),
        # cheap static-analysis trajectory line: planner estimate vs
        # measured live bytes (runs on CPU and TPU alike)
        ("memory", lambda: bench_memory(dev, on_tpu, peak)),
        # numerics-plane cost + loss-parity fingerprint (cheap, CPU+TPU)
        ("numerics", lambda: bench_numerics(dev, on_tpu, peak)),
        # comms plane: analytic vs measured collective bytes/bandwidth
        # (cheap 2-virtual-device subprocess; CPU and TPU alike)
        ("comms", lambda: bench_comms(dev, on_tpu, peak)),
        # GSPMD plane: planner-chosen sharding, parity, ZeRO-1 opt_state
        # shrink (cheap 4-virtual-device subprocess; CPU and TPU alike)
        ("gspmd", lambda: bench_gspmd(dev, on_tpu, peak)),
        # measured-attribution plane: capture window -> summary.json ->
        # measured MFU gauge (cheap in-process loop; CPU and TPU alike)
        ("xprof", lambda: bench_xprof(dev, on_tpu, peak)),
        ("resnet50", lambda: bench_resnet50(dev, on_tpu, peak)),
        ("resnet50_frozen_bn",
         lambda: bench_resnet50(dev, on_tpu, peak, frozen_bn=True)),
        ("bert_long", lambda: bench_bert_long(dev, on_tpu, peak)),
        ("transformer_wmt", lambda: bench_transformer_wmt(dev, on_tpu, peak)),
        ("deepfm_ps", bench_deepfm_ps),
        ("gpt_causal", lambda: bench_gpt_causal(dev, on_tpu, peak)),
        # serving plane: p50/p99 + sustained QPS next to the MFU lines
        ("serving", lambda: bench_serving(dev, on_tpu, peak)),
        # fleet plane: aggregate QPS + tail under autoscaler-absorbed
        # spike and replica-kill failover (subprocess topology)
        ("serving_fleet", lambda: bench_serving_fleet(dev, on_tpu, peak)),
        ("bert_masked", lambda: bench_bert_masked(dev, on_tpu, peak)),
        # flagship metric printed last among the verbose lines
        ("bert", lambda: bench_bert(dev, on_tpu, peak)),
    ]
    if only is not None and only not in dict(benches):
        sys.exit(f"bench.py: unknown workload {only!r} "
                 f"(known: {', '.join(n for n, _ in benches)})")
    failed = [name for name, b in benches
              if (only is None or name == only)
              and not _run_one(name, b, monitor)]
    # FINAL line: compact all-metrics summary (metric/value/vs_baseline
    # only).  The driver's tail capture lost 3 of 10 verbose lines in
    # round 4; this one line carries every measurement and survives any
    # truncation that keeps the last line.
    print(json.dumps(
        [{"metric": r.get("metric"), "value": r.get("value"),
          "vs_baseline": r.get("vs_baseline")} for r in RESULTS],
        separators=(",", ":")))
    if failed:
        sys.exit(f"bench.py: workloads raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
