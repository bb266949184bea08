"""GPT-1 through the repo's public entry points: ``build_gpt_pretrain`` ->
the startup program on the device (weights from the seed) ->
``serving.DecodeEngine`` -> ``serving.DecodeServer``."""

import numpy as np

from .. import harness
from ._params import transformer_reference_params


def _bert_config(config):
    from paddle_tpu.models import transformer as T
    return T.BertConfig(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_layer=config["n_layer"], n_head=config["n_head"],
        d_inner=config["assumed"]["n_inner"], max_pos=config["n_positions"],
        dropout=0.0)


def build_server(config, traffic, seed, on_chip):
    import paddle_tpu as pt
    from paddle_tpu import serving
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models import transformer as T

    cfg = _bert_config(config)
    scope, startup = Scope(), Program()
    with scope_guard(scope), program_guard(Program(), startup):
        # only the parameters are wanted: the startup program makes them on
        # the device in one compiled call; the training program never runs
        T.build_gpt_pretrain(cfg, 8, dropout=0.0)
        exe = pt.Executor(pt.TPUPlace(0)) if on_chip else pt.Executor()
        exe.run(startup, scope=scope, seed=harness.exe_seed(seed))
    e = traffic["engine"]
    engine = serving.DecodeEngine(
        cfg, scope, max_slots=e["slots"], page_len=e["page_len"],
        max_seq=e["max_seq"])
    server = serving.DecodeServer(engine)
    return {"engine": engine, "server": server, "scope": scope, "cfg": cfg,
            "vocab": cfg.vocab_size}


def served_dtypes(engine):
    """What the engine holds on the device, by dtype name."""
    return {"kv_pool": sorted({str(engine.cache.k.dtype),
                               str(engine.cache.v.dtype)}),
            "weights": sorted({str(a.dtype) for a in engine.params.values()})}


def check_logits(config, built, rows, reference):
    """Prefill then decode through the paged cache (the logits the served
    path produced for the watched slots, position by position) against the
    reference's one full-context forward pass over the same tokens, and what
    the engine holds on the device against what the configuration states, by
    dtype: at the TPU's default matmul precision a bfloat16 pool gives the
    logits of a float32 one to within the tolerance, so values cannot tell
    them apart."""
    tol = config["logits_tolerance"]["max_abs_over_std"]
    stated = config["served_dtypes"]
    held = served_dtypes(built["engine"])
    dtypes_ok = all(held[k] == [stated[k]] for k in held)
    rows = {s: r for s, r in rows.items() if r}
    if not rows:
        return {"ok": False, "detail": "no logits were recorded"}
    width = max(len(r) for r in rows.values())
    width = -(-width // 32) * 32                  # few distinct shapes
    tokens = np.ones((len(rows), width), np.int32)
    for j, r in enumerate(rows.values()):
        tokens[j, :len(r)] = [t for t, _ in r]
    params = transformer_reference_params(
        built["scope"], built["cfg"].n_layer, "lm_out")
    ref = np.asarray(reference.logits(
        params, tokens, n_head=built["cfg"].n_head,
        eps=float(config["layer_norm_epsilon"])))
    worst, n_pos, flips = 0.0, 0, 0
    for j, r in enumerate(rows.values()):
        got = np.stack([lg for _, lg in r])
        want = ref[j, :len(r)]
        worst = max(worst, float(np.abs(got - want).max())
                    / float(want.std()))
        flips += int((got.argmax(-1) != want.argmax(-1)).sum())
        n_pos += len(r)
    ok = bool(dtypes_ok and np.isfinite(worst) and worst <= tol)
    return {"ok": ok, "detail":
            f"{len(rows)} sequences, {n_pos} positions (prefill and decode, "
            f"page boundary crossed: {width > built['engine'].page_len}); "
            f"max |logit - reference| = {worst:.5f} of the reference's "
            f"logit std (tolerance {tol}); argmax differs at {flips} "
            f"positions; held on the device {held} (stated {stated}: "
            f"{'as stated' if dtypes_ok else 'NOT as stated'})"}
