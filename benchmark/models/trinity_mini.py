"""Trinity-Mini pre-training, one chip's share, through the repo's public
entry points: ``models.transformer.build_trinity_pretrain`` (window and full
attention mixed over grouped K/V heads, per-head QK-norm, output gate, four
norms, sigmoid routing over 128 experts of which this chip holds 16, a
shared expert, the fused head) + AMP AdamW + the Executor; weights made on
the device by the startup program from the seed.  ``correct`` is decided as
the OLMoE cell decides it (``models/olmoe_1b_7b.py``, whose comparisons this
file uses) and, beyond it, by the timed step's own first gradient; all of it
after the window and the memory reading."""

import numpy as np

from .. import harness, trinity_flops
from . import _train
from . import olmoe_1b_7b as _olmoe

make_batch = _olmoe.make_batch


def trinity_config(config):
    from paddle_tpu.models import transformer as T
    a = config["assumed"]
    return T.TrinityConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], d_head=config["head_dim"],
        d_inner=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_experts=a["router_outputs"], top_k=config["num_experts_per_tok"],
        n_shared=config["num_shared_experts"],
        n_dense_layer=config["num_dense_layers"],
        layer_types=config["layer_types"], window=config["sliding_window"],
        score_func=config["score_func"], route_norm=config["route_norm"],
        route_scale=config["route_scale"], rms_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"], mup=config["mup_enabled"],
        n_held=config["num_experts"], expert_offset=a["expert_offset"])


def reference_kw(cfg, q_block=1024):
    return dict(layer_types=tuple(cfg.layer_types), n_head=cfg.n_head,
                n_kv_head=cfg.n_kv_head, d_head=cfg.d_head, top_k=cfg.top_k,
                eps=float(cfg.rms_eps), theta=float(cfg.rope_theta),
                window=int(cfg.window), route_scale=float(cfg.route_scale),
                expert_offset=int(cfg.expert_offset), mup=bool(cfg.mup),
                q_block=int(q_block))


def reference_params(get, cfg):
    """The program's parameters (``get(name)`` -> float32 array) in the
    layout of ``reference/trinity_mini.py``: the fused [d, 2 H dh + 2 Hkv
    dh] projection split into Q, K, V and the gate, the fused gate-up
    weights into their two."""
    dq, dkv = cfg.n_head * cfg.d_head, cfg.n_kv_head * cfg.d_head
    blocks = []
    for i in range(cfg.n_layer):
        p = f"dec_{i}"
        qkv = get(f"{p}.attn.qkv.w")
        blk = {"wq": qkv[:, :dq], "wk": qkv[:, dq:dq + dkv],
               "wv": qkv[:, dq + dkv:dq + 2 * dkv],
               "wg": qkv[:, dq + 2 * dkv:],
               "q_norm_w": get(f"{p}.attn.q_norm.w"),
               "k_norm_w": get(f"{p}.attn.k_norm.w"),
               "wo": get(f"{p}.attn.out.w")}
        for n in ("ln1", "ln2", "ln3", "ln4"):
            blk[f"{n}_w"] = get(f"{p}.{n}.w")
        if i < cfg.n_dense_layer:
            gu, f = get(f"{p}.ffn.gate_up.w"), cfg.d_inner
            blk.update(ffn_gate=gu[:, :f], ffn_up=gu[:, f:],
                       ffn_down=get(f"{p}.ffn.down.w"))
        else:
            gu, f = get(f"{p}.shared.gate_up.w"), cfg.d_expert * cfg.n_shared
            blk.update(shared_gate=gu[:, :f], shared_up=gu[:, f:],
                       shared_down=get(f"{p}.shared.down.w"),
                       router_w=get(f"{p}.moe.router.w"),
                       select_bias=get(f"{p}.moe.select_bias"),
                       gate_w=get(f"{p}.moe.gate.w"),
                       up_w=get(f"{p}.moe.up.w"),
                       down_w=get(f"{p}.moe.down.w"))
        blocks.append(blk)
    return {"wte": get("word_embedding"), "blocks": blocks,
            "final_norm_w": get("final_norm.w"), "head_w": get("lm_out.w")}


def reference_loss(reference, params, feed, cfg, hidden=None, q_block=1024):
    """The reference's loss of ``feed`` and its per-layer top-k choices, one
    sequence at a time; with ``hidden`` [B, T, d] (a program's final-norm
    output) also, per token, its squared distance from the reference's and
    the reference's own squared size (``olmoe_1b_7b.hidden_difference``)."""
    import jax
    import jax.numpy as jnp
    total, tops, off2, size2 = None, [], [], []
    for i in range(feed["src_ids"].shape[0]):
        s = reference.sequence_sums(
            params, jnp.asarray(feed["src_ids"][i:i + 1]),
            jnp.asarray(feed["lm_label"][i:i + 1]),
            **reference_kw(cfg, q_block))
        tops.append(np.asarray(s.pop("top_e")))
        want = s.pop("hidden").astype(jnp.float32)
        if hidden is not None:
            got = jnp.asarray(hidden[i:i + 1], jnp.float32)
            off2.append(np.asarray(
                jnp.sum(jnp.square(got - want), axis=-1), np.float64).ravel())
            size2.append(np.asarray(
                jnp.sum(jnp.square(want), axis=-1), np.float64).ravel())
        total = s if total is None else \
            jax.tree_util.tree_map(jnp.add, total, s)
    return (float(reference.loss_of_sums(total)["loss"]),
            np.concatenate(tops, axis=1),
            (np.concatenate(off2), np.concatenate(size2))
            if hidden is not None else None)


def _forward_program(cfg, seq, scope, amp):
    """The same model, forward only, over the parameters of ``scope``; the
    names to fetch: loss, final-norm output, each expert layer's ExpertLoad
    and TopExperts."""
    import paddle_tpu as pt
    from paddle_tpu.framework import Program, program_guard, scope_guard
    from paddle_tpu.models import transformer as T
    main = Program()
    with scope_guard(scope), program_guard(main, Program()):
        _, parts, loss = T.build_trinity_pretrain(cfg, seq, is_test=True)
    if amp:
        pt.amp.enable(main)
    tops = [op.outputs["TopExperts"][0] for op in main.global_block().ops
            if op.type == "moe_ffn"]
    return main, [loss.name, parts["hidden"].name], \
        [v.name for v in parts["expert_load"]], tops


def _count_loads(cfg, loads):
    """Each fetched ExpertLoad into the program's routed-rows counter (read
    by ``layer_metrics/moe_local_rows_share.py``): fetched with the checks,
    never inside the window."""
    from paddle_tpu.ops import moe_ops
    for load in loads:
        moe_ops.record_expert_load(load, cfg.expert_offset, cfg.n_held)


def build_train(config, traffic, seed, chips, on_chip):
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models import transformer as T

    cfg = trinity_config(config)
    seq = traffic["seq_len"]
    batch = traffic["batch_per_chip"] * chips
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        # the cell runs without recomputation: the step fits (15.97 of 16.9
        # GB, PERF.md).  ISSUE 32's fallback, checkpoints at the five block
        # outputs and nothing finer, is what tools/window_stalls.py
        # --recompute runs to see whether the stalls follow the footprint
        checkpoints = [] if traffic.get("recompute") else None
        _, _, loss = T.build_trinity_pretrain(cfg, seq,
                                              checkpoints=checkpoints)
        adamw = opt.AdamWOptimizer(learning_rate=traffic["learning_rate"],
                                   weight_decay=traffic["weight_decay"])
        stepper = adamw
        if checkpoints:
            stepper = opt.RecomputeOptimizer(adamw)
            stepper._set_checkpoints(checkpoints)
        pt.amp.decorate(stepper).minimize(loss)
        exe = _train.executor(on_chip)
        exe.run(startup, scope=scope, seed=harness.exe_seed(seed))
    rng = _train.rng_of(seed)
    ring = [make_batch(rng, cfg, batch, seq) for _ in range(traffic["ring"])]
    return {
        "exe": exe, "scope": scope, "cfg": cfg,
        "program": _train.maybe_data_parallel(main, loss, chips),
        "loss": loss.name, "ring": ring, "batch": batch,
        "parameters": main.all_parameters(),
        "flops_per_sample": trinity_flops.train_flops_per_sample(config, seq),
        # for the checks after the window: the startup program makes the
        # initial state again from the seed, and a step from zeroed moments
        # leaves (1 - beta1) x its gradient in each parameter's first moment
        "startup": startup, "seed": seed, "beta1": adamw._beta1,
        "moment1": {name: v.name for name, v in
                    adamw._accumulators["moment1"].items()},
    }


def check_before_window(config, traffic, built, seed, reference, chips):
    """Nothing before the window: every comparison of this cell runs after
    it and after the memory reading (:func:`check_first_loss`).  The float32
    forward program beside the step's 8.47 GB of state put the heap's peak at
    9.58 GB before the step's own region was in use, and ``peak_hbm_gb`` read
    the sum of two peaks that never stood together (17.0 GB of a 16.9 GB
    chip; my chip runs, PR 32); the initial state is made again from the seed
    by the startup program, so nothing is kept for later either."""
    return {"ok": True,
            "detail": "no check before the window: the float32 forward "
            "program, the step's own first loss and its first gradient are "
            "compared with the reference after the window and after the "
            "memory reading, from the initial state the startup program "
            "makes again from the seed"}


def _erase(scope, keep=()):
    for name in list(scope.local_var_names()):
        if name not in keep:
            scope.erase(name)


def _initial_state(built):
    """The scope emptied and the startup program run again with the run's
    seed: the state the timed program's first step started from."""
    _erase(built["scope"])
    built["exe"].run(built["startup"], scope=built["scope"],
                     seed=harness.exe_seed(built["seed"]))


def _routing_at_close(built, fwd_amp, feed):
    """The forward-only AMP program over the weights as the window left
    them: each expert layer's ExpertLoad on the first batch of the ring,
    into the program's routed-rows counter.  What the last steps of the
    window, the traced ones, routed: ``moe_local_rows_share`` reads it and
    ``moe_share_experts_roofline`` counts its rows from it."""
    main, _, loads, _ = fwd_amp
    load = [np.asarray(v) for v in built["exe"].run(
        main, feed=feed, fetch_list=loads, scope=built["scope"])]
    _count_loads(built["cfg"], load)
    return load


def _replayed_first_step(built, first_feed):
    """The timed program's first step once more, from the initial state:
    its loss, and every parameter's gradient as the AdamW op saw it, read
    from the first moment (zero before, ``(1 - beta1) g`` after), on the
    host."""
    import jax
    _initial_state(built)
    scope = built["scope"]
    feed = {k: jax.device_put(v) for k, v in first_feed.items()}
    out, = built["exe"].run(built["program"], feed=feed,
                            fetch_list=[built["loss"]], scope=scope)
    scale = 1.0 / (1.0 - built["beta1"])
    grads = {p: np.asarray(scope.find_var(m), np.float32) * scale
             for p, m in built["moment1"].items()}
    return float(np.asarray(out)), grads


def reference_gradient(reference, params, feed, cfg, q_block):
    """``(loss, gradient)`` of the float32 reference on ``feed``, the
    gradient a tree like ``params`` on the host."""
    import jax
    import jax.numpy as jnp
    kw = reference_kw(cfg, q_block)
    want, g_ref = jax.jit(jax.value_and_grad(
        lambda p, ids, labels: reference.loss(p, ids, labels, **kw)))(
            params, jnp.asarray(feed["src_ids"]),
            jnp.asarray(feed["lm_label"]))
    return float(want), jax.tree_util.tree_map(np.asarray, g_ref)


def gradient_difference(g_ref, grads):
    """``|g - g_ref| / |g_ref|`` of every leaf of the reference's parameters
    (``grads``: another computation's, in the reference's layout; the
    selection bias has no gradient and is left out) and over all leaves
    together.  Three kinds of leaf, each with a limit of its own, because
    bf16 activations choose another expert for a third of the tokens in some
    layer: a router's gradient comes through the weights of the chosen
    experts and an expert's through its rows, so both jump with the choice
    and read four to six times what the other leaves read.
    ``{"router": (worst, leaf), "experts": (worst, leaf), "rest": (worst,
    leaf), "all": overall}``."""
    import jax
    off2 = size2 = 0.0
    worst = {"router": (0.0, ""), "experts": (0.0, ""), "rest": (0.0, "")}
    for (path, ref), got in zip(
            jax.tree_util.tree_flatten_with_path(g_ref)[0],
            jax.tree_util.tree_leaves(grads)):
        name = jax.tree_util.keystr(path)
        if "select_bias" in name:
            continue
        ref = np.asarray(ref, np.float64)
        d2 = float(np.sum(np.square(np.asarray(got, np.float64) - ref)))
        r2 = float(np.sum(np.square(ref)))
        off2, size2 = off2 + d2, size2 + r2
        e = (d2 / r2) ** 0.5 if r2 > 0 else float(d2 > 0)
        kind = ("router" if "router_w" in name else "experts"
                if name.endswith(("['gate_w']", "['up_w']", "['down_w']"))
                else "rest")
        if not e <= worst[kind][0]:             # a NaN is the worst
            worst[kind] = (e, name)
    return dict(worst, all=(off2 / max(size2, 1e-300)) ** 0.5)


def check_first_loss(config, traffic, built, first_loss, first_feed,
                     reference):
    """Every comparison of the cell, after the window and after the memory
    reading, each from the initial state the startup program makes again:

    * the routing as the window left it (:func:`_routing_at_close`);
    * the timed AMP AdamW step itself, once more from the initial state: its
      loss is the one it fetched first in this run, and **its gradient**,
      every parameter's, read from the first moment, against ``jax.grad`` of
      the float32 reference on the same 8192-token batch: the window and
      grouped-KV flash backward, ``moe_ffn_grad``'s held path and the
      AdamW op at the timed sizes;
    * the float32 forward program (no AMP, matmuls at ``highest``) on a
      seeded batch of its own against the reference: loss, each token's 8 of
      128 experts, the final-norm output over the tokens whose experts are
      the reference's (``olmoe_1b_7b.check_before_window`` says why the two
      are held apart);
    * the step's first loss against the reference's, and a forward-only AMP
      program's final-norm output, ExpertLoad and experts a token."""
    import jax
    import jax.numpy as jnp
    cfg, scope, exe = built["cfg"], built["scope"], built["exe"]
    tol = config["loss_tolerance"]
    seq, n = traffic["seq_len"], traffic["check_batch"]
    q_block = traffic.get("reference_q_block", 1024)
    fwd_amp = _forward_program(cfg, seq, scope, amp=True)
    load_close = _routing_at_close(built, fwd_amp, first_feed)
    replayed, grads = _replayed_first_step(built, first_feed)

    _initial_state(built)
    _erase(scope, keep={v.name for v in built["parameters"]})

    def initial(name):
        return jnp.asarray(scope.find_var(name), jnp.float32)

    # the float32 forward program on its own batch
    main, heads, loads, tops = _forward_program(cfg, seq, scope, amp=False)
    feed = make_batch(_train.rng_of(built["seed"], 7), cfg, n, seq)
    with jax.default_matmul_precision("highest"):
        got, hidden, *rest = exe.run(
            main, feed=feed, fetch_list=heads + loads + tops, scope=scope)
    want, ref_top, per_token = reference_loss(
        reference, reference_params(initial, cfg), feed, cfg, hidden=hidden,
        q_block=q_block)
    f32 = _olmoe.before_window_verdict(
        tol, np.asarray(got), want, per_token,
        np.stack([np.asarray(v).reshape(-1, cfg.top_k)
                  for v in rest[len(loads):]]), ref_top,
        [np.asarray(v) for v in rest[:len(loads)]], n)
    del hidden, rest, per_token

    # the forward-only AMP program on the step's first batch
    main, heads, loads, tops = fwd_amp
    got, hidden, *rest = exe.run(
        main, feed=first_feed, fetch_list=heads + loads + tops, scope=scope)
    params = reference_params(initial, cfg)
    want, ref_top, per_token = reference_loss(
        reference, params, first_feed, cfg, hidden=hidden, q_block=q_block)
    hidden_off = _olmoe.hidden_difference(per_token)
    load = [np.asarray(v) for v in rest[:len(loads)]]
    top = np.stack([np.asarray(v).reshape(-1, cfg.top_k)
                    for v in rest[len(loads):]])
    del hidden, rest, per_token

    # the step's gradient: the fused weights go, the reference's stay
    _erase(scope)
    want_g, g_ref = reference_gradient(reference, params, first_feed, cfg,
                                       q_block)
    g_off = gradient_difference(
        g_ref, reference_params(lambda name: grads.get(
            name, np.zeros(cfg.n_experts, np.float32)), cfg))
    del g_ref, grads

    rows = top.shape[1] * cfg.top_k
    differ = int(_olmoe.tokens_that_differ(top, ref_top).sum())
    err = _train.rel_err(first_loss, want)
    err_fwd = _train.rel_err(np.asarray(got), first_loss)
    err_replay = _train.rel_err(replayed, first_loss)
    t_loss = tol["first_training_loss_relative"]
    t_grad = {k: tol[f"first_gradient_{k}_relative"]
              for k in ("rest", "experts", "router", "all")}
    dropless = all(int(v.sum()) == rows for v in load + load_close)

    def held(loads_):
        return [int(v[cfg.expert_offset:cfg.expert_offset + cfg.n_held].sum())
                for v in loads_]

    ok = bool(f32["ok"] and np.isfinite(err) and err <= t_loss
              and err_fwd <= t_loss and err_replay <= 1e-6 and dropless
              and hidden_off <= tol["first_hidden_relative"]
              and all(g_off[k][0] <= t_grad[k]
                      for k in ("rest", "experts", "router"))
              and g_off["all"] <= t_grad["all"])
    return {"ok": ok,
            "detail": f"{f32['detail']}; first training loss "
            f"{float(first_loss):.6f} (AMP) vs reference {want:.6f} "
            f"(float32) on {built['batch']} sequences: relative difference "
            f"{err:.2e} (tolerance {t_loss}); the forward-only AMP program "
            f"reads {float(np.asarray(got)):.6f} ({err_fwd:.2e} from the "
            f"step's), its final-norm output {hidden_off:.2e} from the "
            f"reference's (tolerance {tol['first_hidden_relative']}); the "
            f"first step once more from the startup program's state reads "
            f"{replayed:.6f} ({err_replay:.2e} from the run's first), its "
            f"gradient against jax.grad of the reference (loss "
            f"{want_g:.6f}): " + "".join(
                f"worst {k} leaf {g_off[k][0]:.3e} at {g_off[k][1]} "
                f"(tolerance {t_grad[k]}), "
                for k in ("rest", "experts", "router")) +
            f"all leaves together {g_off['all']:.3e} (tolerance "
            f"{t_grad['all']}); "
            f"ExpertLoad sums to {rows} in every layer: {dropless}, rows on "
            f"the {cfg.n_held} held experts {held(load)} at the initial "
            f"weights and {held(load_close)} as the window left them; "
            f"tokens whose top-{cfg.top_k} differs from the reference's: "
            f"{differ} of {top.shape[1]}"}
